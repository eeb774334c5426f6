"""Reader of the per-layer metric ``run_ahead_share.tokens`` (see PERF.md,
Layers): of the window's working ``serve.iteration`` spans, the share whose
device step had its successor launched before its tokens were fetched — the
span's ``ahead`` counter — in %. 100 is an engine that is always one step
ahead of its host; 0 one that launches, waits and commits in turn. ``None``
where the program's spans carry no such counter."""

from benchmark import spanreaders


def read(ctx: dict):
    ahead = [s.attrs["ahead"] for s in spanreaders.working_iterations(ctx)
             if "ahead" in s.attrs]
    if not ahead:
        return None
    return 100.0 * sum(ahead) / len(ahead)
