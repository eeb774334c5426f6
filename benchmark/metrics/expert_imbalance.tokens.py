"""Reader of the per-layer metric ``expert_imbalance.tokens`` (see PERF.md,
Layers): the busiest held expert's rows over the mean held expert's — sum of
``expert_rows_max`` x experts held over sum of ``expert_rows`` on the
window's working ``serve.iteration`` spans, in %. 100 is an even load; the
busiest expert sets an expert layer's time under expert parallelism.
``None`` where the program's spans carry no such counters."""

from benchmark import spanreaders


def read(ctx: dict):
    its = [s.attrs for s in spanreaders.working_iterations(ctx)
           if "expert_rows" in s.attrs]
    rows = sum(a["expert_rows"] for a in its)
    if not rows:
        return None
    held = int(ctx["config"]["n_routed_experts"])
    return 100.0 * held * sum(a["expert_rows_max"] for a in its) / rows
