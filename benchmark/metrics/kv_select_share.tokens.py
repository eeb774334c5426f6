"""Reader of the per-layer metric ``kv_select_share.tokens`` (see PERF.md,
Layers): of the cached rows the decoding slots hold, the share their queries
read — sum of ``kv_rows_selected`` over sum of ``kv_rows_live`` on the
window's working ``serve.iteration`` spans, in %. 100 is dense attention;
a model with a learned selection reads less. ``None`` where the program's
spans carry no such counters."""

from benchmark import spanreaders


def read(ctx: dict):
    its = [s.attrs for s in spanreaders.working_iterations(ctx)
           if "kv_rows_selected" in s.attrs]
    live = sum(a["kv_rows_live"] for a in its)
    if not live:
        return None
    return 100.0 * sum(a["kv_rows_selected"] for a in its) / live
