"""Reader of the per-layer metric ``index_scan_share.tokens`` (see PERF.md,
Layers): the index keys the decoding slots' lane reads and scores over the
cached rows those slots hold — sum of ``index_rows_scored`` over sum of
``kv_rows_live`` on the window's working ``serve.iteration`` spans, in %.
100 is an indexer that scores the live rows and no other; a lane that goes
through a slot's whole page budget reads more. ``None`` where the program's
spans carry no such counter, or where no index key was scored (a model
without an indexer)."""

from benchmark import spanreaders


def read(ctx: dict):
    its = [s.attrs for s in spanreaders.working_iterations(ctx)
           if "index_rows_scored" in s.attrs]
    live = sum(a["kv_rows_live"] for a in its)
    scored = sum(a["index_rows_scored"] for a in its)
    if not live or not scored:
        return None
    return 100.0 * scored / live
