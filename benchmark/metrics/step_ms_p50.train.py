"""Reader of the per-layer metric ``step_ms_p50.train`` (see PERF.md, Layers)."""

from benchmark import readers


def read(ctx):
    return readers.step_ms(ctx, 50)
