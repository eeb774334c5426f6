"""Reader of the per-layer metric ``ttft_p90_ms.tpot`` (see PERF.md, Layers)."""

from benchmark import readers


def read(ctx):
    return readers.ttft_ms(ctx, 90)
