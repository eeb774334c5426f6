"""Reader of the per-layer metric ``ttft_p50_ms.tpot`` (see PERF.md, Layers)."""

from benchmark import readers


def read(ctx):
    return readers.ttft_ms(ctx, 50)
