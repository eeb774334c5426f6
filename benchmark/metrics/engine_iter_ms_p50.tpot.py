"""Reader of the per-layer metric ``engine_iter_ms_p50.tpot`` (see PERF.md, Layers)."""

from benchmark.readers import engine_iter_ms_p50 as read  # noqa: F401
