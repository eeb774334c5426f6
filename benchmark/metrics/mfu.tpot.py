"""Reader of the per-layer metric ``mfu.tpot`` (see PERF.md, Layers)."""

from benchmark.readers import serve_mfu as read  # noqa: F401
