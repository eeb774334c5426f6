"""Reader of the per-layer metric ``serve_dispatch_ms_p50.tpot`` (see PERF.md, Layers)."""

from benchmark.spanreaders import serve_dispatch_ms_p50 as read  # noqa: F401
