"""Reader of the per-layer metric ``serve_host_ms_p50.tokens`` (see PERF.md, Layers)."""

from benchmark.spanreaders import serve_host_ms_p50 as read  # noqa: F401
