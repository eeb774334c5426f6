"""Reader of the per-layer metric ``dispatch_ms_p50.train`` (see PERF.md, Layers)."""

from benchmark.spanreaders import dispatch_ms_p50 as read  # noqa: F401
