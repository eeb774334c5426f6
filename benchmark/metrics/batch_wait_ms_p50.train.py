"""Reader of the per-layer metric ``batch_wait_ms_p50.train`` (see PERF.md, Layers)."""

from benchmark.spanreaders import batch_wait_ms_p50 as read  # noqa: F401
