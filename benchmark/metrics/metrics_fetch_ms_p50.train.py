"""Reader of the per-layer metric ``metrics_fetch_ms_p50.train`` (see PERF.md, Layers)."""

from benchmark.spanreaders import metrics_fetch_ms_p50 as read  # noqa: F401
