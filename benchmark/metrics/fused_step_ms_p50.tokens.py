"""Reader of the per-layer metric ``fused_step_ms_p50.tokens`` (see PERF.md, Layers)."""

from benchmark.spanreaders import fused_step_ms_p50 as read  # noqa: F401
