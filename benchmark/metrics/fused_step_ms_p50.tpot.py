"""Reader of the per-layer metric ``fused_step_ms_p50.tpot`` (see PERF.md, Layers)."""

from benchmark.spanreaders import fused_step_ms_p50 as read  # noqa: F401
