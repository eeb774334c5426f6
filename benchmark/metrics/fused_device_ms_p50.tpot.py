"""Reader of the per-layer metric ``fused_device_ms_p50.tpot`` (see PERF.md, Layers)."""

from benchmark.stepreaders import fused_device_ms_p50 as read  # noqa: F401
