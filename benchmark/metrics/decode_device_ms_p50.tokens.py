"""Reader of the per-layer metric ``decode_device_ms_p50.tokens`` (see PERF.md, Layers)."""

from benchmark.stepreaders import decode_device_ms_p50 as read  # noqa: F401
