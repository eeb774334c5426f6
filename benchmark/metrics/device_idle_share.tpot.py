"""Reader of the per-layer metric ``device_idle_share.tpot`` (see PERF.md, Layers)."""

from benchmark.readers import device_idle_share as read  # noqa: F401
