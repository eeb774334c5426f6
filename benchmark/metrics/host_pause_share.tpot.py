"""Reader of the per-layer metric ``host_pause_share.tpot`` (see PERF.md, Layers)."""

from benchmark.stepreaders import host_pause_share as read  # noqa: F401
