"""Reader of the per-layer metric ``host_gap_share.tpot`` (see PERF.md, Layers)."""

from benchmark.stepreaders import host_gap_share as read  # noqa: F401
