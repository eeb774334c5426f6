"""Reader of the per-layer metric ``fused_iter_share.tpot`` (see PERF.md, Layers)."""

from benchmark.spanreaders import fused_iter_share as read  # noqa: F401
