"""Reader of the per-layer metric ``hbm_peak_gb.tpot`` (see PERF.md, Layers)."""

from benchmark.readers import hbm_peak_gb as read  # noqa: F401
