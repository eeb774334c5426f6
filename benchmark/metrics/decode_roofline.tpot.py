"""Reader of the per-layer metric ``decode_roofline.tpot`` (see PERF.md, Layers)."""

from benchmark.readers import decode_roofline as read  # noqa: F401
