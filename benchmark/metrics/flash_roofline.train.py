"""Reader of the per-layer metric ``flash_roofline.train`` (see PERF.md, Layers)."""

from benchmark.readers import flash_roofline as read  # noqa: F401
