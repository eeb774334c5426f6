"""Reader of the per-layer metric ``slot_occupancy.tokens`` (see PERF.md, Layers)."""

from benchmark.readers import slot_occupancy as read  # noqa: F401
