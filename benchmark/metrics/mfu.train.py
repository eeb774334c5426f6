"""Reader of the per-layer metric ``mfu.train`` (see PERF.md, Layers)."""

from benchmark.readers import train_mfu as read  # noqa: F401
