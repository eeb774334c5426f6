"""Reader of the per-layer metric ``init_s`` (see PERF.md, Layers)."""

from benchmark.spanreaders import init_s as read  # noqa: F401
