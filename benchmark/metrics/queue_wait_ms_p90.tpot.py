"""Reader of the per-layer metric ``queue_wait_ms_p90.tpot`` (see PERF.md, Layers)."""

from benchmark.spanreaders import queue_wait_ms_p90 as read  # noqa: F401
