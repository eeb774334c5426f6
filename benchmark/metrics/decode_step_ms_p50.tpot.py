"""Reader of the per-layer metric ``decode_step_ms_p50.tpot`` (see PERF.md, Layers)."""

from benchmark.spanreaders import decode_step_ms_p50 as read  # noqa: F401
