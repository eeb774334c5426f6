"""Reader of the per-layer metric ``prefill_ms_p90.tpot`` (see PERF.md, Layers)."""

from benchmark.spanreaders import prefill_ms_p90 as read  # noqa: F401
