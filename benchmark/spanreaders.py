"""Per-layer readers of the program's own spans.

The program keeps every span it opens (``serve.*``, ``train.*``, ``data.*``,
``setup.*``) in a ring on ``time.perf_counter()``, the clock of the drivers'
windows; ``observability/trace.py::host_spans`` hands them out. Each metric's
file under ``metrics/`` names one function here. A reader returns ``None``,
and the metric is left out of the line, when the program has no such ring
(a commit before the spans), when no span of its name lies inside the
window, or when the run was not on a TPU: a time from a CPU run is not
written under a device metric's name.
"""

from __future__ import annotations

from benchmark import servestats


def _ring(device: dict):
    """``host_spans`` of the program under test, or ``None``."""
    if device["platform"] != "tpu":
        return None
    try:
        from distributed_training_tpu.observability import trace
    except ImportError:
        return None
    return getattr(trace, "host_spans", None)


def window_of(ctx: dict):
    """The window on the spans' clock. The serving driver returns its
    bounds; the training driver returns only the traced part's, whose end
    is the window's."""
    w = ctx["window"]
    if "open_t" in w:
        return w["open_t"], w["close_t"]
    if w.get("traced"):
        return w["traced"][1] - ctx["seconds"], w["traced"][1]
    return None


def spans(ctx: dict, name: str, **attrs) -> list:
    """The window's spans of one name whose attributes match."""
    read, bounds = _ring(ctx["device"]), window_of(ctx)
    if read is None or bounds is None:
        return []
    return [s for s in read(*bounds) if s.name == name
            and all(s.attrs.get(k) == v for k, v in attrs.items())]


def p_ms(found: list, q: float):
    """A percentile of the spans' durations in ms; ``None`` of nothing."""
    if not found:
        return None
    return servestats.percentile([(s.t1 - s.t0) * 1e3 for s in found], q)


def decode_step_ms_p50(ctx: dict):
    return p_ms(spans(ctx, "serve.device_step", program="decode"), 50)


def fused_step_ms_p50(ctx: dict):
    return p_ms(spans(ctx, "serve.device_step", program="fused"), 50)


def working_iterations(ctx: dict) -> list:
    return [s for s in spans(ctx, "serve.iteration")
            if s.attrs.get("program") != "idle"]


def fused_iter_share(ctx: dict):
    """Working iterations that carried a prefill chunk, in %."""
    its = working_iterations(ctx)
    if not its:
        return None
    fused = sum(1 for s in its if s.attrs.get("program") == "fused")
    return 100.0 * fused / len(its)


def serve_host_ms_p50(ctx: dict):
    """Per working iteration, what is not the wait for the device's tokens:
    the iteration's span less its ``serve.token_wait``, which share a key."""
    waited = {s.key: s.t1 - s.t0 for s in spans(ctx, "serve.token_wait")}
    host = [(s.t1 - s.t0 - waited[s.key]) * 1e3
            for s in working_iterations(ctx) if s.key in waited]
    return servestats.percentile(host, 50) if host else None


def serve_dispatch_ms_p50(ctx: dict):
    return p_ms(spans(ctx, "serve.dispatch"), 50)


def queue_wait_ms_p90(ctx: dict):
    """Arrival to seat, of requests that arrived and were seated inside
    the window."""
    return p_ms(spans(ctx, "serve.queued"), 90)


def prefill_ms_p90(ctx: dict):
    """Seat to first token (the chunk lane's wait included), of the
    requests ``queue_wait_ms_p90`` counts."""
    counted = {s.key for s in spans(ctx, "serve.queued")}
    return p_ms([s for s in spans(ctx, "serve.prefill")
                 if s.key in counted], 90)


def batch_wait_ms_p50(ctx: dict):
    return p_ms(spans(ctx, "train.batch_wait"), 50)


def dispatch_ms_p50(ctx: dict):
    return p_ms(spans(ctx, "train.dispatch"), 50)


def metrics_fetch_ms_p50(ctx: dict):
    return p_ms(spans(ctx, "train.metrics_fetch"), 50)


def constructor_s(device: dict):
    """Seconds in the constructor of the trainer or of the engine: the
    last such span in the ring. The harness calls this when set-up ends: a
    serving window opens more spans than the ring holds (32 768, some
    3 000 iterations), so by its close the constructor's has gone."""
    read = _ring(device)
    if read is None:
        return None
    found = [s for s in read()
             if s.name in ("setup.trainer_init", "setup.engine_init")]
    return found[-1].t1 - found[-1].t0 if found else None


def init_s(ctx: dict):
    """What :func:`constructor_s` read when set-up ended."""
    return ctx.get("init_s")
