"""Per-layer readers of the device steps the serving engine stamps.

Each working ``serve.iteration`` span carries the stamps of the step it
delivered (``serving/engine.py::_DeviceStep``), on the ring's
``perf_counter`` clock: ``launch_t0`` / ``launch_t1``, when the host's
launch of it began and returned, and ``landed_lo`` / ``landed_hi``, a
bracket around the moment its outputs were ready — closed (``lo == hi``)
where the fetch had to wait for them, whose return is the landing.

A step's landing is ``landed_hi``: the first look that found it ready, or
the wait's return. Its device time is its landing less its start, and its
start is the later of its launch's end and its predecessor's landing (the
step delivered by the previous working iteration). On a TPU v5 lite the
device's first operation of a step follows ``launch_t1``, not ``launch_t0``
(PERF.md, Layers). The host cannot place a landing inside an open bracket:
most fall inside the next launch's jitted call, ~1.5 ms long, nearer its end
than its middle. So a step's device time is read only where both ends are
pinned — its own landing waited for, and its start either a waited landing
before it or its launch's end with the step before already seen landed.

The readers go through :mod:`benchmark.spanreaders`, so they return
``None`` off a TPU, and they return ``None`` where the spans carry no such
stamps (a program from before them). They read the part of the window the
ring still holds.
"""

from __future__ import annotations

from benchmark import servestats, spanreaders, tracereduce

LAUNCHED = "launch_t1"
PAUSES = ("proc.gc", "proc.compile")


def _pinned(a: dict, before: dict):
    """Landing − start of the step ``a`` (s) where both are pinned."""
    if a["landed_lo"] != a["landed_hi"]:
        return None
    launched = a[LAUNCHED]
    if before["landed_lo"] == before["landed_hi"]:
        start = max(launched, before["landed_hi"])
    elif before["landed_hi"] <= launched:
        start = launched
    else:
        return None
    return a["landed_hi"] - start


def steps(ctx: dict) -> list[dict]:
    """The window's delivered steps after the first, in order: ``program``,
    ``device`` (s, ``None`` where not pinned), ``span`` (its landing less
    the one before) and ``adjacent`` (no idle iteration, when nothing was
    live, lies between the two)."""
    out: list[dict] = []
    before, adjacent = None, False
    for s in spanreaders.spans(ctx, "serve.iteration"):
        a = s.attrs
        if a.get("program") == "idle":
            adjacent = False
            continue
        if "landed_hi" not in a:
            return []
        if before is not None:
            out.append({"program": a["program"], "adjacent": adjacent,
                        "device": _pinned(a, before),
                        "span": a["landed_hi"] - before["landed_hi"]})
        before, adjacent = a, True
    return out


def _device_s(found: list[dict]) -> dict[str, list[float]]:
    by: dict[str, list[float]] = {}
    for st in found:
        if st["device"] is not None:
            by.setdefault(st["program"], []).append(st["device"])
    return by


def device_ms_p50(ctx: dict, program: str):
    """p50 of the pinned device times of one program's steps, ms."""
    s = _device_s(steps(ctx)).get(program)
    return servestats.percentile([x * 1e3 for x in s], 50) if s else None


def decode_device_ms_p50(ctx: dict):
    return device_ms_p50(ctx, "decode")


def fused_device_ms_p50(ctx: dict):
    return device_ms_p50(ctx, "fused")


def host_gap_share(ctx: dict):
    """Of the time from each landing to the next, over steps with no idle
    iteration between them, the share in which the device waited for the
    host's launch, in %: the idle the host caused while work was live. A
    step's gap is that time less its device time where it is pinned, else
    less its program's p50, and at least 0 — a gap's near end is a landing
    the host seldom pins."""
    found = steps(ctx)
    typical = {p: servestats.percentile(v, 50)
               for p, v in _device_s(found).items()}
    counted = [st for st in found if st["adjacent"]]
    if not counted or any(st["device"] is None
                          and st["program"] not in typical
                          for st in counted):
        return None
    spanned = sum(st["span"] for st in counted)
    if spanned <= 0:
        return None
    gaps = sum(max(0.0, st["span"] - (typical[st["program"]]
                                      if st["device"] is None
                                      else st["device"]))
               for st in counted)
    return 100.0 * gaps / spanned


def host_pause_share(ctx: dict):
    """``proc.gc`` and ``proc.compile`` time (their union) from the
    window's first working iteration to its last, as a share of that time,
    in %."""
    its = spanreaders.working_iterations(ctx)
    if not its or "landed_hi" not in its[0].attrs:
        return None
    lo, hi = its[0].t0, its[-1].t1
    if hi <= lo:
        return None
    paused = [(s.name, s.t0, s.t1 - s.t0) for name in PAUSES
              for s in spanreaders.spans(ctx, name)
              if lo <= s.t0 and s.t1 <= hi]
    return 100.0 * tracereduce.busy_ns(paused) / (hi - lo)
