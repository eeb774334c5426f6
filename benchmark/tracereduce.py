"""From a profiler trace to numbers: device busy time, per-operation time,
collective time that nothing hides, and who owned each idle gap.

``load`` reads an ``.xplane.pb`` with nothing but JAX into plain lists; the
arithmetic below works on those lists, so it can be checked on a small
recorded trace without a chip. Times are nanoseconds on the trace's clock.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
WINDOW_SPAN = "bench.window"
SHORT_GAP_NS = 20_000
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all"
    r"|collective-broadcast|send|recv)")


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path: str, host_spans: set[str]) -> dict:
    """``{"devices": {plane: [(name, start_ns, dur_ns), ...]},
    "host": [(name, start_ns, dur_ns), ...]}``: every event of each TPU
    plane's operations line, and the host events named in ``host_spans``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: dict[str, list] = {}
    host: list = []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[plane.name] = [
                        (e.name, float(e.start_ns), float(e.duration_ns))
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in host_spans:
                        host.append((e.name, float(e.start_ns),
                                     float(e.duration_ns)))
    return {"devices": devices, "host": host}


def window_of(trace: dict) -> tuple[float, float]:
    """The traced window: the driver's ``bench.window`` host span."""
    spans = [(s, s + d) for n, s, d in trace["host"] if n == WINDOW_SPAN]
    if not spans:
        raise ValueError(f"trace has no {WINDOW_SPAN!r} host span")
    return min(s for s, _ in spans), max(e for _, e in spans)


def clip(events: list, lo: float, hi: float) -> list:
    """Events cut to ``[lo, hi]``; those outside are dropped."""
    out = []
    for name, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append((name, a, b - a))
    return out


def busy_intervals(events: list) -> list[tuple[float, float]]:
    """The union of the events' intervals, sorted and disjoint."""
    merged: list[list[float]] = []
    for s, e in sorted((s, s + d) for _, s, d in events):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(a, b) for a, b in merged]


def busy_ns(events: list) -> float:
    return sum(b - a for a, b in busy_intervals(events))


def self_times(events: list) -> dict[str, float]:
    """Nanoseconds per operation name, a parent (a loop, a call) counting
    only the time its children do not cover."""
    total: dict[str, float] = {}
    stack: list[list] = []    # [name, end, self_ns]
    for name, s, d in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= s:
            n, _, self_ns = stack.pop()
            total[n] = total.get(n, 0.0) + self_ns
        if stack:
            stack[-1][2] -= min(d, stack[-1][1] - s)
        stack.append([name, s + d, d])
    for n, _, self_ns in stack:
        total[n] = total.get(n, 0.0) + self_ns
    return total


def op_kind(name: str) -> str:
    """``%all-reduce-start.12 = ...`` and ``all-reduce-start.12`` are both
    ``all-reduce-start``: the operation without its instance number."""
    return re.sub(r"(\.\d+)+$", "", name.split(" = ")[0].lstrip("%"))


def op_key(name: str) -> str:
    """What the breakdown groups by: an operation's kind and, where the
    trace names it by its HLO text, the shape of its result, so that
    ``%fusion.7 = bf16[32768,20,64]{...} fusion(...)`` and ``%fusion.9`` of
    the same shape are one row."""
    shape = re.search(r" = \(?\s*(\w+\[[\d,]*\])", name)
    return op_kind(name) + (f" {shape.group(1)}" if shape else "")


def exposed_collective_ns(events: list) -> float:
    """Time in which a collective (its start, its wait or itself) holds the
    operations line and no other operation runs: on this line operations
    do not overlap, so it is the collectives' self time."""
    return sum(ns for name, ns in self_times(events).items()
               if COLLECTIVE.match(op_kind(name)))


def idle_gaps(events: list, lo: float, hi: float) -> list[tuple[float, float]]:
    gaps, at = [], lo
    for a, b in busy_intervals(events):
        if a > at:
            gaps.append((at, a))
        at = max(at, b)
    if hi > at:
        gaps.append((at, hi))
    return gaps


def gap_owners(gaps: list, host: list) -> dict[str, float]:
    """Idle nanoseconds by the host span that covered most of each gap;
    ``_gaps_under_20_us_`` gathers the short ones, ``_no_span_`` those no
    span covers. The window's own span owns nothing."""
    spans = sorted((s, s + d, n) for n, s, d in host if n != WINDOW_SPAN)
    owned: dict[str, float] = {}
    for a, b in gaps:
        if b - a < SHORT_GAP_NS:
            key = "_gaps_under_20_us_"
        else:
            best, key = 0.0, "_no_span_"
            for s, e, n in spans:
                if s >= b:
                    break
                ov = min(e, b) - max(s, a)
                if ov > best:
                    best, key = ov, n
        owned[key] = owned.get(key, 0.0) + (b - a)
    return owned


def reduce(trace: dict) -> dict:
    """Everything the per-layer readers take from a trace.

    ``busy_s`` is averaged over the devices; ``idle_share`` and
    ``exposed_collective_share`` are of the busiest device; ``ops`` is
    seconds by operation kind and result shape summed over devices, and
    ``custom_calls`` the part of it in custom calls (the program's own
    kernels)."""
    lo, hi = window_of(trace)
    window_ns = hi - lo
    per_dev = {p: clip(ev, lo, hi) for p, ev in trace["devices"].items()}
    if not per_dev:
        raise ValueError("trace has no TPU operations line")
    busy = {p: busy_ns(ev) for p, ev in per_dev.items()}
    busiest = max(busy, key=busy.get)
    ops: dict[str, float] = {}
    custom: dict[str, float] = {}
    for ev in per_dev.values():
        for name, ns in self_times(ev).items():
            k = op_key(name)
            ops[k] = ops.get(k, 0.0) + ns / 1e9
            if " custom-call(" in name:     # a kernel the program brought
                custom[k] = custom.get(k, 0.0) + ns / 1e9
    gaps = idle_gaps(per_dev[busiest], lo, hi)
    owners = gap_owners(gaps, trace["host"])
    top = sorted(ops.items(), key=lambda kv: -kv[1])[:10]
    return {
        "window_s": window_ns / 1e9,
        "busy_s": sum(busy.values()) / len(busy) / 1e9,
        "busy_s_busiest": busy[busiest] / 1e9,
        "idle_share": 1.0 - busy[busiest] / window_ns,
        "exposed_collective_share":
            exposed_collective_ns(per_dev[busiest]) / window_ns,
        "ops": ops,
        "custom_calls": custom,
        "n_devices": len(per_dev),
        "breakdown": {
            "device_ops": [[k, v] for k, v in top],
            "idle_gaps": [[k, v / 1e9] for k, v in
                          sorted(owners.items(), key=lambda kv: -kv[1])[:10]],
        },
    }
