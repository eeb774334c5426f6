"""Span-level event tracing, exported as Chrome/Perfetto trace JSON.

The flight recorder answers "how fast on this host, on average" —
percentiles over a ring of per-step timestamps. It cannot answer "*what*
was the trainer doing at 14:03:07.2, and what was the checkpoint writer
doing at the same instant" — the timeline question every production
straggler/overlap diagnosis starts from (MegaScale runs on exactly this
kind of cross-component trace). This module is that timeline:

- :class:`TraceSession` buffers events in host memory (a bounded list of
  small dicts; no device interaction anywhere) and exports the standard
  Chrome ``trace_event`` JSON object format, which Perfetto / chrome://
  tracing open directly.
- **Tracks** are (pid, tid) lanes: pid is the host (process index), tid a
  named lane within it ("train", "ckpt-writer", "slot 3", ...). Track
  names are emitted as ``M``-phase metadata so the viewer labels them.
- **Spans** are complete events (``ph: "X"`` with ``ts``+``dur``) — one
  event per span instead of a B/E pair, so a crash mid-span loses only
  that span, never unbalances the file.
- **Instant events** (``ph: "i"``) mark point faults (chaos injections,
  request arrivals, finish reasons); **counter samples** (``ph: "C"``)
  plot series like queue depth.

- :func:`span` / :func:`record` / :func:`spanned` are the ONE way the
  program opens a span. A span enters ``jax.profiler.TraceAnnotation``
  (so it lies on the profiler's clock beside the device's operations in
  any ``jax.profiler`` trace, whoever started it), lands in a
  process-wide bounded ring (:func:`host_spans`; always on, like the
  flight recorder's) and is forwarded to a :class:`TraceSession` when
  one is attached.

Overhead contract: "off" means no ``TraceSession`` and no profiler
session — integration points hold ``trace: TraceSession | None`` and
draw no Chrome event when None. The ring and the annotation remain: a
span costs two ``perf_counter`` reads, one annotation object and one
lock-guarded deque append, no device interaction (the transfer-guard
tests keep pinning that). With a session attached it costs one more
lock-guarded list append.

Clock: all timestamps are ``time.perf_counter()`` seconds, the SAME
clock the flight recorder and serving telemetry use — so a latency
derived from trace attrs equals the telemetry's number exactly (pinned
by tests/test_trace.py). Exported ``ts`` are microseconds relative to
the session epoch (Chrome's unit).
"""

from __future__ import annotations

import collections
import contextlib
import itertools
import json
import os
import threading
import time
from typing import Any, Callable, Iterable, Iterator

from jax.profiler import TraceAnnotation

# One JSON object per file (not the bare-array variant): carries the
# displayTimeUnit + metadata alongside the events.
TRACE_FORMAT = "chrome-trace-events"


class TraceSession:
    """In-memory span/event buffer for one process, one file per dump.

    >>> tr = TraceSession(pid=0, process_name="host0 train")
    >>> with tr.span("step", track="train", step=12):
    ...     ...
    >>> tr.instant("chaos.slow_step", track="train", step=12)
    >>> tr.counter("queue_depth", 3, track="engine")
    >>> tr.save("trace.json")

    Thread-safe: the checkpoint writer thread and data-loader threads
    append concurrently with the step loop (one lock around the buffer).
    The buffer is bounded by ``max_events``: once full, new events are
    dropped and counted (``dropped_events`` in the exported metadata) —
    a forensic trace must never OOM the host it is diagnosing.
    """

    def __init__(self, *, pid: int = 0, process_name: str | None = None,
                 max_events: int = 500_000):
        if max_events < 1:
            raise ValueError(f"max_events must be >= 1, got {max_events}")
        self.pid = int(pid)
        self.process_name = process_name or f"process {pid}"
        self.max_events = int(max_events)
        self._t0 = time.perf_counter()
        self._wall_t0 = time.time()
        self._lock = threading.Lock()
        self._events: list[dict[str, Any]] = []
        self._tracks: dict[str, int] = {}
        self._dropped = 0

    # -- clock ---------------------------------------------------------------
    def now(self) -> float:
        """The session's clock (``perf_counter`` seconds) — integration
        points that already hold a timestamp from the same clock pass it
        straight through instead of re-reading."""
        return time.perf_counter()

    def _ts(self, t: float) -> float:
        """perf_counter seconds → Chrome µs (relative to session epoch)."""
        return (t - self._t0) * 1e6

    # -- tracks --------------------------------------------------------------
    def track(self, name: str) -> int:
        """The tid for ``name`` (registered on first use)."""
        with self._lock:
            tid = self._tracks.get(name)
            if tid is None:
                tid = len(self._tracks)
                self._tracks[name] = tid
            return tid

    # -- emission ------------------------------------------------------------
    def _append(self, ev: dict[str, Any]) -> None:
        with self._lock:
            if len(self._events) >= self.max_events:
                self._dropped += 1
                return
            self._events.append(ev)

    def complete(self, name: str, t_start: float, t_end: float, *,
                 track: str = "main", **attrs: Any) -> None:
        """One complete span from explicit ``perf_counter`` endpoints —
        for retroactive spans whose start predates the emission point
        (e.g. a request's queueing span, emitted when it seats)."""
        ev: dict[str, Any] = {
            "name": name, "ph": "X", "ts": self._ts(t_start),
            "dur": max((t_end - t_start) * 1e6, 0.0),
            "pid": self.pid, "tid": self.track(track),
        }
        if attrs:
            ev["args"] = attrs
        self._append(ev)

    @contextlib.contextmanager
    def span(self, name: str, *, track: str = "main", **attrs: Any):
        """Context manager: one complete span around the body."""
        t_start = time.perf_counter()
        try:
            yield
        finally:
            self.complete(name, t_start, time.perf_counter(),
                          track=track, **attrs)

    def instant(self, name: str, *, track: str = "main",
                t: float | None = None, **attrs: Any) -> None:
        ev: dict[str, Any] = {
            "name": name, "ph": "i",
            "ts": self._ts(time.perf_counter() if t is None else t),
            "pid": self.pid, "tid": self.track(track), "s": "t",
        }
        if attrs:
            ev["args"] = attrs
        self._append(ev)

    def counter(self, name: str, value: float, *, track: str = "counters",
                t: float | None = None) -> None:
        self._append({
            "name": name, "ph": "C",
            "ts": self._ts(time.perf_counter() if t is None else t),
            "pid": self.pid, "tid": self.track(track),
            "args": {name: float(value)},
        })

    # -- export --------------------------------------------------------------
    def __len__(self) -> int:
        with self._lock:
            return len(self._events)

    def to_json(self) -> dict[str, Any]:
        """The Chrome trace object. Events are sorted by ``ts`` so every
        (pid, tid) subsequence is timestamp-monotonic — a validity
        property tests (and some viewers) rely on."""
        with self._lock:
            events = sorted(self._events, key=lambda e: e["ts"])
            tracks = dict(self._tracks)
            dropped = self._dropped
        meta: list[dict[str, Any]] = [{
            "name": "process_name", "ph": "M", "pid": self.pid, "tid": 0,
            "ts": 0.0, "args": {"name": self.process_name},
        }]
        for name, tid in sorted(tracks.items(), key=lambda kv: kv[1]):
            meta.append({
                "name": "thread_name", "ph": "M", "pid": self.pid,
                "tid": tid, "ts": 0.0, "args": {"name": name},
            })
        return {
            "traceEvents": meta + events,
            "displayTimeUnit": "ms",
            "otherData": {
                "format": TRACE_FORMAT,
                "wall_time_origin": self._wall_t0,
                "dropped_events": dropped,
            },
        }

    def save(self, path: str) -> str:
        """Write the trace to ``path`` (dirs created, atomic replace so a
        crash mid-write never leaves a torn file); returns ``path``."""
        d = os.path.dirname(os.path.abspath(path))
        os.makedirs(d, exist_ok=True)
        tmp = path + ".tmp"
        with open(tmp, "w") as fh:
            json.dump(self.to_json(), fh, allow_nan=False)  # graftlint: disable=scrape-safety -- json.dump serializes to a file handle; it mutates no recorder (the rule's name list means telemetry dump hooks)
        os.replace(tmp, path)
        return path

    def checkpoint(self, path: str) -> str:
        """``save()`` under a collision-free name for HANDLER call
        graphs. The serving frontend persists its trace from the
        request thread at the two durability points (before the first
        streamed byte, after the terminal frame) so a SIGKILLed
        replica's spans survive for the fleet-timeline merge
        (tools/fleet_trace.py). graftlint resolves a bare-name
        ``.save()`` from a handler root against every ``save`` in the
        repo — the async checkpoint writer's included, which really
        does read devices — so the handler-reachable spelling gets its
        own name and resolves only here."""
        return self.save(path)


# -- the program's spans ------------------------------------------------------
# Ring capacity: a 40 s window of the fastest cell (~6 engine iterations
# or ~8 training steps a second, <= 10 spans each) plus its set-up and
# pre-roll fits four times over; at ~0.4 KB a record the ring tops out
# near 13 MB.
RING_SPANS = 32768

_ring: collections.deque = collections.deque(maxlen=RING_SPANS)
_ring_lock = threading.Lock()
_ids = itertools.count(1)
_local = threading.local()


def _stack() -> list:
    try:
        return _local.stack
    except AttributeError:
        _local.stack = []
        return _local.stack


class Span(contextlib.ContextDecorator):
    """One span of the program: the context manager :func:`span` returns
    and, once closed, the record :func:`host_spans` hands out.

    ``id``/``parent`` nest per thread; ``t0``/``t1`` are
    ``perf_counter`` seconds; ``key`` names the unit of work (iteration,
    step, request uid) and ``attrs`` is a small dict the body may still
    fill (``sp.attrs["program"] = "fused"``) until the span closes.
    ``key``, ``session`` and ``track`` left ``None`` are the enclosing
    span's on this thread, so the phases of one iteration share them
    without repeating them.
    """

    __slots__ = ("name", "key", "attrs", "id", "parent", "t0", "t1",
                 "thread", "session", "track", "_annotation")

    def __init__(self, name: str, key: Any, session: "TraceSession | None",
                 track: str | None, attrs: dict[str, Any]):
        self.name = name
        self.key = key
        self.session = session
        self.track = track
        self.attrs = attrs
        self.id = self.parent = self.t0 = self.t1 = self.thread = None

    def _recreate_cm(self):     # as a decorator: a fresh span per call
        return Span(self.name, self.key, self.session, self.track,
                    dict(self.attrs))

    def _adopt(self, stack: list) -> None:
        """Take id, thread and what the enclosing span hands down."""
        self.id = next(_ids)
        self.thread = threading.current_thread().name
        if stack:
            outer = stack[-1]
            self.parent = outer.id
            if self.key is None:
                self.key = outer.key
            if self.session is None:
                self.session = outer.session
            if self.track is None:
                self.track = outer.track

    def _close(self) -> None:
        with _ring_lock:
            _ring.append(self)
        if self.session is not None:
            args = (self.attrs if self.key is None
                    else {**self.attrs, "key": self.key})
            self.session.complete(self.name, self.t0, self.t1,
                                  track=self.track or self.thread, **args)
            self.session = None     # the ring must not keep a session alive

    def __enter__(self) -> "Span":
        stack = _stack()
        self._adopt(stack)
        stack.append(self)
        self._annotation = TraceAnnotation(self.name)
        self._annotation.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.t1 = time.perf_counter()
        self._annotation.__exit__(*exc)
        self._annotation = None
        _stack().pop()
        self._close()

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


def span(name: str, *, key: Any = None,
         session: "TraceSession | None" = None, track: str | None = None,
         **attrs: Any) -> Span:
    """Open a span around a ``with`` body (or, as a decorator, around
    every call of a function)."""
    return Span(name, key, session, track, attrs)


def record(name: str, t0: float, t1: float, *, key: Any = None,
           session: "TraceSession | None" = None, track: str | None = None,
           **attrs: Any) -> Span:
    """A span after the fact, from ``perf_counter`` endpoints the caller
    already holds (a request's queueing, known when it seats). It has no
    live extent, so it enters no profiler annotation."""
    sp = Span(name, key, session, track, attrs)
    sp._adopt(_stack())
    sp.t0, sp.t1 = t0, t1
    sp._close()
    return sp


def spanned(iterable: Iterable, name: str, *,
            key: Callable[[], Any] | None = None, **kw: Any) -> Iterator:
    """``iterable``, with every ``next()`` (the last, which ends it,
    too) inside a span: the wait of a loop on its source. ``key()`` is
    read before each wait; without it the key is the item's ordinal."""
    it = iter(iterable)
    for n in itertools.count():
        with span(name, key=n if key is None else key(), **kw):
            try:
                item = next(it)
            except StopIteration:
                return
        yield item


def host_spans(since: float | None = None,
               until: float | None = None) -> list[Span]:
    """The ring's closed spans, oldest first; with bounds, those that lie
    wholly inside ``[since, until]`` (``perf_counter`` seconds)."""
    with _ring_lock:
        spans = list(_ring)
    if since is not None:
        spans = [s for s in spans if s.t0 >= since]
    if until is not None:
        spans = [s for s in spans if s.t1 <= until]
    return spans


def session_for_run(cfg, *, default_dir: str, component: str = "train"
                    ) -> tuple["TraceSession | None", str | None]:
    """``(session, output_path)`` from a :class:`~distributed_training_
    tpu.config.TraceConfig` — ``(None, None)`` when disabled, which is
    what keeps every integration point span-free by default.

    The pid is the jax process index (one trace file per host; a
    multihost run names them ``trace_p<idx>.json`` so hosts never race
    on one file); ``cfg.dir=None`` resolves under ``default_dir`` (the
    trainers pass their flight-forensics dir).
    """
    if not cfg.enabled:
        return None, None
    import jax

    pidx = jax.process_index()
    session = TraceSession(pid=pidx,
                           process_name=f"host {pidx} {component}",
                           max_events=cfg.max_events)
    d = cfg.dir or os.path.join(default_dir, "trace")
    fname = ("trace.json" if jax.process_count() == 1
             else f"trace_p{pidx}.json")
    return session, os.path.join(d, fname)


def session_for_cli(enabled: bool, trace_dir: str, component: str
                    ) -> tuple["TraceSession | None", str | None]:
    """``(session, output_path)`` for the serving CLIs' ``--trace`` /
    ``--trace-dir`` flags — the flag-shaped twin of
    :func:`session_for_run` (which takes the trainers' TraceConfig).
    Routes through :class:`~distributed_training_tpu.config.TraceConfig`
    so its validation and ``max_events`` default apply to serving traces
    too; the file is named ``<component>_trace.json``.
    """
    if not enabled:
        return None, None
    from distributed_training_tpu.config import TraceConfig

    cfg = TraceConfig(enabled=True, dir=trace_dir)
    session = TraceSession(process_name=component,
                           max_events=cfg.max_events)
    return session, os.path.join(cfg.dir, f"{component}_trace.json")


def fleet_session(component: str, trace_dir: str | None,
                  *, max_events: int | None = None
                  ) -> tuple["TraceSession | None", str | None]:
    """``(session, output_path)`` for one fleet participant (a serve_net
    replica or the router front door) — ``(None, None)`` when
    ``trace_dir`` is falsy, keeping every integration point span-free
    by default.

    Fleet traces differ from the single-process CLI traces in two ways
    that :mod:`tools.fleet_trace` depends on: the session pid is the
    REAL ``os.getpid()`` (a SIGKILLed replica and its supervisor-spawned
    successor must land on distinct Perfetto tracks — a replica *index*
    would fold both incarnations onto one), and the file is named
    ``<component>_pid<pid>_trace.json`` so a restart never clobbers the
    dead process's file. Clock alignment across the files rides each
    session's ``wall_time_origin`` plus the hop handshake instants the
    door/replica stamp (``hop.send``/``hop.recv``).
    """
    if not trace_dir:
        return None, None
    from distributed_training_tpu.config import TraceConfig

    cfg = TraceConfig(enabled=True, dir=trace_dir,
                      **({} if max_events is None
                         else {"max_events": max_events}))
    pid = os.getpid()
    session = TraceSession(pid=pid, process_name=f"{component} pid {pid}",
                           max_events=cfg.max_events)
    return session, os.path.join(
        cfg.dir, f"{component}_pid{pid}_trace.json")


def load_trace(path: str) -> dict[str, Any]:
    """Load + structurally validate a trace file written by
    :meth:`TraceSession.save` (or any Chrome trace object). Raises
    ``ValueError`` naming the first malformed event (path-free — the
    report tool prefixes the path in its one-line error)."""
    with open(path) as fh:
        obj = json.load(fh)
    if not isinstance(obj, dict) or "traceEvents" not in obj:
        raise ValueError("not a Chrome trace object "
                         "(missing 'traceEvents')")
    events = obj["traceEvents"]
    if not isinstance(events, list):
        raise ValueError("'traceEvents' is not a list")
    for i, ev in enumerate(events):
        for key in ("name", "ph", "ts", "pid", "tid"):
            if key not in ev:
                raise ValueError(
                    f"event {i} missing required key {key!r}: {ev}")
    return obj
