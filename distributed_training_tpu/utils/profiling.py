"""Profiling hooks.

The reference pins NVTX/DLProf wheels but never imports them, and ships
DeepSpeed's ``wall_clock_breakdown`` flag turned off
(``resnet/deepspeed/deepspeed_train.py:209``; SURVEY.md §5 "Tracing").
TPU-native equivalents:

- ``jax.profiler`` traces (TensorBoard trace viewer) via :func:`trace`;
- :class:`WallClock` — a working ``wall_clock_breakdown``: wall-time split
  into data / step / logging phases per epoch.
"""

from __future__ import annotations

import contextlib
from collections import defaultdict

import jax

from distributed_training_tpu.observability import trace as trace_lib


@contextlib.contextmanager
def trace(log_dir: str | None):
    """Capture a jax.profiler trace into ``log_dir`` (no-op when None)."""
    if not log_dir:
        yield
        return
    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


class WallClock:
    """Phase timer: ``with clock.phase('data'): ...``; report per epoch.

    Attribution is EXCLUSIVE: entering a nested phase pauses the outer
    one (e.g. the eval loop's internal 'data' staging accrues to 'data',
    not double-counted under 'eval'), so the totals partition the tracked
    wall-time — which is what lets the flight recorder's goodput read
    them as fractions that sum to 1 (``observability/flight_recorder.py``).

    Every phase is also one ``train.<name>`` span of the program
    (``observability/trace.py::span``: entry → exit, INCLUSIVE of nested
    phases — the timeline wants the enclosing extent; exclusivity is the
    totals' concern), forwarded onto ``track`` of the ``trace`` session
    when one is attached — which is how both trainers get their
    step/eval/ckpt spans without touching a single phase call site.
    """

    def __init__(self, enabled: bool = False, *, trace=None,
                 track: str = "train"):
        self.enabled = enabled
        self.trace = trace
        self.track = track
        self.totals: dict[str, float] = defaultdict(float)
        # Run-lifetime totals: ``report()`` clears ``totals`` per epoch,
        # but the flight recorder's goodput wants the whole run.
        self.lifetime: dict[str, float] = defaultdict(float)
        self._stack: list[list] = []  # [name, segment_start] frames

    def _accrue(self, name: str, dt: float) -> None:
        self.totals[name] += dt
        self.lifetime[name] += dt

    @property
    def current_phase(self) -> str | None:
        """The innermost open phase name, or None outside any phase.
        Read lock-free from other threads (the /healthz endpoint): the
        stack only ever gains/loses whole frames under the GIL, and a
        transiently stale answer is fine for a liveness probe."""
        try:
            return self._stack[-1][0]
        except IndexError:  # popped between the probe's check and read
            return None

    @contextlib.contextmanager
    def phase(self, name: str):
        if not self.enabled:
            yield
            return
        sp = trace_lib.span("train." + name, session=self.trace,
                            track=self.track)
        try:
            with sp:
                now = sp.t0
                if self._stack:  # pause the outer phase
                    outer = self._stack[-1]
                    self._accrue(outer[0], now - outer[1])
                self._stack.append([name, now])
                yield
        finally:
            now = sp.t1
            frame = self._stack.pop()
            self._accrue(frame[0], now - frame[1])
            if self._stack:  # resume the outer phase's segment
                self._stack[-1][1] = now

    def snapshot(self) -> dict[str, float]:
        """Run-lifetime phase totals, never cleared (the flight
        recorder's goodput reads this at dump time; ``report`` keeps its
        clearing per-epoch semantics)."""
        return dict(self.lifetime)

    def report(self) -> dict[str, float]:
        out = dict(self.totals)
        self.totals.clear()
        return out
