"""Host spans and the profiler, for the traced run only.

``span(name)`` is a ``jax.profiler.TraceAnnotation`` while a trace is being
taken and nothing otherwise, so the untraced run pays nothing.
"""

from __future__ import annotations

import contextlib
import shutil

_active = False
SPANS: set[str] = set()


def span(name: str):
    SPANS.add(name)
    if not _active:
        return contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation(name)


def start(trace_dir: str) -> None:
    global _active
    import jax

    shutil.rmtree(trace_dir, ignore_errors=True)
    jax.profiler.start_trace(trace_dir)
    _active = True


def stop() -> None:
    global _active
    import jax

    _active = False
    jax.profiler.stop_trace()

