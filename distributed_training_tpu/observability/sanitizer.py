"""Compiled-program sanitizer: pin the XLA compilation inventory.

The whole static-shape discipline (docs/ARCHITECTURE.md, the serving
engine's "masks, never shapes" rule) exists so each hot loop runs a
KNOWN, FIXED set of compiled programs: the serving engine's fused
chunk+decode step plus its decode-only sibling (2 programs, one shape
each — docs/SERVING.md "compiled-program inventory"), a trainer's
single step function. A
silent retrace — a shape that varies per call, a weakly-typed scalar, a
donated buffer that changed layout — keeps every test green while the
TPU spends its time compiling instead of computing. This module is the
runtime complement of ``tools/lint``'s ``static-shape`` rule: the
linter catches dynamic *control flow* statically; the sanitizer catches
dynamic *shapes* by counting what XLA actually compiled.

Two measurement surfaces, both host-side and cheap:

- :class:`CompileWatch` — a process-global counter of XLA backend
  compilations, fed by a ``jax.monitoring`` event listener
  (``/jax/core/compile/backend_compile_duration`` fires once per
  backend compile, cache misses only). Wrap a steady-state window and
  :meth:`~CompileWatch.check_no_growth`: any compile inside the window
  is a retrace leak. The ``compile_watch`` pytest fixture
  (tests/conftest.py) hands one to any test.
- :func:`jit_cache_size` / :func:`check_engine_inventory` — per-program
  trace counts read from the jit wrappers' compilation caches, checked
  against the documented inventory via ``Engine.compiled_programs()``.

Failures raise :class:`RecompileError` with the observed-vs-pinned
counts; CI runs the inventory + no-growth checks in the recompile
sanitizer smoke (tests/test_recompile_sanitizer.py) and inside the
serving smoke via ``tools/serve_bench.py --check-compiles``.
"""

from __future__ import annotations

import threading

# The monitoring event jax 0.4.x records once per XLA backend compile
# (jax._src.interpreters.pxla / pjit lowering paths). Trace-only cache
# hits do not fire it.
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_state_lock = threading.Lock()
_installed = False
_compiles = 0


class RecompileError(AssertionError):
    """The compiled-program inventory grew past its pin (a retrace leak)."""


def _listener(event: str, _duration: float, **_kwargs) -> None:
    global _compiles
    if event == _COMPILE_EVENT:
        with _state_lock:
            _compiles += 1


def install() -> None:
    """Register the compile-event listener (idempotent, process-global).

    jax.monitoring has no per-listener deregistration, so the listener
    is installed once and stays; it is a counter increment on compile
    events only — zero cost on the hot path, which never compiles.
    """
    global _installed
    with _state_lock:
        if _installed:
            return
        from jax import monitoring

        monitoring.register_event_duration_secs_listener(_listener)
        _installed = True


def compile_count() -> int:
    """XLA backend compilations observed since :func:`install`."""
    install()
    with _state_lock:
        return _compiles


class CompileWatch:
    """Count XLA backend compilations over a window.

    ``mark()`` (or context-manager entry) snapshots the global counter;
    :attr:`compiles` is the growth since. Warm up first, then watch the
    steady state::

        engine.run_until_warm(...)
        with CompileWatch() as watch:
            serve_measured_window(...)
        watch.check_no_growth("measured serving window")
    """

    def __init__(self) -> None:
        install()
        self._start = compile_count()

    def mark(self) -> None:
        self._start = compile_count()

    def __enter__(self) -> "CompileWatch":
        self.mark()
        return self

    def __exit__(self, *_exc) -> bool:
        return False

    @property
    def compiles(self) -> int:
        return compile_count() - self._start

    def check_no_growth(self, context: str = "watched window") -> None:
        """Raise :class:`RecompileError` if anything compiled since
        :meth:`mark` — a warm loop that compiles is retracing."""
        n = self.compiles
        if n:
            raise RecompileError(
                f"{n} XLA compilation(s) inside {context} — a warm hot "
                f"loop must not retrace (shape drift or weak-type "
                f"promotion; see docs/STATIC_ANALYSIS.md, 'Compiled-"
                f"program sanitizer')")

    def expect(self, n: int, context: str = "watched window") -> None:
        """Raise unless exactly ``n`` compilations happened since
        :meth:`mark` (warm-up pins: serve warm-up = both programs)."""
        got = self.compiles
        if got != n:
            raise RecompileError(
                f"expected exactly {n} XLA compilation(s) inside "
                f"{context}, observed {got}")


def jit_cache_size(fn) -> int | None:
    """Compiled-shape count of one ``jax.jit`` wrapper (None when the
    running jax doesn't expose the cache — the check degrades to the
    event counter rather than guessing)."""
    get = getattr(fn, "_cache_size", None)
    if not callable(get):
        return None
    return int(get())


# The documented serving inventory (docs/SERVING.md): the fused step
# and the decode-only step, one shape each. Speculation
# (serving/speculative.py) leaves the count alone — the verify window
# IS the decode program at a wider fixed shape — except a GPT drafter,
# which contributes exactly one extra single-shape 'draft' program.
PAGED_PROGRAMS = 2


def check_engine_inventory(engine) -> dict:
    """Pin a serving engine's compiled programs against the docs.

    Checks (via ``Engine.compiled_programs()``): the program COUNT is
    exactly 2 — plus the drafter's ``draft`` program when one reports
    it — and every program that has run holds exactly one compiled
    shape. Returns the observed ``{name: shapes}`` inventory for logging.
    """
    progs = engine.compiled_programs()
    expected = PAGED_PROGRAMS + ("draft" in progs)
    if len(progs) != expected:
        raise RecompileError(
            f"engine has {len(progs)} compiled programs "
            f"{sorted(progs)}, inventory pins {expected} "
            f"(docs/SERVING.md)")
    for name, shapes in sorted(progs.items()):
        if shapes is None:
            continue  # cache introspection unavailable on this jax
        if shapes > 1:
            raise RecompileError(
                f"engine program '{name}' compiled {shapes} "
                f"shapes — the inventory pins one trace per program "
                f"(retrace leak; docs/SERVING.md)")
    return progs
