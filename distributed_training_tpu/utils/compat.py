"""Platform query + the framework's ``shard_map`` spelling."""

from __future__ import annotations

import jax
from jax import shard_map as _shard_map


def on_tpu() -> bool:
    """True when the first visible device is a TPU.

    A failed backend query RAISES (whatever ``jax.devices()`` raised): the
    Pallas ops pick compiled vs interpret mode from this, and a swallowed
    error would silently turn a compiled kernel into the interpreter.
    """
    return jax.devices()[0].platform == "tpu"


def pallas_interpret(requested: bool | None) -> bool:
    """Resolve a Pallas op's ``interpret`` argument — the one place that
    decides it. ``None`` = auto: compiled on TPU, interpret mode elsewhere
    (tests, CPU mesh). On platform ``tpu`` interpret mode is refused: a
    kernel that cannot compile must fail, never run interpreted."""
    tpu = on_tpu()
    if requested is None:
        return not tpu
    if requested and tpu:
        raise RuntimeError(
            "pallas interpret mode requested on platform 'tpu'; kernels "
            "run compiled on the chip (interpret mode is for CPU tests)")
    return bool(requested)


def shard_map(fn, mesh, in_specs, out_specs, axis_names=None):
    """``shard_map`` without replication checking.

    ``axis_names`` selects *partial-manual* mode: only the named mesh axes
    are manual (specs refer to them); the remaining axes stay automatic, so
    GSPMD keeps propagating shardings through the body. This is how the
    explicit strategies compose with declarative TP: ring attention /
    pipeline collectives run manually over ``sequence``/``pipe`` while the
    megatron ``model``-axis psums are inserted by GSPMD inside the shards.
    """
    kwargs = {} if axis_names is None else {"axis_names": set(axis_names)}
    return _shard_map(fn, mesh=mesh, in_specs=in_specs,
                      out_specs=out_specs, check_vma=False, **kwargs)
