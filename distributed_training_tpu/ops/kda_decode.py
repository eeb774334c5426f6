"""Pallas decode step of Kimi Delta Attention (TPU kernel): one token of
every decoding slot against its recurrent state, the state read once and
written once, in place.

A slot's state is ``S [H, dk, dv]`` float32 (row ``c`` of a head is key
channel ``c``). One token with query ``q``, key ``k``, value ``v``,
per-channel log decay ``g <= 0`` and write strength ``beta`` advances it
by the gated delta rule::

    S' = Diag(exp g) S                       # per key channel
    S  = S' + (beta k) (v - S'^T k)^T        # = (I - beta k k^T) S' + beta k v^T
    o  = S^T q

In XLA the decay, ``S'^T k``, the rank-1 update and ``S^T q`` are separate
passes over the state, each reading (and one writing) 2 MiB a slot and
layer at 32 heads of 128 x 128. Here a grid step holds one slot's whole
state in VMEM and does all four on it.

Slots that are not decoding are skipped: the state's block index of a
step whose row is not live repeats the last live row's (or, before the
first, the next one's), so the pipeline neither fetches nor writes back
anything for it, and the aliased state keeps its value in HBM. Step 0,
where not live, copies its block's input to the output, so the block
that is written back when the index first changes (or at the end, with
no live row at all) is the state as it was.

The per-head vectors meet the state as columns (a key channel a
sublane): ``q``, ``k``, ``beta k`` and ``g`` arrive stacked ``[4H, dk]``
and one transpose in VMEM turns them into ``[dk, 4H]``. Everything is
float32 on the vector unit. Off the TPU the kernel runs in Pallas
interpret mode (``utils/compat.py::pallas_interpret``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distributed_training_tpu.utils.compat import pallas_interpret

NAME = "kda_decode"
# Two buffers each of a slot's state in and out (2 MiB each at 32 heads of
# 128 x 128) and the per-head temporaries.
VMEM_LIMIT = 32 * 1024 * 1024


def block_index(slots, live):
    """Per row ``b`` the state block its grid step names: its own slot
    where live, else the last live row's slot before it, else the first
    live row's after it (0 where no row is live)."""
    b = live.shape[0]
    rows = jnp.arange(b, dtype=jnp.int32)
    before = jax.lax.cummax(jnp.where(live, rows, -1))
    after = jax.lax.cummin(jnp.where(live, rows, b), reverse=True)
    src = jnp.where(before >= 0, before, jnp.where(after < b, after, 0))
    return slots.astype(jnp.int32)[src]


def _kernel(idx_ref, live_ref, x_ref, v_ref, s_in, o_ref, s_out, *, heads):
    del idx_ref
    b = pl.program_id(0)
    live = live_ref[b] != 0

    @pl.when(jnp.logical_and(b == 0, jnp.logical_not(live)))
    def _():
        s_out[...] = s_in[...]

    @pl.when(jnp.logical_not(live))
    def _():
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

    @pl.when(live)
    def _():
        cols = x_ref[0].T                           # [dk, 4H]
        decay = jnp.exp(cols[:, 3 * heads:])         # [dk, H]
        for h in range(heads):
            s = s_in[0, h] * decay[:, h:h + 1]       # [dk, dv]
            ks = jnp.sum(cols[:, heads + h:heads + h + 1] * s, axis=0,
                         keepdims=True)              # [1, dv]
            u = v_ref[0, h:h + 1, :] - ks
            s = s + cols[:, 2 * heads + h:2 * heads + h + 1] * u
            s_out[0, h] = s
            o_ref[0, h:h + 1, :] = jnp.sum(cols[:, h:h + 1] * s, axis=0,
                                           keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def kda_decode(q, k, v, g, beta, state, slots, live, *,
               interpret: bool | None = None):
    """One decode token per row: ``q`` / ``k`` / ``g`` [B, H, dk], ``v``
    [B, H, dv], ``beta`` [B, H], all float32; ``state`` [slots, H, dk, dv]
    float32, row ``b``'s at ``slots[b]``; ``live`` [B] bool. Returns ``(o
    [B, H, dv], state)``: the live rows' outputs (zero elsewhere) and the
    state with the live rows' slots advanced and every other slot as it
    was (the state is aliased: in place where the caller donated it)."""
    b, h, dk = q.shape
    dv = v.shape[-1]
    f32 = jnp.float32
    x = jnp.concatenate([q, k, beta[..., None] * k, g], axis=1).astype(f32)
    idx = block_index(slots, live)

    def row(width, rows):
        return pl.BlockSpec((1, rows, width), lambda i, idx, live: (i, 0, 0))

    state_block = pl.BlockSpec((1, h, dk, dv),
                               lambda i, idx, live: (idx[i], 0, 0, 0))
    o, state = pl.pallas_call(
        functools.partial(_kernel, heads=h),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b,),
            in_specs=[row(dk, 4 * h), row(dv, h), state_block],
            out_specs=[row(dv, h), state_block]),
        out_shape=[jax.ShapeDtypeStruct((b, h, dv), f32),
                   jax.ShapeDtypeStruct(state.shape, f32)],
        input_output_aliases={4: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=pallas_interpret(interpret),
        name=NAME,
    )(idx, live.astype(jnp.int32), x, v.astype(f32), state)
    return o, state


def kda_step(q, k, v, g, beta, state):
    """The same step in plain XLA over ``state`` [B, H, dk, dv] (each row
    its own state): elementwise products and sums in float32, four passes
    over the state. The kernel's yardstick in the tests and in
    ``tools/kda_decode_bench.py``."""
    s = state * jnp.exp(g)[..., None]
    ks = (k[..., None] * s).sum(-2)
    s = s + (beta[..., None] * k)[..., None] * (v - ks)[..., None, :]
    return (q[..., None] * s).sum(-2), s
