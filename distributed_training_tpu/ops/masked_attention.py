"""Pallas attention of a prefill chunk over one block of keys under a
selection mask (TPU kernel): the per-head form of latent attention without
the scores ever reaching memory.

The chunk lane of :mod:`~distributed_training_tpu.models.deepseek_v32`
attends ``T`` query rows (one prefill chunk) against the live key blocks
of its slot, ``S`` keys a block, each query under its own selection (the
indexer's top-k as a boolean ``[T, S]``). Written in XLA, the scores of a
block, float32 ``[heads, T, S]``, cross HBM three times (the product writes
them, the running max reads them, the exponentials read them again) and the
probabilities twice: at 128 heads x 1024 x 1024 that is 2 GB a block and
layer against 86 GFLOP of products. Here a head's tile of scores lives in
VMEM from the product to the weighted sum of values.

One call is one key block; the online-softmax state (``o`` and the row
statistics ``m``, ``l``) comes in, is advanced by the block and goes out in
place (aliased), so the caller loops over as many blocks as are live and
divides ``o`` by ``l`` at the end (:func:`finish`). Shapes are the ones
the caller has without a transpose per block: the
keys and values of all heads as the plain products ``c_kv @ W`` ``[S,
heads x dim]`` (a head is a lane-aligned column block), the rotated key
``[S, rope]`` shared by the heads, the output ``[T, heads x v]``.

The **grouped form** serves grouped-query attention from the same body: the
keys and values hold fewer heads than the queries (``[S, kv_heads x dim]``
as a K/V pool's pages hold them), query head ``h`` reads the column block
``h // group``, and there is no shared rotated key (``q_rope`` and
``k_rope`` are None: the whole key is the head's own). A group's query heads
follow one another in the grid, so a key head's block is fetched once for
all of them and is never expanded to the query heads in HBM.

Off the TPU the kernel runs in Pallas interpret mode
(``utils/compat.py::pallas_interpret``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from distributed_training_tpu.ops.flash_attention import LSE_LANES, NEG_INF
from distributed_training_tpu.utils.compat import pallas_interpret

# Query rows a grid step holds: its float32 scores [BLOCK_Q, S] are 2 MB at
# S = 1024, inside the default scoped VMEM with the operands double-buffered.
BLOCK_Q = 512
NAME = "masked_attention"


def kernel_fits(batch: int, t: int, key_block: int, nope: int, rope: int,
                v_dim: int, dtype) -> bool:
    """Whether a call's shapes are ones the kernel serves: one sequence, a
    chunk of whole query blocks, and heads whose key and value columns are
    whole lane tiles; ``rope`` is the width of the rotated key the heads
    share, 0 where there is none (the grouped form). Decided from shapes
    and dtype alone — the same answer on every backend."""
    if jnp.dtype(dtype).itemsize not in (2, 4):
        return False
    return (batch == 1 and t % min(t, BLOCK_Q) == 0 and t % 32 == 0
            and key_block % 128 == 0 and nope % 128 == 0
            and v_dim % 128 == 0 and rope % 8 == 0)


def _kernel(qn_ref, qr_ref, kn_ref, kr_ref, v_ref, keep_ref,
            o_in, stats_in, o_out, stats_out, *, scale):
    contract_last = (((1,), (1,)), ((), ()))
    # operands in their own dtype, float32 accumulation (flash_attention.py)
    s = jax.lax.dot_general(qn_ref[0], kn_ref[...], contract_last,
                            preferred_element_type=jnp.float32)
    if qr_ref is not None:
        s = s + jax.lax.dot_general(qr_ref[0], kr_ref[...], contract_last,
                                    preferred_element_type=jnp.float32)
    keep = keep_ref[...] != 0
    s = jnp.where(keep, s * scale, NEG_INF)
    m_prev, l_prev = stats_in[0][:, :1], stats_in[0][:, 1:2]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    # a row with no key selected so far keeps m at NEG_INF and l at 0
    p = jnp.where(keep, jnp.exp(s - m_new), 0.0)
    fade = jnp.exp(m_prev - m_new)
    l_new = l_prev * fade + jnp.sum(p, axis=-1, keepdims=True)
    o_out[...] = o_in[...] * fade + jax.lax.dot_general(
        p.astype(v_ref.dtype), v_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    lane = jax.lax.broadcasted_iota(jnp.int32, stats_out.shape[1:], 1)
    stats_out[0] = jnp.where(lane == 0, m_new, l_new)


def init_state(t: int, heads: int, v_dim: int):
    """The online-softmax state before any block, float32: ``o`` [T, heads x
    v], and the row statistics [heads, T, LSE_LANES] with the running max
    ``m`` in lane 0 and the running sum ``l`` in the others."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (heads, t, LSE_LANES), 2)
    return (jnp.zeros((t, heads * v_dim), jnp.float32),
            jnp.where(lane == 0, NEG_INF, 0.0).astype(jnp.float32))


def finish(state, heads: int):
    """``o / l`` as [T, heads, v]: the attention's output. A row that no
    block gave a key reads NaN (0 / 0), as a softmax over nothing does."""
    o, stats = state
    t = o.shape[0]
    return o.reshape(t, heads, -1) / stats[:, :, 1].T[:, :, None]


def masked_attention_block(q_nope, q_rope, k_nope, k_rope, v, keep, state,
                           *, scale: float, interpret: bool | None = None):
    """Advance ``state`` (:func:`init_state`) by one block of keys.

    ``q_nope`` [H, T, nope] and ``q_rope`` [H, T, rope] are the queries by
    head; ``k_nope`` [S, H x nope] and ``v`` [S, H x v] every head's keys
    and values side by side; ``k_rope`` [S, rope] the rotated key the heads
    share; ``keep`` [T, S] (int8, non-zero = attend) the selection. Scores
    ``(q_nope . k_nope + q_rope . k_rope) * scale`` and the softmax
    statistics are float32; the probabilities meet the values in the
    values' dtype, as in the flash kernels.

    The grouped form: ``k_nope`` [S, KVH x nope] and ``v`` [S, KVH x v]
    hold ``KVH = H / group`` heads, query head ``h`` reads head ``h //
    group`` of both, and ``q_rope`` / ``k_rope`` are None (no key part is
    shared by the heads); the output's head size is ``v``'s."""
    h, t, nope = q_nope.shape
    s = k_nope.shape[0]
    group = h * nope // k_nope.shape[1]
    v_dim = v.shape[1] * group // h
    bq = min(t, BLOCK_Q)

    def by_head(width):          # a head's rows of the queries
        return pl.BlockSpec((1, bq, width), lambda hh, i: (hh, i, 0))

    def key_head(width):         # the column block query head hh reads
        if group == 1:
            return pl.BlockSpec((s, width), lambda hh, i: (0, hh))
        return pl.BlockSpec((s, width), lambda hh, i: (0, hh // group))

    row = pl.BlockSpec((1, bq, LSE_LANES), lambda hh, i: (hh, i, 0))
    out = pl.BlockSpec((bq, v_dim), lambda hh, i: (i, hh))
    shared = q_rope is not None      # a rotated key part all heads share
    operands = [(q_nope, by_head(nope))]
    if shared:
        operands.append((q_rope, by_head(q_rope.shape[-1])))
    operands.append((k_nope, key_head(nope)))
    if shared:
        operands.append((k_rope, pl.BlockSpec((s, k_rope.shape[-1]),
                                              lambda hh, i: (0, 0))))
    operands += [(v, key_head(v_dim)),
                 (keep, pl.BlockSpec((bq, s), lambda hh, i: (i, 0))),
                 (state[0], out), (state[1], row)]
    n = len(operands)
    return pl.pallas_call(
        functools.partial(_kernel if shared else _grouped_kernel,
                          scale=scale),
        grid=(h, t // bq),
        in_specs=[spec for _, spec in operands],
        out_specs=[out, row],
        out_shape=[jax.ShapeDtypeStruct(a.shape, a.dtype) for a in state],
        input_output_aliases={n - 2: 0, n - 1: 1},
        interpret=pallas_interpret(interpret),
        name=NAME,
    )(*(a for a, _ in operands))


def _grouped_kernel(q_ref, k_ref, v_ref, keep_ref, o_in, stats_in, o_out,
                    stats_out, *, scale):
    _kernel(q_ref, None, k_ref, None, v_ref, keep_ref, o_in, stats_in,
            o_out, stats_out, scale=scale)
