"""Pallas flash attention (TPU kernel) with custom VJP.

The hot op of the transformer path. The exact attention in
``parallel/ring_attention.py`` materializes the [T, T] score matrix in HBM —
fine for short sequences, quadratic HBM traffic for long ones. This kernel
computes attention blockwise in VMEM with the online-softmax recurrence, so
HBM traffic is linear in T: the canonical memory-bound TPU kernel ("pallas
for the hot ops").

Layout: grid (batch·heads, q_blocks, k_blocks), k innermost — TPU grids run
sequentially, so the (acc, m, l) scratch persists across the k sweep of one
q block (the flash recurrence), initialized at k==0 and normalized into the
output at the last k step. The backward pass is ONE fused Pallas kernel
(round 4): dq/dk/dv share the recomputed scores and probabilities; dk/dv
accumulate in VMEM scratch across the q sweep while dq writes per-k-block
partials that XLA sums outside (``_fused_bwd_kernel``). Probabilities are
recomputed from the saved logsumexp rather than stored — the standard
flash-attention VJP. (An interior-tile mask-skip specialization — branch
per tile so fully-below-diagonal tiles skip the iota/compare/select —
was tried and measured NO faster at T1024/4096/16384: the VPU cost there
is the exp, not the mask; reverted to keep one code path.)

Off-TPU (tests, CPU mesh) the kernels run in pallas interpret mode,
bit-compatible with the compiled path; on TPU interpret mode is refused
(``utils/compat.py::pallas_interpret``). Block sizes default to the 128-lane
hardware tile; sequence length must divide into blocks.

No reference counterpart exists (the reference has no attention model at
all, SURVEY.md §5 "Long-context"); the design follows the public
flash-attention algorithm, re-tiled for MXU/VMEM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distributed_training_tpu.utils.compat import pallas_interpret

NEG_INF = -1e30

# Lane width of the per-row logsumexp / delta sidecars. Mosaic needs the
# minor-most BLOCK dim to be a 128-multiple or span the full array dim, so
# per-row scalars are stored replicated across lanes; 8 lanes (one sublane
# tile, "full dim" for the block) instead of 128 cuts the sidecar HBM
# traffic 16x — at B16 H12 T1024 the lse+delta tensors were 100 MB each
# per layer, written in forward and read by BOTH backward kernels (~5.6
# GB/step, ~7 ms of the GPT step at v5e bandwidth).
LSE_LANES = 8


def _block(t: int, requested: int) -> int:
    """Largest usable block ≤ ``requested`` for a length-``t`` sequence.

    Mosaic blocks must be (8, 128)-tile aligned or span the full dimension,
    so candidates are 128-multiples dividing t (e.g. t=768, requested=512 →
    384), or t itself when it's short enough to be one block.
    """
    if t <= requested:
        return t
    if t % requested == 0:
        return requested
    for b in range(min(requested, t) // 128 * 128, 0, -128):
        if t % b == 0:
            return b
    raise ValueError(
        f"sequence length {t} is not divisible by block {requested} nor by "
        f"any 128-multiple below it; pad the sequence to a multiple of 128")



def _live_block(qi, ki, *, causal, block_q, block_k):
    """False only for causal blocks that are entirely masked (k_start >
    q_end) — the skip predicate shared by all three kernels."""
    return (ki * block_k <= qi * block_q + block_q - 1) if causal else True


def _masked_scores(q_ref, k_ref, qi, ki, *, scale, causal, block_q, block_k):
    """Scaled q·kᵀ for one tile (fp32 accumulation), causally masked by
    global positions.

    The dot runs in the INPUT dtype with ``preferred_element_type=f32`` —
    NOT on fp32-cast operands. On TPU an explicit f32×f32 matmul runs the
    MXU at the fp32 rate (~1/4 of bf16 on v5e); bf16 operands with fp32
    accumulation keep full MXU rate at the same accumulation precision
    (measured: the fp32-cast version held the whole kernel to ~52 TFLOP/s
    on bf16 models). fp32 inputs still get an exact fp32 matmul — the
    tests' oracle tolerances are dtype-driven.
    """
    s = jax.lax.dot_general(
        q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale
    if causal:
        qpos = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        kpos = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        s = jnp.where(kpos > qpos, NEG_INF, s)
    return s


# -- forward -----------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc, m, l,
                *, scale, causal, block_q, block_k, nk):
    ki = pl.program_id(2)
    qi = pl.program_id(1)
    # exp2 mode: log2(e) folds into the score scale, the (m, l) recurrence
    # runs in the log2 domain, and only the stored lse converts back to
    # natural log — zero extra per-element VPU ops (see _USE_EXP2).
    use2 = _USE_EXP2
    eff = scale * _LOG2E if use2 else scale
    exp_fn = jnp.exp2 if use2 else jnp.exp

    @pl.when(ki == 0)
    def _():
        acc[:] = jnp.zeros_like(acc)
        m[:] = jnp.full_like(m, NEG_INF)
        l[:] = jnp.zeros_like(l)

    # Causal block skip: a fully-masked block's matmuls are predicated out
    # (halves the causal FLOPs; the grid still visits the block).
    @pl.when(_live_block(qi, ki, causal=causal, block_q=block_q,
                         block_k=block_k))
    def _():
        s = _masked_scores(q_ref, k_ref, qi, ki, scale=eff, causal=causal,
                           block_q=block_q, block_k=block_k)
        m_prev = m[:, :1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = exp_fn(s - m_new)
        corr = exp_fn(m_prev - m_new)
        l[:] = jnp.broadcast_to(
            l[:, :1] * corr + jnp.sum(p, axis=-1, keepdims=True), l.shape)
        # p in the value dtype (standard flash practice: p ∈ [0, 1], bf16
        # keeps the MXU at full rate), fp32 accumulation into acc.
        acc[:] = acc[:] * corr + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m[:] = jnp.broadcast_to(m_new, m.shape)

    @pl.when(ki == nk - 1)
    def _():
        lsum = l[:, :1]
        # Fully-masked rows (causal warmup of padded blocks) have l == 0.
        o_ref[0] = jnp.where(
            lsum > 0, acc[:] / lsum, 0.0).astype(o_ref.dtype)
        # LSE_LANES-wide broadcast layout: Mosaic requires the last block
        # dim be a 128-multiple OR span the full array dim; the sidecar's
        # minor dim is LSE_LANES (= the whole array dim), so the per-row
        # logsumexp is stored replicated across those lanes.
        logl = (jnp.log2 if use2 else jnp.log)(jnp.maximum(lsum, 1e-30))
        lse_nat = (m[:, :1] + logl) / (_LOG2E if use2 else 1.0)
        lse_ref[0] = jnp.broadcast_to(lse_nat, lse_ref.shape[1:])


def _flash_fwd(q, k, v, *, causal, block_q, block_k, interpret):
    bh, t, d = q.shape
    bq = _block(t, block_q)
    bk = _block(t, block_k)
    nq, nk = t // bq, t // bk
    scale = 1.0 / (d ** 0.5)

    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal,
        block_q=bq, block_k=bk, nk=nk)
    out, lse = pl.pallas_call(
        kernel,
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq, LSE_LANES), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t, d), q.dtype),
            jax.ShapeDtypeStruct((bh, t, LSE_LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
        ],
        interpret=interpret,
        name="flash_fwd",
    )(q, k, v)
    return out, lse


# -- backward ----------------------------------------------------------------

def _recomputed_probs(q_ref, k_ref, lse_ref, qi, ki, *, scale, causal,
                      block_q, block_k):
    """Softmax probabilities recomputed from the saved natural-log lse —
    the shared backward step. In exp2 mode the scores carry log2(e) in
    their scale and the stored lse converts with one per-ROW multiply
    ([bq, 1], negligible vs the [bq, bk] exp)."""
    use2 = _USE_EXP2
    eff = scale * _LOG2E if use2 else scale
    s = _masked_scores(q_ref, k_ref, qi, ki, scale=eff, causal=causal,
                       block_q=block_q, block_k=block_k)
    lse_row = lse_ref[0][:, :1] * _LOG2E if use2 else lse_ref[0][:, :1]
    return (jnp.exp2 if use2 else jnp.exp)(s - lse_row)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq,
               *, scale, causal, block_q, block_k, nk):
    ki = pl.program_id(2)
    qi = pl.program_id(1)

    @pl.when(ki == 0)
    def _():
        dq[:] = jnp.zeros_like(dq)

    @pl.when(_live_block(qi, ki, causal=causal, block_q=block_q,
                         block_k=block_k))
    def _():
        p = _recomputed_probs(q_ref, k_ref, lse_ref, qi, ki, scale=scale,
                              causal=causal, block_q=block_q,
                              block_k=block_k)
        # Input-dtype matmuls, fp32 accumulation (see _masked_scores); ds
        # is cast back to the key dtype for the dq contraction — the
        # standard flash-backward precision recipe.
        dp = jax.lax.dot_general(
            do_ref[0], v_ref[0],
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0][:, :1])
        dq[:] += scale * jax.lax.dot_general(
            ds.astype(k_ref.dtype), k_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _():
        dq_ref[0] = dq[:].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk, dv,
                *, scale, causal, block_q, block_k, nq):
    qi = pl.program_id(2)
    ki = pl.program_id(1)

    @pl.when(qi == 0)
    def _():
        dk[:] = jnp.zeros_like(dk)
        dv[:] = jnp.zeros_like(dv)

    @pl.when(_live_block(qi, ki, causal=causal, block_q=block_q,
                         block_k=block_k))
    def _():
        p = _recomputed_probs(q_ref, k_ref, lse_ref, qi, ki, scale=scale,
                              causal=causal, block_q=block_q,
                              block_k=block_k)
        do = do_ref[0]
        # dV += P^T dO — p in the output-grad dtype, fp32 accumulation.
        dv[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            do, v_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0][:, :1])
        # dK += dS^T Q
        dk[:] += scale * jax.lax.dot_general(
            ds.astype(q_ref.dtype), q_ref[0], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(qi == nq - 1)
    def _():
        dk_ref[0] = dk[:].astype(dk_ref.dtype)
        dv_ref[0] = dv[:].astype(dv_ref.dtype)


def _fused_bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      dq_ref, dk_ref, dv_ref, dk, dv,
                      *, scale, causal, block_q, block_k, nq):
    """One kernel for all three gradients — the round-4 backward.

    The separate dq / dkv kernels each recomputed the masked scores and the
    softmax probabilities (7 tile matmuls + two mask/exp chains per [bq, bk]
    tile in total); fusing shares s, p and dp across the three gradient
    contractions (5 matmuls + one chain). dk/dv accumulate in VMEM scratch
    across the inner q sweep exactly as before; dq cannot (its block index
    varies along the INNER grid dim), so each k block writes its own partial
    dq tile to HBM and XLA sums the ``nk`` partials outside the kernel —
    the same partial-accumulation layout jax's fused splash-attention
    backward uses. At the default blocks the partial sum is 1-2 extra
    passes over dq, far cheaper than a second score recompute sweep.
    """
    qi = pl.program_id(2)
    ki = pl.program_id(1)

    @pl.when(qi == 0)
    def _():
        dk[:] = jnp.zeros_like(dk)
        dv[:] = jnp.zeros_like(dv)

    live = _live_block(qi, ki, causal=causal, block_q=block_q,
                       block_k=block_k)

    @pl.when(live)
    def _():
        p = _recomputed_probs(q_ref, k_ref, lse_ref, qi, ki, scale=scale,
                              causal=causal, block_q=block_q,
                              block_k=block_k)
        do = do_ref[0]
        # dV += P^T dO — p in the output-grad dtype, fp32 accumulation.
        dv[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            do, v_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = (p * (dp - delta_ref[0][:, :1])).astype(q_ref.dtype)
        # dK += dS^T Q
        dk[:] += scale * jax.lax.dot_general(
            ds, q_ref[0], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        # dQ partial for this k block (summed over k blocks outside; with
        # nk == 1 the "partial" IS dq and the out dtype is q's, casting
        # in-kernel to skip an external fp32->bf16 convert pass).
        dq_ref[0, 0] = (scale * jax.lax.dot_general(
            ds, k_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)).astype(dq_ref.dtype)

    @pl.when(jnp.logical_not(live))
    def _():
        # Dead causal tiles still own a partial-dq slot in HBM: zero it so
        # the outside sum reads defined memory.
        dq_ref[0, 0] = jnp.zeros_like(dq_ref[0, 0])

    @pl.when(qi == nq - 1)
    def _():
        dk_ref[0] = dk[:].astype(dk_ref.dtype)
        dv_ref[0] = dv[:].astype(dv_ref.dtype)


# A/B switch for tools/flash_kernel_bench.py --split-bwd; the model path
# always runs the fused backward.
_USE_SPLIT_BWD = False

# A/B switch for tools/flash_kernel_bench.py --exp2: compute the softmax
# exponentials as native 2^x with log2(e) FOLDED INTO the score scale (the
# fwd recurrence then runs entirely in the log2 domain), zero extra VPU
# ops. Probes whether Mosaic's exp lowering already uses the pow2 unit —
# the VPU exp is the kernels' profiled cost (round-4 mask-skip
# falsification).
_USE_EXP2 = False
_LOG2E = 1.4426950408889634


def _bwd_prologue(res, g, block_q, block_k, g_lse):
    """Shared backward prep: block math and the delta sidecar.

    ``g_lse`` is the cotangent of lse as a differentiable OUTPUT (the
    ring-hop composition): it folds into the delta term —
    ``ds = p·(dp − δ + ḡ_lse)`` because ∂lse_i/∂s_ij = p_ij — so the same
    backward kernels serve both the plain and the (out, lse) variants.
    """
    q, k, v, out, lse = res
    t, d = q.shape[-2:]
    bq = _block(t, block_q)
    bk = _block(t, block_k)
    scale = 1.0 / (d ** 0.5)
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    if g_lse is not None:
        delta = delta - g_lse.astype(jnp.float32)
    delta = jnp.broadcast_to(delta[..., None],
                             (*delta.shape, LSE_LANES))
    return q, k, v, lse, delta, bq, bk, t // bq, t // bk, scale


def _flash_bwd(res, g, *, causal, block_q, block_k, interpret, g_lse=None):
    if _USE_SPLIT_BWD:
        return _flash_bwd_split(res, g, causal=causal, block_q=block_q,
                                block_k=block_k, interpret=interpret,
                                g_lse=g_lse)
    q, k, v, lse, delta, bq, bk, nq, nk, scale = _bwd_prologue(
        res, g, block_q, block_k, g_lse)
    bh, t, d = q.shape

    dq_partial, dk, dv = pl.pallas_call(
        functools.partial(_fused_bwd_kernel, scale=scale, causal=causal,
                          block_q=bq, block_k=bk, nq=nq),
        grid=(bh, nk, nq),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, bq, d), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, bq, LSE_LANES), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, bq, LSE_LANES), lambda b, j, i: (b, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b, j, i: (j, b, i, 0)),
            pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nk, bh, t, d),
                                 q.dtype if nk == 1 else jnp.float32),
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, d), jnp.float32),
            pltpu.VMEM((bk, d), jnp.float32),
        ],
        interpret=interpret,
        name="flash_bwd_fused",
    )(q, k, v, g, lse, delta)
    dq = (dq_partial[0] if nk == 1
          else dq_partial.sum(axis=0).astype(q.dtype))
    return dq, dk, dv


def _flash_bwd_split(res, g, *, causal, block_q, block_k, interpret,
                     g_lse=None):
    """The pre-round-4 two-kernel backward (dq sweep; dk/dv sweep).

    Kept for A/B measurement (``tools/flash_kernel_bench.py --split-bwd``)
    and as the fallback shape for tilings where the fused kernel's
    partial-dq HBM cost could exceed the saved recompute (nk large with
    tiny blocks). Not reachable from the model path.
    """
    q, k, v, lse, delta, bq, bk, nq, nk, scale = _bwd_prologue(
        res, g, block_q, block_k, g_lse)
    bh, t, d = q.shape

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal,
                          block_q=bq, block_k=bk, nk=nk),
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq, LSE_LANES), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq, LSE_LANES), lambda b, i, j: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        interpret=interpret,
        name="flash_bwd_dq",
    )(q, k, v, g, lse, delta)

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal,
                          block_q=bq, block_k=bk, nq=nq),
        grid=(bh, nk, nq),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, bq, d), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, bq, LSE_LANES), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, bq, LSE_LANES), lambda b, j, i: (b, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, d), jnp.float32),
            pltpu.VMEM((bk, d), jnp.float32),
        ],
        interpret=interpret,
        name="flash_bwd_dkv",
    )(q, k, v, g, lse, delta)
    return dq, dk, dv


# -- public op ---------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash_core(q, k, v, causal, block_q, block_k,
                bwd_block_q, bwd_block_k, interpret):
    out, _ = _flash_fwd(q, k, v, causal=causal, block_q=block_q,
                        block_k=block_k, interpret=interpret)
    return out


def _flash_core_fwd(q, k, v, causal, block_q, block_k,
                    bwd_block_q, bwd_block_k, interpret):
    out, lse = _flash_fwd(q, k, v, causal=causal, block_q=block_q,
                          block_k=block_k, interpret=interpret)
    return out, (q, k, v, out, lse)


def _flash_core_bwd(causal, block_q, block_k, bwd_block_q, bwd_block_k,
                    interpret, res, g):
    return _flash_bwd(res, g, causal=causal, block_q=bwd_block_q,
                      block_k=bwd_block_k, interpret=interpret)


_flash_core.defvjp(_flash_core_fwd, _flash_core_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash_core_lse(q, k, v, causal, block_q, block_k,
                    bwd_block_q, bwd_block_k, interpret):
    """Like :func:`_flash_core` but also returns the per-row logsumexp as a
    differentiable output — the hop primitive for ring+flash composition
    (``parallel/ring_attention.py``): per-hop (out, lse) pairs merge across
    hops with the online-softmax recurrence, and the merge weights
    back-propagate into lse."""
    out, lse = _flash_fwd(q, k, v, causal=causal, block_q=block_q,
                          block_k=block_k, interpret=interpret)
    return out, lse[..., 0]


def _flash_core_lse_fwd(q, k, v, causal, block_q, block_k,
                        bwd_block_q, bwd_block_k, interpret):
    out, lse = _flash_fwd(q, k, v, causal=causal, block_q=block_q,
                          block_k=block_k, interpret=interpret)
    return (out, lse[..., 0]), (q, k, v, out, lse)


def _flash_core_lse_bwd(causal, block_q, block_k, bwd_block_q, bwd_block_k,
                        interpret, res, g):
    g_out, g_lse = g
    return _flash_bwd(res, g_out, causal=causal, block_q=bwd_block_q,
                      block_k=bwd_block_k, interpret=interpret, g_lse=g_lse)


_flash_core_lse.defvjp(_flash_core_lse_fwd, _flash_core_lse_bwd)


def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = False,
    block_q: int | None = None,
    block_k: int | None = None,
    bwd_block_q: int | None = None,
    bwd_block_k: int | None = None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Blockwise attention over [..., T, head_dim] (any leading batch dims).

    Returns softmax(q kᵀ / √d [, causal-masked]) v without materializing the
    [T, T] score matrix in HBM. ``interpret`` defaults to auto: compiled on
    TPU, interpret mode elsewhere (bit-compatible semantics).

    Block sizes default to the v5e-measured auto rule: forward
    ``min(T, 1024) × min(T, 2048)`` (round-2 sweep: wide K blocks keep the
    MXU fed and amortize the recurrence), backward ``min(T, 512) ×
    min(T, 2048)`` (round-4 sweep over the FUSED backward kernel, bf16
    causal fwd+bwd: T1024 6.15 ms / T4096 6.77 ms / T16384 49.8 ms vs
    7.9 / 9.7 / 63.2 for the round-3 two-kernel backward at its auto
    blocks; wider q or k blocks fail Mosaic compile at T≥4096 — VMEM).
    T must divide by the block, so shorter/odd sequences clamp via
    ``_block``.
    """
    args = _flat_args(q, k, v, block_q, block_k, bwd_block_q, bwd_block_k,
                      interpret)
    lead, t, d = q.shape[:-2], *q.shape[-2:]
    out = _flash_core(*args[:3], causal, *args[3:])
    return out.reshape(*lead, t, d)


def _flat_args(q, k, v, block_q, block_k, bwd_block_q, bwd_block_k,
               interpret):
    """Shared arg prep: shape check, auto block rule, flatten lead dims."""
    if q.shape != k.shape or q.shape != v.shape:
        raise ValueError(f"q/k/v shapes differ: {q.shape} {k.shape} {v.shape}")
    run_interpret = pallas_interpret(interpret)
    t, d = q.shape[-2:]
    if block_q is None:
        block_q = min(t, 1024)
    if block_k is None:
        block_k = min(t, 2048)
    if bwd_block_q is None:
        bwd_block_q = min(t, 512)
    if bwd_block_k is None:
        bwd_block_k = min(t, 2048)
    qf = q.reshape((-1, t, d))
    kf = k.reshape((-1, t, d))
    vf = v.reshape((-1, t, d))
    return (qf, kf, vf, block_q, block_k, bwd_block_q, bwd_block_k,
            run_interpret)


def flash_attention_lse(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = False,
    block_q: int | None = None,
    block_k: int | None = None,
    bwd_block_q: int | None = None,
    bwd_block_k: int | None = None,
    interpret: bool | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Blockwise attention returning ``(out, lse)`` with lse differentiable.

    ``out`` is the softmax-normalized attention output ([..., T, d], input
    dtype); ``lse`` the per-row logsumexp of the scaled scores ([..., T],
    fp32; ≈``NEG_INF`` for fully-masked rows). The hop primitive for ring
    attention with flash compute: per-hop results merge across hops as
    ``out = Σ_h exp(lse_h − lse_tot)·out_h`` with
    ``lse_tot = logaddexp_h lse_h`` — exactly the online-softmax recurrence
    at hop granularity. Block-size defaults and dtypes match
    :func:`flash_attention`.
    """
    args = _flat_args(q, k, v, block_q, block_k, bwd_block_q, bwd_block_k,
                      interpret)
    lead, t, d = q.shape[:-2], *q.shape[-2:]
    out, lse = _flash_core_lse(*args[:3], causal, *args[3:])
    return out.reshape(*lead, t, d), lse.reshape(*lead, t)
