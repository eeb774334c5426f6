"""Ring attention: sequence/context parallelism over a mesh axis.

The reference has no attention model and no sequence parallelism
(SURVEY.md §5 "Long-context": its only model is torchvision resnet18), but
long-context support is first-class here. The TPU-native formulation: shard
the sequence over a ``sequence`` mesh axis and rotate key/value blocks
around the ring with ``lax.ppermute`` (neighbor hops ride the ICI torus),
accumulating attention with the online-softmax (flash) recurrence so the
full [T, T] score matrix never materializes. Compute per hop is a dense
[T/n, d] x [d, T/n] matmul — MXU-shaped — and XLA overlaps each hop's
ppermute with the previous block's compute.

Used inside ``shard_map`` (the axis must be bound); the pure math
:func:`ring_attention` is also exact single-device when ``axis_size == 1``,
which is what the correctness tests compare against full attention.
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax


class PagedKV(NamedTuple):
    """Per-call paged-KV routing state (a pytree of device arrays).

    The serving engine passes one of these through ``model.apply`` when
    the KV cache is the paged pool (``kv_page_size`` set): the cache
    collection then holds only the position-free page pool, while WHICH
    pool rows a batch row reads/writes travels here — so a decode batch
    of ``max_batch`` slots, a ``[1, chunk]`` prefill chunk, and a
    ``[max_batch, spec_k + 1]`` speculative verify window (the decode
    batch widened with per-slot draft tokens, ``serving/speculative.py``)
    all share one pool inside one compiled step despite different batch
    shapes — the attend is general over the incoming window width.

    - ``table`` int32 [B, pages_per_slot]: each row's logical→physical
      page map. Unallocated logical pages point at physical page 0, the
      reserved null page (never handed out by the allocator) — reads of
      it are causally masked, writes to it are discarded garbage.
    - ``positions`` int32 [B, T_in]: each incoming token's global write
      position (the engine's host-side write heads; the contiguous
      layout's ``cache_index`` counter, externalized).
    - ``valid`` bool [B, T_in]: tokens that really exist. Invalid lanes
      (inactive decode slots, chunk padding) write to the null page and
      their outputs are discarded host-side — masks, never shapes.
    """

    table: jnp.ndarray
    positions: jnp.ndarray
    valid: jnp.ndarray


def _online_block_update(o, m, l, s, v):
    """One flash-attention accumulation step.

    o: [..., Tq, d] running (unnormalized) output
    m: [..., Tq]    running row max
    l: [..., Tq]    running row sum of exp
    s: [..., Tq, Tk] raw scores for this block
    v: [..., Tk, d] values for this block
    """
    m_new = jnp.maximum(m, s.max(axis=-1))
    # exp of current block, shifted by the new max
    p = jnp.exp(s - m_new[..., None])
    correction = jnp.exp(m - m_new)
    l_new = l * correction + p.sum(axis=-1)
    o_new = o * correction[..., None] + jnp.einsum("...qk,...kd->...qd", p, v)
    return o_new, m_new, l_new


def ring_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    axis_name: str | None,
    causal: bool = False,
    impl: str = "exact",
) -> jnp.ndarray:
    """Blockwise ring attention over ``axis_name``.

    Args:
      q, k, v: [batch, heads, T_local, head_dim] — the local sequence shard.
      axis_name: bound mesh axis to ring over; None = single-block (exact
        softmax attention, used as the test oracle).
      causal: apply a causal mask using *global* positions (each shard knows
        its ring index, so masks are exact across shards).
      impl: per-hop score computation — 'exact' materializes the local
        [T_loc, T_loc] block in HBM; 'flash' runs the Pallas blockwise
        kernel per hop (:func:`_ring_attention_flash`), so HBM traffic
        stays linear in T_loc even within a hop — the composition that
        makes the long-context strategy use the linear-memory kernel.

    Returns [batch, heads, T_local, head_dim].
    """
    if impl not in ("exact", "flash"):
        raise ValueError(f"unknown ring attention impl {impl!r}")
    if impl == "flash" and axis_name is not None:
        return _ring_attention_flash(q, k, v, axis_name=axis_name,
                                     causal=causal)
    if impl == "flash":
        from distributed_training_tpu.ops.flash_attention import (
            flash_attention,
        )

        return flash_attention(q, k, v, causal=causal)
    scale = 1.0 / jnp.sqrt(q.shape[-1]).astype(q.dtype)
    t_local = q.shape[-2]

    if axis_name is None:
        s = jnp.einsum("...qd,...kd->...qk", q, k) * scale
        if causal:
            qpos = jnp.arange(t_local)[:, None]
            kpos = jnp.arange(t_local)[None, :]
            s = jnp.where(kpos > qpos, jnp.finfo(s.dtype).min, s)
        p = jax.nn.softmax(s.astype(jnp.float32), axis=-1)
        return jnp.einsum("...qk,...kd->...qd", p.astype(v.dtype), v)

    axis_size = lax.axis_size(axis_name)
    my_idx = lax.axis_index(axis_name)
    # Accumulate in fp32 regardless of compute dtype: the recurrence
    # subtracts running maxima and sums many exps — bf16 drifts.
    o = jnp.zeros(q.shape, jnp.float32)
    m = jnp.full(q.shape[:-1], -jnp.inf, jnp.float32)
    l = jnp.zeros(q.shape[:-1], jnp.float32)
    qf = q.astype(jnp.float32)

    def hop(i, carry):
        o, m, l, k_blk, v_blk = carry
        # After i hops each device holds the block originating at ring
        # position (my_idx + i) mod axis_size (ppermute shifts index -1).
        src = (my_idx + i) % axis_size
        s = jnp.einsum("...qd,...kd->...qk", qf, k_blk.astype(jnp.float32))
        s = s * scale
        if causal:
            qpos = my_idx * t_local + jnp.arange(t_local)
            kpos = src * t_local + jnp.arange(k_blk.shape[-2])
            mask = kpos[None, :] > qpos[:, None]
            s = jnp.where(mask, -jnp.inf, s)
        o, m, l = _online_block_update(o, m, l, s, v_blk.astype(jnp.float32))
        perm = [(j, (j - 1) % axis_size) for j in range(axis_size)]
        k_blk = lax.ppermute(k_blk, axis_name, perm)
        v_blk = lax.ppermute(v_blk, axis_name, perm)
        return o, m, l, k_blk, v_blk

    o, m, l, _, _ = lax.fori_loop(0, axis_size, hop, (o, m, l, k, v))
    # Fully-masked rows (causal, strictly-future shards) have l == 0; the
    # where avoids 0/0 — their output is defined as 0.
    out = jnp.where(l[..., None] > 0, o / jnp.maximum(l, 1e-30)[..., None], 0.0)
    return out.astype(v.dtype)


def _ring_attention_flash(q, k, v, *, axis_name: str, causal: bool):
    """Ring attention with the Pallas flash kernel as the hop compute.

    Each hop runs :func:`~distributed_training_tpu.ops.flash_attention.
    flash_attention_lse` on (local q, visiting K/V block) and the per-hop
    ``(out_h, lse_h)`` pairs merge with the online-softmax recurrence in
    fp32 — the same math the exact path's ``_online_block_update`` applies
    per hop, lifted to normalized per-hop results. Causality needs no
    in-kernel global positions: relative to the local shard a visiting
    block is either the *diagonal* (same global offset → the kernel's own
    causal mask is exact), entirely in the *past* (no mask), or entirely in
    the *future* (skipped — ``lse = NEG_INF`` contributes zero weight, and
    no kernel runs). The backward ring falls out of autodiff: the lse
    cotangent threads the merge weights into each hop's kernel VJP and
    ``ppermute``'s transpose is the reverse hop.
    """
    from distributed_training_tpu.ops.flash_attention import (
        NEG_INF,
        flash_attention_lse,
    )

    axis_size = lax.axis_size(axis_name)
    my_idx = lax.axis_index(axis_name)
    o = jnp.zeros(q.shape, jnp.float32)
    lse_acc = jnp.full(q.shape[:-1], NEG_INF, jnp.float32)

    def diag(args):
        return flash_attention_lse(*args, causal=True)

    def full(args):
        return flash_attention_lse(*args, causal=False)

    def skip(args):
        qh, _, _ = args
        return (jnp.zeros(qh.shape, qh.dtype),
                jnp.full(qh.shape[:-1], NEG_INF, jnp.float32))

    def hop(i, carry):
        o, lse_acc, k_blk, v_blk = carry
        src = (my_idx + i) % axis_size
        if causal:
            out_h, lse_h = lax.cond(
                src == my_idx, diag,
                lambda args: lax.cond(src < my_idx, full, skip, args),
                (q, k_blk, v_blk))
        else:
            out_h, lse_h = full((q, k_blk, v_blk))
        # Online merge. NEG_INF is finite (-1e30), so the recurrence needs
        # no -inf/nan guards: a skipped hop's weight underflows to exactly
        # 0, and all-skipped rows merge to o = 0 with lse ≈ NEG_INF.
        lse_new = jnp.logaddexp(lse_acc, lse_h)
        w_acc = jnp.exp(lse_acc - lse_new)
        w_h = jnp.exp(lse_h - lse_new)
        o = o * w_acc[..., None] + out_h.astype(jnp.float32) * w_h[..., None]
        perm = [(j, (j - 1) % axis_size) for j in range(axis_size)]
        k_blk = lax.ppermute(k_blk, axis_name, perm)
        v_blk = lax.ppermute(v_blk, axis_name, perm)
        return o, lse_new, k_blk, v_blk

    o, _, _, _ = lax.fori_loop(0, axis_size, hop, (o, lse_acc, k, v))
    return o.astype(v.dtype)


def _flat_init(rng, shape, dtype, n_in_dims: int):
    """Replicate flax DenseGeneral's kernel init exactly: the draw happens
    on the 2D (fan_in, fan_out) flattening and is reshaped — keeping init
    values bit-identical to the DenseGeneral modules these projections
    replaced (checkpoints and equivalence tests depend on it)."""
    import numpy as np

    flat = (int(np.prod(shape[:n_in_dims])), int(np.prod(shape[n_in_dims:])))
    return nn.initializers.lecun_normal()(rng, flat, dtype).reshape(shape)


class _QKVProj(nn.Module):
    """QKV projection emitting q/k/v in the attention-native [B, H, T, d]
    layout as a tuple.

    Parameter-compatible with ``nn.DenseGeneral(features=(3, H, d),
    name='qkv')`` — same ``kernel``/``bias`` shapes, same init draws — but
    the head/time transpose lives in each einsum's OUTPUT indexing, where
    XLA folds it into the matmul epilogue, instead of as a separate
    [B, T, H, d] → [B, H, T, d] HBM pass after the projection (measured at
    ~5% of the GPT step, ``profiles/gpt_t1024.json``)."""

    num_heads: int
    head_dim: int
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        d_in = x.shape[-1]
        kernel = self.param(
            "kernel", functools.partial(_flat_init, n_in_dims=1),
            (d_in, 3, self.num_heads, self.head_dim), self.param_dtype)
        bias = self.param(
            "bias", nn.initializers.zeros,
            (3, self.num_heads, self.head_dim), self.param_dtype)
        # One einsum per q/k/v over a PARAM slice (tiny), not one fused
        # einsum sliced afterwards: the q/k/v consumers are Pallas custom
        # calls, whose operands cannot fuse a producer — slicing a fused
        # [3, B, H, T, d] output materializes three full activation copies
        # (profiled at ~0.29 ms × 12 blocks forward, plus the mirrored
        # backward concat, profiles/gpt_t1024_r4e.json). Param layout is
        # unchanged (still DenseGeneral-compatible).
        xc = x.astype(self.dtype)
        kc = kernel.astype(self.dtype)
        bc = bias.astype(self.dtype)
        q, k, v = (
            jnp.einsum("btm,mhd->bhtd", xc, kc[:, s])
            + bc[s][None, :, None, :]
            for s in range(3))
        return q, k, v


class _OutProj(nn.Module):
    """Output projection consuming [B, H, T, d] directly (conjugate of
    :class:`_QKVProj`; parameter-compatible with ``nn.DenseGeneral(
    features=D, axis=(-2, -1), name='out')``)."""

    features: int
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        h, d = x.shape[1], x.shape[-1]
        kernel = self.param(
            "kernel", functools.partial(_flat_init, n_in_dims=2),
            (h, d, self.features), self.param_dtype)
        bias = self.param("bias", nn.initializers.zeros, (self.features,),
                          self.param_dtype)
        y = jnp.einsum("bhtd,hdm->btm", x.astype(self.dtype),
                       kernel.astype(self.dtype))
        return y + bias.astype(self.dtype)


def paged_formulation(t_in: int, num_heads: int, head_dim: int,
                      page_size: int, dtype, kv_dtype: str | None) -> str:
    """Which way a paged decode call of these shapes attends: ``"kernel"``
    (``ops/paged_attention.py``: live pages fetched where they lie) or
    ``"gather"`` (every row's whole table gathered). Shapes and dtypes
    only — no knob, no backend. The int8 pool dequantizes in its gather;
    a wide window (the prefill chunk) or a page smaller than a tile is
    outside what the kernel serves."""
    from distributed_training_tpu.ops.paged_attention import kernel_fits

    if kv_dtype is None and kernel_fits(t_in, num_heads, head_dim,
                                        page_size, dtype):
        return "kernel"
    return "gather"


def paged_write_form(t_in: int, page_size: int, dtypes) -> str:
    """How a paged call ``t_in`` rows a slot wide writes its rows into
    pools of ``dtypes`` (:func:`paged_write`): ``"page"`` where the run
    spans at least a page and a page is whole tiles of every pool's
    dtype (16 rows of bfloat16, 8 of float32, 32 of int8 — the pool
    viewed ``[pages, page_size, width]`` is then a bitcast), else
    ``"row"``. Shapes and dtypes only — no knob, no backend."""
    whole_tiles = all(page_size % (32 // jnp.dtype(d).itemsize) == 0
                      for d in dtypes)
    return "page" if t_in >= page_size and whole_tiles else "row"


def paged_write(pools, rows, pages: PagedKV, page_size: int):
    """Write a call's new rows into paged pools through the page table;
    the one writer of every paged cache. ``pools`` are ``[pool_rows,
    width]`` arrays, ``rows`` the call's ``[B, T_in, ...]`` for each (the
    trailing dimensions make ``width``: ``[H, hd]`` per-head rows too);
    returns the pools as written (in place where the caller donated
    them). Invalid rows go to the null page (page 0), where duplicate
    writes are harmless garbage.

    :func:`paged_write_form` picks the form from the call's shapes:

    - **row** — each row scattered alone (the decode lane's row a slot,
      a verify window, pages smaller than a tile of some pool's dtype —
      the int8 pool at pages under 32 rows). On the device a row rewrites its whole tile row, so a
      wide call pays a serialized update per row, padding included.
    - **page** — a row of the call is a RUN: its valid rows are a
      prefix holding the consecutive positions ``positions[b, 0] ..``
      (the chunk lane's and the verify window's contract). The run's
      pages are written whole: a page the run covers only in part (the
      first, the last) is first merged with the rows it holds, so no
      row outside the run changes; a page holding no row of the run
      goes to the null page — a table entry past the slot's
      allocation may be stale. Bit-equal to the row form on every page
      but the null page."""
    table, positions, valid = pages
    b, t = positions.shape
    ps = page_size
    if paged_write_form(t, ps, [p.dtype for p in pools]) == "row":
        phys = jnp.take_along_axis(table, positions // ps, axis=1) * ps \
            + positions % ps
        write_idx = jnp.where(valid, phys, 0).reshape(-1)
        # per-head rows [B, T, H, hd] become tokens first, all of them,
        # as the GPT layer did before this writer was shared: the decode
        # programs' lowered text stays byte-equal (PR 36)
        rows = [r.reshape(b * t, *r.shape[2:]) if r.ndim > 3 else r
                for r in rows]
        return [pool.at[write_idx].set(r.reshape(b * t, -1))
                for pool, r in zip(pools, rows)]
    count = valid.sum(axis=1, dtype=jnp.int32)          # [B]
    off = positions[:, 0] % ps                          # first row's place
    span = (t + 2 * ps - 2) // ps                       # most pages a run spans
    j = jnp.arange(span)
    holds = (j * ps < (off + count)[:, None]) & (count > 0)[:, None]
    logical = jnp.minimum(positions[:, :1] // ps + j, table.shape[1] - 1)
    phys = jnp.where(holds, jnp.take_along_axis(table, logical, axis=1), 0)
    last = jnp.maximum(off + count - 1, 0) // ps        # the run's last page
    edges = jnp.stack(
        [phys[:, 0], jnp.take_along_axis(phys, last[:, None], axis=1)[:, 0]],
        axis=1)                                          # [B, 2]
    place = jnp.arange(span * ps)
    fresh = ((place >= off[:, None]) & (place < (off + count)[:, None])
             ).reshape(b, span, ps, 1)

    def write(pool, r):
        width = pool.shape[-1]
        paged = pool.reshape(-1, ps, width)
        # what the run's edge pages hold: page 0 of the run merges with
        # the first, every later page with the last (only those two can
        # be partial; the others are whole or go to the null page)
        held = paged[edges]                              # [B, 2, ps, w]
        old = jnp.where((j == 0)[None, :, None, None], held[:, :1],
                        held[:, 1:])
        # the rows where their pages hold them: padded by a page in
        # front and sliced from ps - off (a chip run timed this at 9 µs
        # a 256-row chunk into GPT-2 large's pool, 12-13 µs as an update
        # of a zero buffer, 39 µs row by row: PERF.md §6, PR 36)
        padded = jnp.pad(r.reshape(b, t, width),
                         ((0, 0), (ps, span * ps - t), (0, 0)))
        new = jax.vmap(lambda x, o: lax.dynamic_slice_in_dim(
            x, ps - o, span * ps))(padded, off).reshape(b, span, ps, width)
        merged = jnp.where(fresh, new, old)
        return paged.at[phys.reshape(-1)].set(
            merged.reshape(b * span, ps, width)).reshape(pool.shape)

    return [write(pool, r) for pool, r in zip(pools, rows)]


def paged_gather_attention(q, k_all, v_all, table, positions, *,
                           page_size: int, scales=None):
    """The gather formulation of paged attention, and the plain oracle of
    the kernel: q [B, T_in, H, hd] against pools [pool_rows, H·hd].

    Static shapes: row b reads its table's pages in logical order —
    positions 0..L-1 exactly as the contiguous cache lays them out
    (L = pages_per_slot × page_size; unallocated logical pages read the
    null page) — and the global-position causal mask hides everything
    past the query along with the future. ``scales`` (int8 pools): the
    per-row per-head (key, value) scales [pool_rows, H], applied in the
    gather — dequantization inside the same compiled program as the
    attention, so the compiled-program inventory grows by zero.
    """
    b, _, num_heads, head_dim = q.shape
    l_all = table.shape[1] * page_size
    gather_idx = (table[:, :, None] * page_size
                  + jnp.arange(page_size)[None, None, :]).reshape(b, l_all)
    heads = (b, l_all, num_heads, head_dim)
    kg = k_all[gather_idx].reshape(heads)  # [B, L, H, hd]
    vg = v_all[gather_idx].reshape(heads)
    if scales is not None:
        kg = kg.astype(jnp.float32) * scales[0][gather_idx][..., None]
        vg = vg.astype(jnp.float32) * scales[1][gather_idx][..., None]
    qh, kh, vh = (jnp.swapaxes(t, -3, -2) for t in (q, kg, vg))
    scale = 1.0 / jnp.sqrt(jnp.asarray(head_dim, jnp.float32))
    s = jnp.einsum("...qd,...kd->...qk", qh.astype(jnp.float32),
                   kh.astype(jnp.float32)) * scale
    kpos = jnp.arange(l_all)
    s = jnp.where(kpos[None, None, None, :] > positions[:, None, :, None],
                  -jnp.inf, s)
    p = jax.nn.softmax(s, axis=-1).astype(vh.dtype)
    out = jnp.einsum("...qk,...kd->...qd", p, vh)
    return jnp.swapaxes(out, -3, -2)  # back to [B, T, H, hd]


class RingSelfAttention(nn.Module):
    """Multi-head self-attention with ring-parallel sequence sharding.

    Drop-in for ``nn.MultiHeadDotProductAttention`` inside models whose
    sequence dimension is sharded over ``axis_name`` (e.g. ViT encoder
    blocks under a ``sequence`` mesh axis). QKV/out projections are local
    (position-wise); only K/V blocks travel the ring.

    ``attn_impl='flash'`` computes the attention with the Pallas blockwise
    kernel (``ops/flash_attention.py``) instead of the exact [T, T] softmax
    — linear HBM traffic, measured ~1.8× faster than the XLA exact path at
    T=4096 on v5e. Under a bound ring axis the kernel becomes the per-hop
    compute (ring+flash, :func:`_ring_attention_flash`), so the sequence-
    parallel path keeps the linear-memory kernel too.

    ``decode=True`` (autoregressive inference) appends this call's K/V to a
    ``cache`` collection of length ``cache_len`` and attends the incoming
    queries against the whole cache. The first decode call may carry the
    full prompt (chunked prefill); subsequent calls carry one token each.
    Unsharded only — generation shards over batch/model axes, not sequence.
    """

    num_heads: int
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32
    axis_name: str | None = None
    causal: bool = False
    attn_impl: str = "exact"  # exact | flash
    cache_len: int | None = None  # KV-cache length for decode=True
    # Paged KV cache (serving engine): the cache collection becomes a
    # position-free pool of kv_pages pages × kv_page_size tokens
    # (physical page 0 reserved as the null page) and decode calls route
    # through the :class:`PagedKV` page tables instead of cache_index.
    kv_page_size: int | None = None
    kv_pages: int | None = None  # physical pages INCLUDING the null page
    # Paged-pool storage dtype: None = store K/V at their compute dtype;
    # "int8" = pools held int8 with per-row per-head fp32 scales in
    # sibling cache variables (key_scales/value_scales), quantized on
    # scatter and dequantized in the gather of the SAME call — no extra
    # compiled program, and each row's scale depends only on that row's
    # own K/V, so lanes stay batch-composition-independent.
    kv_dtype: str | None = None

    def _decode_attend(self, q, k, v, head_dim: int):
        """Cached-KV attention: write K/V at ``cache_index``, attend q
        against the full cache. Shapes: q/k/v [B, T_in, H, hd]."""
        b, t_in = q.shape[0], q.shape[1]
        if self.kv_dtype is not None:
            raise ValueError(
                "kv_dtype requires the paged cache (kv_page_size set); "
                "the contiguous layout keeps full-precision slots")
        if self.cache_len is None:
            raise ValueError("decode=True requires cache_len")
        if not self.causal:
            raise ValueError("decode=True only makes sense for causal attention")
        shape = (b, self.cache_len, self.num_heads, head_dim)
        ck = self.variable("cache", "cached_key", jnp.zeros, shape, k.dtype)
        cv = self.variable("cache", "cached_value", jnp.zeros, shape, v.dtype)
        idx = self.variable(
            "cache", "cache_index", lambda: jnp.zeros((), jnp.int32))
        i0 = idx.value
        k_all = lax.dynamic_update_slice(ck.value, k, (0, i0, 0, 0))
        v_all = lax.dynamic_update_slice(cv.value, v, (0, i0, 0, 0))
        if not self.is_initializing():
            ck.value, cv.value = k_all, v_all
            idx.value = i0 + t_in

        # [B, T, H, hd] -> [B, H, T, hd]
        qh, kh, vh = (jnp.swapaxes(t, -3, -2) for t in (q, k_all, v_all))
        scale = 1.0 / jnp.sqrt(jnp.asarray(head_dim, jnp.float32))
        s = jnp.einsum("...qd,...kd->...qk", qh.astype(jnp.float32),
                       kh.astype(jnp.float32)) * scale
        # Global positions: queries sit at i0..i0+T_in-1; cache slots past
        # the write head are zeros but kpos > qpos masks them along with
        # the future — one mask covers both.
        qpos = i0 + jnp.arange(t_in)
        kpos = jnp.arange(self.cache_len)
        s = jnp.where(kpos[None, :] > qpos[:, None], -jnp.inf, s)
        # Past-the-end decode: dynamic_update_slice would clamp the write
        # start and silently corrupt history (the traced index cannot be
        # checked eagerly), so NaN-poison the WHOLE call when any of it
        # overflows — a chunk straddling the end also corrupts the slots its
        # clamped write landed on, so the in-bounds rows are wrong too.
        s = jnp.where(i0 + t_in > self.cache_len, jnp.nan, s)
        p = jax.nn.softmax(s, axis=-1).astype(vh.dtype)
        out = jnp.einsum("...qk,...kd->...qd", p, vh)
        return jnp.swapaxes(out, -3, -2)  # back to [B, T, H, hd]

    def _paged_decode_attend(self, q, k, v, head_dim: int, pages: PagedKV):
        """Paged-pool cached-KV attention (serving engine's decode path).

        Shapes: q/k/v [B, T_in, H, hd]; the cache collection holds one
        flat pool per K and V — [kv_pages * kv_page_size, H·hd], a row
        holding every head of one token, page 0 being the reserved null
        page. (Two dimensions, not [rows, H, hd]: the device lays a
        [rows, 20, 64] array out with ROWS minor-most, so every use of
        it as rows began and ended with a relayout of the whole pool;
        [rows, H·hd] is row-major on the device as it is here, and a page
        is one contiguous tile.) Each incoming token's K/V land at
        ``table[b, pos // ps] * ps + pos % ps`` (null page when ``valid``
        is False) through :func:`paged_write` — a page at a time for the
        chunk, a row at a time for the narrow lanes — in place: the pool
        that enters is the pool that leaves. Then every query row attends
        keys ``0..pos`` of its OWN row's page table, the rows this same
        call wrote included, in one
        of two formulations, chosen from the call's shapes and dtypes
        alone (:func:`paged_formulation`):

        - **kernel** — a narrow window (the decode lane's one row, a
          speculative verify window) on a pool in the compute dtype with
          tile-sized pages: the Pallas kernel fetches each slot's live
          pages from the pool by page index and nothing else.
        - **gather** — the prefill chunk, the int8 pool, pages smaller
          than a tile: every row gathers its table back into a
          contiguous-looking [L, H, hd] view (L = pages_per_slot × ps;
          for the chunk lane that is one slot's budget) and attends with
          the same global-position causal mask the contiguous path uses.
          The plain oracle the kernel is tested against.

        Row arithmetic follows :meth:`_decode_attend` — entries for
        written positions ARE the contiguous cache values, and everything
        past the query position (unwritten pages, stale freed pages, the
        null page) is masked exactly like the contiguous tail — so
        greedy outputs stay token-identical to the sequential
        ``Generator`` (pinned by tests/test_serving.py). A position past
        the table poisons THAT row with NaN in both.

        The engine's speculative verify window rides this same
        generality: ``T_in = spec_k + 1`` rows per slot (incoming token
        + drafts), scatter-before-read meaning each draft row attends
        the rows before it in the SAME call — which is what lets a
        rejected draft suffix be overwritten by the next window before
        any valid query can see it (tests/test_speculative.py pins the
        resulting bitwise oracle).
        """
        from distributed_training_tpu.ops.paged_attention import (
            paged_attention,
        )

        b, t_in = q.shape[0], q.shape[1]
        if self.kv_pages is None:
            raise ValueError("paged decode requires kv_pages (pool size)")
        if self.kv_dtype not in (None, "int8"):
            raise ValueError(
                f"kv_dtype must be None or 'int8', got {self.kv_dtype!r}")
        quant = self.kv_dtype == "int8"
        ps = int(self.kv_page_size)
        pool_rows = int(self.kv_pages) * ps
        width = self.num_heads * head_dim
        shape = (pool_rows, width)
        ck = self.variable("cache", "key_pages", jnp.zeros, shape,
                           jnp.int8 if quant else k.dtype)
        cv = self.variable("cache", "value_pages", jnp.zeros, shape,
                           jnp.int8 if quant else v.dtype)
        if quant:
            # Per-row per-head scales live beside the pools: a token-row's
            # K/V dequantize with ONE broadcast multiply after the gather,
            # and the scale travels with the page through every alias
            # (prefix-cache hits, preempt-and-restore) for free.
            sshape = (pool_rows, self.num_heads)
            cks = self.variable("cache", "key_scales", jnp.zeros, sshape,
                                jnp.float32)
            cvs = self.variable("cache", "value_scales", jnp.zeros, sshape,
                                jnp.float32)
        table, positions, valid = pages
        pools, rows = [ck, cv], [k, v]          # [B, T_in, H, hd] each
        if quant:
            # Quantize-on-scatter: symmetric per-row per-head int8,
            # scale = amax/127 over head_dim, round-to-nearest
            # (deterministic). A row's scale is a function of that row's
            # own K/V only — no cross-lane amax — which is what keeps
            # quantized decode bitwise batch-composition-independent.
            def _quantize_rows(rows):
                r32 = rows.astype(jnp.float32)
                amax = jnp.max(jnp.abs(r32), axis=-1)
                scl = jnp.where(amax > 0, amax / 127.0, 1.0)
                qr = jnp.clip(jnp.round(r32 / scl[..., None]),
                              -127, 127).astype(jnp.int8)
                return qr, scl

            k_rows, k_scl = _quantize_rows(k)
            v_rows, v_scl = _quantize_rows(v)
            pools += [cks, cvs]
            rows = [k_rows, v_rows, k_scl, v_scl]
        written = paged_write([p.value for p in pools], rows, pages, ps)
        if not self.is_initializing():
            for pool, new in zip(pools, written):
                pool.value = new
        k_all, v_all = written[:2]
        if quant:
            ks_all, vs_all = written[2:]

        if paged_formulation(t_in, self.num_heads, head_dim, ps, k.dtype,
                             self.kv_dtype) == "kernel":
            out = paged_attention(
                q.reshape(b, t_in, width), k_all, v_all, table, positions,
                valid, num_heads=self.num_heads, page_size=ps)
            out = out.reshape(q.shape)
        else:
            out = paged_gather_attention(
                q, k_all, v_all, table, positions, page_size=ps,
                scales=(ks_all, vs_all) if quant else None)
            # Dequantized math ran in fp32; hand back the compute dtype
            # the contiguous path would have produced.
            out = out.astype(v.dtype)
        # Per-ROW overflow poison (the contiguous path's guard, scoped to
        # the offending query so a padded chunk row can't poison real
        # ones): a write position past the page table corrupts whatever
        # page the clamped table gather aliased, so that row is wrong.
        overflow = positions >= table.shape[1] * ps
        return jnp.where(overflow[:, :, None, None], jnp.nan, out)

    @nn.compact
    def __call__(self, x, deterministic: bool = True, decode: bool = False,
                 pages: PagedKV | None = None):
        d = x.shape[-1]
        if d % self.num_heads:
            raise ValueError(f"hidden {d} not divisible by {self.num_heads} heads")
        head_dim = d // self.num_heads

        # Projections emit/consume the attention-native [B, H, T, d] layout
        # directly: the head/time permutation rides the matmul epilogues
        # instead of standalone transpose passes over the activations.
        q, k, v = _QKVProj(
            num_heads=self.num_heads, head_dim=head_dim, dtype=self.dtype,
            param_dtype=self.param_dtype, name="qkv")(x)  # each [B, H, T, hd]

        if decode:
            if self.axis_name is not None:
                raise ValueError(
                    "decode=True is the unsharded inference path; generation "
                    "does not compose with sequence-parallel attention")
            # The KV-cache keeps its [B, cache_len, H, hd] layout (decode is
            # latency-, not layout-bound; T is 1 per step).
            qd, kd, vd = (jnp.swapaxes(t, 1, 2) for t in (q, k, v))
            if pages is not None:
                if self.kv_page_size is None:
                    raise ValueError(
                        "pages= passed but kv_page_size is unset; build "
                        "the model with kv_page_size/kv_pages for the "
                        "paged decode path")
                out = self._paged_decode_attend(qd, kd, vd, head_dim, pages)
            else:
                out = self._decode_attend(qd, kd, vd, head_dim)
            out = jnp.swapaxes(out, 1, 2)  # [B, H, T, hd]
        else:
            # model.init traces this module outside shard_map where the mesh
            # axis is unbound; params don't depend on the ring, so init uses
            # the exact single-block path. Real applies keep the axis
            # requirement loud: an unbound axis at apply time raises,
            # catching models run under plain jit when they needed the
            # shard_map step.
            axis_name = None if self.is_initializing() else self.axis_name
            if self.attn_impl == "flash" and not self.is_initializing():
                # With a bound ring axis this is ring+flash: the Pallas
                # kernel computes each hop, (out, lse) pairs merge across
                # hops (see _ring_attention_flash) — the linear-memory
                # kernel and the linear-memory schedule compose.
                out = ring_attention(
                    q, k, v, axis_name=axis_name, causal=self.causal,
                    impl="flash")
            else:
                out = ring_attention(
                    q, k, v, axis_name=axis_name, causal=self.causal)

        return _OutProj(
            features=d, dtype=self.dtype, param_dtype=self.param_dtype,
            name="out")(out)
