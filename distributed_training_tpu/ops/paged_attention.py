"""Pallas paged decode attention (TPU kernel): read a slot's live pages
where they lie in the pool.

The serving engine's decode lane attends ``W`` query rows per slot (one
token, or a speculative verify window) against that slot's cached
context, which lives in a paged pool ``[pool_rows, H·hd]`` — row
``page × page_size + offset``, page 0 the null page
(``parallel/ring_attention.py::_paged_decode_attend``,
``serving/pages.py``). The gather formulation there copies every slot's
whole page budget out of the pool whatever is live. This kernel moves
only live bytes: the page table and the per-row positions arrive as
scalar prefetch, K and V stay in HBM, and each slot's live pages are
DMA'd page by page into a double-buffered VMEM block of
``pages_per_block`` pages. Pages past a slot's last valid position are
neither fetched nor computed.

Work list, not a grid: the wrapper flattens the live (slot, block) pairs
into one list (a few integer ops on ``[B]`` arrays), and the kernel is a
single invocation that loops over it — the loop's trip count is data, so
dead blocks cost nothing, and item ``w + 1``'s pages are in flight while
item ``w`` computes, across slot boundaries too.

All heads at once on the MXU: a row of the pool holds every head
(``H·hd`` lanes), so the queries of one slot are laid out block-
diagonally — row ``w·Hp + h`` holds head ``h`` of query ``w`` in lanes
``h·hd .. (h+1)·hd`` and zeros elsewhere (``Hp`` = H rounded up to the
sublane tile). ``Qbd · Kᵀ`` is then every head's scores in one product,
``P · V`` every head's values (the off-diagonal lanes are dropped at the
end), with one online-softmax accumulator per (slot, head, row): scores
and the running max / sum in f32, operands in the pool's dtype.

The shared-row form (:func:`paged_latent_attention`): where a key is ONE
row for all heads — latent attention's cache row ``[c_kv | k_rope]`` in
the absorbed form — the pool serves as K and as V (the row's first
``value_lanes`` lanes), a slot's query stack is its ``W·H`` absorbed
queries as they are (no block diagonal, nothing dropped at the end), and
one product scores every head against a block of rows. Same work list,
same page-by-page DMA and double buffer; the queries and the outputs stay
in HBM and travel a slot at a time (the next slot's queries in flight
behind the current one's last block), so nothing in VMEM grows with the
number of slots.

Off the TPU the kernels run in Pallas interpret mode
(``utils/compat.py::pallas_interpret``). Prior art for the page-table
indexing: ``jax.experimental.pallas.ops.tpu.paged_attention``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distributed_training_tpu.ops.flash_attention import NEG_INF
from distributed_training_tpu.utils.compat import pallas_interpret

# Rows of K (and of V) one work item holds in VMEM: 128 rows × 1280 lanes
# of bf16 is 320 KB a buffer, 1.3 MB for K and V double-buffered.
BLOCK_ROWS = 128
# The block-diagonal query stack has W·Hp rows; past one 128-row MXU pass
# the f32 accumulator [W·Hp, H·hd] outgrows its use (a 256-row prefill
# chunk would need 6144 rows), so wider windows keep the gather.
MAX_QUERY_ROWS = 128
# Rows of the latent pool one work item of the shared-row form holds: a row
# is narrower (640 lanes) and serves as K and V, so a block twice as long
# costs the same VMEM a buffer and halves the items a slot takes.
LATENT_BLOCK_ROWS = 256


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def kernel_fits(t_in: int, num_heads: int, head_dim: int, page_size: int,
                dtype, *, value_lanes: int | None = None) -> bool:
    """Whether a call's shapes are ones the kernel serves: a narrow query
    window, and pages that are whole tiles of the pool's dtype (a page is
    one DMA: ``page_size`` a multiple of the dtype's sublane tile, ``H·hd``
    a multiple of the 128 lanes). Decided from shapes and dtype alone —
    the same answer on every backend.

    ``value_lanes`` asks about the shared-row form instead: ``head_dim``
    is then the width of a pool row (one key for all heads) and
    ``value_lanes`` how many of its leading lanes are the value; both
    whole lane tiles, and the slot's ``t_in·H`` query rows whole sublane
    tiles of the pool's dtype."""
    itemsize = jnp.dtype(dtype).itemsize
    if itemsize not in (2, 4):
        return False
    tile = 32 // itemsize
    if value_lanes is not None:
        return (t_in * num_heads <= MAX_QUERY_ROWS
                and (t_in * num_heads) % tile == 0
                and page_size % tile == 0 and head_dim % 128 == 0
                and value_lanes % 128 == 0 and 0 < value_lanes <= head_dim)
    return (t_in * _round_up(num_heads, 8) <= MAX_QUERY_ROWS
            and page_size % tile == 0
            and (num_heads * head_dim) % 128 == 0)


def _page_copies(item_slot, item_blk, n_live, table, pools, sems, w, buf,
                 act, *, page_size, pages_per_block, pages_per_slot):
    """Start, or wait for, the DMAs of work item ``w``'s live pages into
    buffer ``buf`` of each ``(pool in HBM, block in VMEM)`` of ``pools``.
    A wait mirrors its start page for page."""
    ps, ppb = page_size, pages_per_block
    slot, blk = item_slot[w], item_blk[w]
    live = n_live[slot]
    for i in range(ppb):
        @pl.when(blk * (ps * ppb) + i * ps < live)
        def _(i=i):
            page = table[slot * pages_per_slot + blk * ppb + i]
            src = pl.ds(pl.multiple_of(page * ps, ps), ps)
            dst = pl.ds(i * ps, ps)
            for j, (hbm, vmem) in enumerate(pools):
                copy = pltpu.make_async_copy(
                    hbm.at[src, :], vmem.at[buf, dst, :], sems.at[j, buf])
                getattr(copy, act)()        # "start" or "wait"


def _kernel(item_slot, item_blk, n_items, n_live, q_pos, table,
            q_ref, mask_ref, k_hbm, v_hbm, o_ref,
            kbuf, vbuf, sems, acc, m, l,
            *, scale, w_rows, hp, page_size, pages_per_block,
            pages_per_slot):
    ps, ppb = page_size, pages_per_block
    block_rows = ps * ppb
    n_q = w_rows * hp

    pages = functools.partial(
        _page_copies, item_slot, item_blk, n_live, table,
        ((k_hbm, kbuf), (v_hbm, vbuf)), sems, page_size=ps,
        pages_per_block=ppb, pages_per_slot=pages_per_slot)

    total = n_items[0]
    # Slots with nothing live get no work item: their rows read zero.
    o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(total > 0)
    def _():
        pages(0, 0, "start")

    def item(w, carry):
        buf = lax.rem(w, 2)

        @pl.when(w + 1 < total)
        def _():
            pages(w + 1, 1 - buf, "start")

        pages(w, buf, "wait")
        slot, blk = item_slot[w], item_blk[w]
        live = n_live[slot]
        k = kbuf[buf]
        v = vbuf[buf]

        @pl.when(blk == 0)
        def _():
            acc[...] = jnp.zeros_like(acc)
            m[...] = jnp.full_like(m, NEG_INF)
            l[...] = jnp.zeros_like(l)

        # Block-diagonal queries and their positions, from the slot's W
        # rows (an invalid row's position is -1: it sees no key).
        mask = mask_ref[...]          # [Hp, D]: 1 where lane is in head
        row = lax.broadcasted_iota(jnp.int32, (n_q, 1), 0)
        qpos = jnp.full((n_q, 1), -1, jnp.int32)
        stack = []
        for wi in range(w_rows):
            r = slot * w_rows + wi
            stack.append((q_ref[pl.ds(r, 1), :] * mask).astype(k.dtype))
            qpos = jnp.where((row >= wi * hp) & (row < (wi + 1) * hp),
                             q_pos[r], qpos)
        qbd = stack[0] if w_rows == 1 else jnp.concatenate(stack, axis=0)

        s = lax.dot_general(qbd, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
        kpos = blk * block_rows + lax.broadcasted_iota(
            jnp.int32, (n_q, block_rows), 1)
        s = jnp.where(kpos > qpos, NEG_INF, s)
        m_prev = m[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l[...] = jnp.broadcast_to(
            l[:, :1] * corr + jnp.sum(p, axis=-1, keepdims=True), l.shape)
        # Rows past the slot's last valid position were not fetched (or
        # are another sequence's): p is 0 there, but 0 × a stale NaN is
        # NaN, so they leave the product as zeros.
        vrow = blk * block_rows + lax.broadcasted_iota(
            jnp.int32, (block_rows, 1), 0)
        v = jnp.where(vrow < live, v, jnp.zeros_like(v))
        acc[...] = acc[...] * corr + lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m[...] = jnp.broadcast_to(m_new, m.shape)

        @pl.when((blk + 1) * block_rows >= live)
        def _():
            # The slot's last block: normalize, keep each head's own
            # lanes, and fold the Hp rows of a query into its one row.
            lsum = l[:, :1]
            out = acc[...] / jnp.where(lsum > 0, lsum, 1.0)
            for wi in range(w_rows):
                r = slot * w_rows + wi
                own = jnp.where(mask > 0, out[wi * hp:(wi + 1) * hp], 0.0)
                o_ref[pl.ds(r, 1), :] = jnp.where(
                    q_pos[r] >= 0, jnp.sum(own, axis=0, keepdims=True), 0.0)

        return carry

    lax.fori_loop(0, total, item, None)


def _work_list(n_live, block_rows: int, max_blocks: int):
    """The live (slot, block) pairs in slot order, padded to the static
    ``B × max_blocks``, and how many of them are real."""
    n_blocks = -(-n_live // block_rows)
    ends = jnp.cumsum(n_blocks)
    w = jnp.arange(n_live.shape[0] * max_blocks, dtype=jnp.int32)
    slot = jnp.minimum(jnp.searchsorted(ends, w, side="right"),
                       n_live.shape[0] - 1).astype(jnp.int32)
    blk = w - (ends - n_blocks)[slot]
    return slot, blk.astype(jnp.int32), ends[-1:].astype(jnp.int32)


@functools.partial(jax.jit,
                   static_argnames=("num_heads", "page_size", "interpret"))
def paged_attention(q, k_pool, v_pool, table, positions, valid, *,
                    num_heads: int, page_size: int,
                    interpret: bool | None = None):
    """Causal attention of ``q`` [B, W, H·hd] over each slot's pages.

    ``k_pool`` / ``v_pool`` [pool_rows, H·hd] hold row ``p`` of slot
    ``b`` at ``table[b, p // page_size] · page_size + p % page_size``;
    ``positions`` / ``valid`` [B, W] give each query row's position and
    whether it counts. A valid row at position ``p`` attends rows
    ``0..p`` of its own table; an invalid row reads zero. Nothing past a
    slot's last valid position is fetched. Returns [B, W, H·hd] in
    ``q``'s dtype. The caller checks :func:`kernel_fits` first.

    Jitted so that a model's layers share one trace and one lowering of
    the kernel: a 36-layer decode program lowered 36 copies of it in 41 s
    (sandbox, for a described v5e) where the rest of the program takes 2.
    """
    b, w_rows, d = q.shape
    head_dim = d // num_heads
    pages_per_slot = table.shape[1]
    ppb = min(BLOCK_ROWS // page_size, pages_per_slot)
    block_rows = ppb * page_size
    hp = _round_up(num_heads, 8)
    n_q = w_rows * hp

    q_pos = jnp.where(valid, positions, -1).astype(jnp.int32)
    n_live = jnp.minimum(jnp.max(q_pos, axis=1) + 1,
                         pages_per_slot * page_size)
    slot, blk, total = _work_list(
        n_live, block_rows, -(-pages_per_slot // ppb))
    lane_head = jnp.arange(d) // head_dim
    mask = (lane_head[None, :] == jnp.arange(hp)[:, None]).astype(
        jnp.float32)

    kernel = functools.partial(
        _kernel, scale=1.0 / (head_dim ** 0.5), w_rows=w_rows, hp=hp,
        page_size=page_size, pages_per_block=ppb,
        pages_per_slot=pages_per_slot)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6,
            grid=(1,),
            in_specs=[vmem, vmem, hbm, hbm],
            out_specs=vmem,
            scratch_shapes=[
                pltpu.VMEM((2, block_rows, d), k_pool.dtype),
                pltpu.VMEM((2, block_rows, d), v_pool.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((n_q, d), jnp.float32),
                pltpu.VMEM((n_q, 128), jnp.float32),
                pltpu.VMEM((n_q, 128), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((b * w_rows, d), jnp.float32),
        interpret=pallas_interpret(interpret),
        name="paged_attention",
    )(slot, blk, total, n_live, q_pos.reshape(-1),
      table.reshape(-1).astype(jnp.int32),
      q.reshape(b * w_rows, d).astype(jnp.float32), mask, k_pool, v_pool)
    return out.reshape(b, w_rows, d).astype(q.dtype)


def _latent_kernel(item_slot, item_blk, item_qbuf, n_items, n_live, q_pos,
                   table, q_hbm, pool_hbm, o_hbm,
                   kbuf, qbuf, obuf, sems, qsems, osem, acc, m, l,
                   *, scale, w_rows, heads, value_lanes, page_size,
                   pages_per_block, pages_per_slot):
    block_rows = page_size * pages_per_block
    n_q = w_rows * heads
    pages = functools.partial(
        _page_copies, item_slot, item_blk, n_live, table,
        ((pool_hbm, kbuf),), sems, page_size=page_size,
        pages_per_block=pages_per_block, pages_per_slot=pages_per_slot)

    def queries(w):
        """The DMA of work item ``w``'s slot's query stack into the buffer
        the work list gave that slot (consecutive worked slots alternate,
        so the next slot's queries land beside the current one's)."""
        return pltpu.make_async_copy(
            q_hbm.at[item_slot[w]], qbuf.at[item_qbuf[w]],
            qsems.at[item_qbuf[w]])

    def result(slot):
        return pltpu.make_async_copy(obuf, o_hbm.at[slot], osem.at[0])

    total = n_items[0]

    @pl.when(total > 0)
    def _():
        queries(0).start()
        pages(0, 0, "start")

    def item(w, carry):
        buf = lax.rem(w, 2)
        slot, blk = item_slot[w], item_blk[w]

        @pl.when(w + 1 < total)
        def _():
            pages(w + 1, 1 - buf, "start")

            @pl.when(item_blk[w + 1] == 0)
            def _():
                queries(w + 1).start()

        @pl.when(blk == 0)
        def _():
            queries(w).wait()
            acc[...] = jnp.zeros_like(acc)
            m[...] = jnp.full_like(m, NEG_INF)
            l[...] = jnp.zeros_like(l)

        pages(w, buf, "wait")
        live = n_live[slot]
        k = kbuf[buf]                            # [block_rows, width]
        q = qbuf[item_qbuf[w]]                   # [n_q, width]

        # Row w·H + h of the stack is head h of the slot's query row w (an
        # invalid row's position is -1: it sees no key).
        row = lax.broadcasted_iota(jnp.int32, (n_q, 1), 0)
        qpos = jnp.full((n_q, 1), -1, jnp.int32)
        for wi in range(w_rows):
            qpos = jnp.where((row >= wi * heads) & (row < (wi + 1) * heads),
                             q_pos[slot * w_rows + wi], qpos)

        s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
        kpos = blk * block_rows + lax.broadcasted_iota(
            jnp.int32, (n_q, block_rows), 1)
        s = jnp.where(kpos > qpos, NEG_INF, s)
        m_prev = m[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l[...] = jnp.broadcast_to(
            l[:, :1] * corr + jnp.sum(p, axis=-1, keepdims=True), l.shape)
        # The value is the row's leading lanes. Rows past the slot's last
        # valid position were not fetched: p is 0 there, but 0 × a stale
        # NaN is NaN, so they enter the product as zeros.
        vrow = blk * block_rows + lax.broadcasted_iota(
            jnp.int32, (block_rows, 1), 0)
        v = jnp.where(vrow < live, k[:, :value_lanes], 0).astype(k.dtype)
        acc[...] = acc[...] * corr + lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m[...] = jnp.broadcast_to(m_new, m.shape)

        @pl.when((blk + 1) * block_rows >= live)
        def _():
            # The slot's last block: the buffer's previous result has left
            # (it was started a whole slot ago), normalize and send.
            @pl.when(w >= blk + 1)
            def _():
                result(slot).wait()

            lsum = l[:, :1]
            obuf[...] = (acc[...] / jnp.where(lsum > 0, lsum, 1.0)).astype(
                obuf.dtype)
            result(slot).start()

        return carry

    lax.fori_loop(0, total, item, None)

    @pl.when(total > 0)
    def _():
        result(0).wait()


@functools.partial(jax.jit, static_argnames=(
    "value_lanes", "page_size", "scale", "interpret"))
def paged_latent_attention(q, pool, table, positions, valid, *,
                           value_lanes: int, page_size: int, scale: float,
                           interpret: bool | None = None):
    """Causal attention of ``q`` [B, W, H, width] over each slot's pages of
    a pool whose row is one key for all heads (the shared-row form).

    ``pool`` [pool_rows, width] holds row ``p`` of slot ``b`` at
    ``table[b, p // page_size] · page_size + p % page_size``; a score is
    ``q · row · scale`` over the whole width, a value the row's first
    ``value_lanes`` lanes. ``positions`` / ``valid`` [B, W] as in
    :func:`paged_attention`: a valid row at position ``p`` attends rows
    ``0..p`` of its own table, an invalid row reads zero, nothing past a
    slot's last valid position is fetched or computed. Scores and the
    online softmax in f32, operands in the pool's dtype. Returns [B, W, H,
    value_lanes] in ``q``'s dtype. The caller checks :func:`kernel_fits`
    (``value_lanes=``) first. Jitted for the reason :func:`paged_attention`
    is."""
    b, w_rows, heads, width = q.shape
    pages_per_slot = table.shape[1]
    ppb = min(LATENT_BLOCK_ROWS // page_size, pages_per_slot)
    block_rows = ppb * page_size
    n_q = w_rows * heads

    q_pos = jnp.where(valid, positions, -1).astype(jnp.int32)
    n_live = jnp.minimum(jnp.max(q_pos, axis=1) + 1,
                         pages_per_slot * page_size)
    slot, blk, total = _work_list(
        n_live, block_rows, -(-pages_per_slot // ppb))
    # the worked slots in order, alternating between the two query buffers
    qbuf_of = (jnp.cumsum(n_live > 0) - 1) % 2

    kernel = functools.partial(
        _latent_kernel, scale=scale, w_rows=w_rows, heads=heads,
        value_lanes=value_lanes, page_size=page_size, pages_per_block=ppb,
        pages_per_slot=pages_per_slot)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=7,
            grid=(1,),
            in_specs=[hbm, hbm],
            out_specs=hbm,
            scratch_shapes=[
                pltpu.VMEM((2, block_rows, width), pool.dtype),
                pltpu.VMEM((2, n_q, width), pool.dtype),
                pltpu.VMEM((n_q, value_lanes), q.dtype),
                pltpu.SemaphoreType.DMA((1, 2)),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.DMA((1,)),
                pltpu.VMEM((n_q, value_lanes), jnp.float32),
                pltpu.VMEM((n_q, 128), jnp.float32),
                pltpu.VMEM((n_q, 128), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((b, n_q, value_lanes), q.dtype),
        interpret=pallas_interpret(interpret),
        name="paged_latent_attention",
    )(slot, blk, qbuf_of[slot].astype(jnp.int32), total, n_live,
      q_pos.reshape(-1), table.reshape(-1).astype(jnp.int32),
      q.reshape(b, n_q, width).astype(pool.dtype), pool)
    # Slots with nothing live got no work item, and an invalid row of a
    # live slot attended nothing: both read zero.
    out = out.reshape(b, w_rows, heads, value_lanes)
    return jnp.where((q_pos >= 0)[:, :, None, None], out, 0)
