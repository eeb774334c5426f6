"""Pallas grouped SwiGLU over the held experts (TPU kernel): each hit
expert's weights streamed once per pass, its rows picked from and added
back into VMEM.

An expert layer's (token, expert) pairs arrive sorted by held expert:
``pair_token`` / ``pair_weight`` [P] give each sorted pair's token and
routing weight, ``rows`` / ``starts`` [E] each held expert's run of them
(pairs routed to experts held elsewhere sort after the last run and are
never read). The result is, per token, ``sum_pairs w * FFN_e(x_token)``
in float32, with ``FFN_e(x) = (SiLU(x W1_e) * (x W3_e)) W2_e``.

The grid walks (visit, hidden tile). A visit is one **hit** expert: the
hit experts are listed first, in order, and the ``E - hits`` visits after
them name the last hit expert's last tile again, so the pipeline fetches
nothing for them and they compute nothing. A step's weight tiles are
``W1_e[:, j]``, ``W3_e[:, j]`` [d, th] and ``W2_e[j, :]`` [th, d]: the
BlockSpec pipeline fetches the next step's while this one computes, so the
next expert's read overlaps this expert's products, with no loop trip or
ramp between experts. Inside a step the expert's pairs go through in row
tiles of ``row_tile``: the tile's rows of ``x`` are copied from VMEM by
token, the three products run on the MXU with bf16 operands (the operands'
dtype) and float32 accumulation, and each live row is added into the
float32 accumulator ``[t, d]`` at its token under its weight. Rows are
read and written one at a time from VMEM — in float32, since Mosaic
cannot place a single bf16 row at a dynamic sublane — so their cost
follows the pairs and not ``t``.

``x`` (as float32) and the accumulator live in VMEM for the whole call,
so the kernel serves a pass whose ``t x d`` fits beside two steps' weight
tiles (:func:`kernel_fits`); the first step copies ``x`` in, the last
writes the accumulator out. Off the TPU the kernel runs in Pallas
interpret mode (``utils/compat.py::pallas_interpret``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distributed_training_tpu.utils.compat import pallas_interpret

NAME = "expert_ffn"
ROW_QUANTUM = 16             # a row tile is a whole number of bf16 tiles
LANES = 128
WEIGHT_TILE_BYTES = 12 * 1024 * 1024    # one step's three weight tiles
VMEM_BUDGET = 100 * 1024 * 1024         # of the v5e's 128 MiB


def row_tile(t: int, top_k: int, num_experts: int) -> int:
    """Rows an expert takes at a time in a pass of ``t`` tokens: what an
    expert gets on average (``t * top_k / num_experts`` over the router's
    experts), rounded up to a whole number of ``ROW_QUANTUM``."""
    mean = -(-t * top_k // num_experts)
    return -(-max(mean, 1) // ROW_QUANTUM) * ROW_QUANTUM


def hidden_tile(d: int, hidden: int, itemsize: int) -> int:
    """The widest slice of an expert's hidden width whose three weight
    tiles fit ``WEIGHT_TILE_BYTES``: the whole width where it fits, else
    the widest whole number of lanes that divides it (the narrowest such
    where none fits)."""
    if 3 * d * hidden * itemsize <= WEIGHT_TILE_BYTES or hidden % LANES:
        return hidden
    tiles = [th for th in range(hidden, 0, -LANES) if hidden % th == 0]
    fit = [th for th in tiles if 3 * d * th * itemsize <= WEIGHT_TILE_BYTES]
    return fit[0] if fit else tiles[-1]


def vmem_bytes(t: int, d: int, hidden: int, rows: int, itemsize: int) -> int:
    """VMEM the kernel holds: two steps' weight tiles, ``x`` and the
    accumulator (float32), a row tile's rows, hidden activations and
    output, and room for Mosaic's own."""
    th = hidden_tile(d, hidden, itemsize)
    return (2 * 3 * d * th * itemsize + 2 * t * d * 4
            + 2 * rows * d * 4 + 3 * rows * th * 4 + 2 * 1024 * 1024)


def kernel_fits(t: int, d: int, hidden: int, top_k: int, num_experts: int,
                dtype) -> bool:
    """Whether a pass of ``t`` tokens fits the kernel's VMEM."""
    return vmem_bytes(t, d, hidden, row_tile(t, top_k, num_experts),
                      jnp.dtype(dtype).itemsize) <= VMEM_BUDGET


def visits(rows):
    """The visit table of ``rows`` [E] (pairs per held expert): per visit
    its expert and its pair count. Hit experts first, in order; the rest
    name the last hit expert (expert 0 where none is hit) with no rows.
    Counted with comparisons and sums over ``[E, E]``: no sort, no
    scatter."""
    e = rows.shape[0]
    hit = rows > 0
    rank = jnp.cumsum(hit) - 1
    idx = jnp.arange(e, dtype=jnp.int32)
    first = (hit[None, :] & (rank[None, :] == idx[:, None]))
    expert = (first * idx[None, :]).sum(1, dtype=jnp.int32)
    n_hit = hit.sum(dtype=jnp.int32)
    last = jnp.max(jnp.where(hit, idx, 0))
    real = idx < n_hit
    return jnp.where(real, expert, last), jnp.where(real, rows[expert], 0)


def _kernel(v_expert, v_start, v_rows, p_token, p_weight, x_hbm, w1, w3, w2,
            o_hbm, xv, acc, xs, ys, sem, *, row_tile):
    v, j = pl.program_id(0), pl.program_id(1)

    @pl.when(jnp.logical_and(v == 0, j == 0))
    def _():
        copy = pltpu.make_async_copy(x_hbm, xv, sem.at[0])
        copy.start()
        acc[...] = jnp.zeros(acc.shape, acc.dtype)
        xs[...] = jnp.zeros(xs.shape, xs.dtype)
        copy.wait()

    rows = v_rows[v]

    @pl.when(rows > 0)
    def _():
        start = v_start[v]

        def tile(i, carry):
            base = start + i * row_tile
            live = jnp.minimum(rows - i * row_tile, row_tile)

            def pick(r, c):
                xs[pl.ds(r, 1), :] = xv[pl.ds(p_token[base + r], 1), :]
                return c

            jax.lax.fori_loop(0, live, pick, 0)
            xb = xs[...].astype(w1.dtype)
            a = jnp.dot(xb, w1[0], preferred_element_type=jnp.float32)
            b = jnp.dot(xb, w3[0], preferred_element_type=jnp.float32)
            ys[...] = jnp.dot((a * jax.nn.sigmoid(a) * b).astype(w2.dtype),
                              w2[0], preferred_element_type=jnp.float32)

            def add(r, c):
                at = pl.ds(p_token[base + r], 1)
                acc[at, :] += p_weight[base + r] * ys[pl.ds(r, 1), :]
                return c

            jax.lax.fori_loop(0, live, add, 0)
            return carry

        jax.lax.fori_loop(0, pl.cdiv(rows, row_tile), tile, 0)

    @pl.when(jnp.logical_and(v == pl.num_programs(0) - 1,
                             j == pl.num_programs(1) - 1))
    def _():
        copy = pltpu.make_async_copy(acc, o_hbm, sem.at[0])
        copy.start()
        copy.wait()


@functools.partial(jax.jit, static_argnames=("row_tile", "hidden",
                                             "interpret"))
def expert_ffn(x, w1, w3, w2, pair_token, pair_weight, rows, starts, *,
               row_tile: int, hidden: int | None = None,
               interpret: bool | None = None):
    """The held experts' part of a pass: ``x`` [t, d]; ``w1`` / ``w3`` [E,
    d, H], ``w2`` [E, H, d] (E held experts); ``pair_token`` int32 and
    ``pair_weight`` float32 [P], the pairs sorted by held expert;
    ``rows`` / ``starts`` int32 [E], each expert's count of them and the
    first. Returns float32 [t, d]: per token the sum over its pairs on
    held experts of weight x FFN (zero where it has none). ``row_tile``
    rows of an expert go through the products at a time; ``hidden`` is
    the hidden tile (:func:`hidden_tile` by default)."""
    t, d = x.shape
    e, _, h = w1.shape
    th = hidden or hidden_tile(d, h, w1.dtype.itemsize)
    n_h = h // th
    expert, count = visits(rows.astype(jnp.int32))
    start = starts.astype(jnp.int32)[expert]

    def tile_of(v, j, v_expert, v_start, v_rows, *_):
        return jnp.where(v_rows[v] > 0, j, n_h - 1)

    w_in = pl.BlockSpec((1, d, th), lambda v, j, ve, *s: (
        ve[v], 0, tile_of(v, j, ve, *s)))
    w_out = pl.BlockSpec((1, th, d), lambda v, j, ve, *s: (
        ve[v], tile_of(v, j, ve, *s), 0))
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    need = vmem_bytes(t, d, h, row_tile, w1.dtype.itemsize)
    return pl.pallas_call(
        functools.partial(_kernel, row_tile=row_tile),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(e, n_h),
            in_specs=[hbm, w_in, w_in, w_out],
            out_specs=hbm,
            scratch_shapes=[
                pltpu.VMEM((t, d), jnp.float32),
                pltpu.VMEM((t, d), jnp.float32),
                pltpu.VMEM((row_tile, d), jnp.float32),
                pltpu.VMEM((row_tile, d), jnp.float32),
                pltpu.SemaphoreType.DMA((1,))]),
        out_shape=jax.ShapeDtypeStruct((t, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=min(need + 16 * 1024 * 1024,
                                 120 * 1024 * 1024)),
        interpret=pallas_interpret(interpret),
        name=NAME,
    )(expert, start, count, pair_token.astype(jnp.int32),
      pair_weight.astype(jnp.float32), x.astype(jnp.float32), w1, w3, w2)


def expert_walk(x, w1, w3, w2, pair_token, pair_weight, rows, starts, *,
                block_rows: int):
    """The same sum in plain XLA, as the layer computed it before the
    kernel: one loop over ``block_rows``-row blocks of every held expert's
    run in turn — the block's rows gathered, multiplied by its expert in
    ``x``'s dtype, and scattered back into a float32 ``[t, d]`` under their
    weights. An expert without pairs has no block. The kernel's yardstick
    in the tests and in ``tools/expert_ffn_bench.py``."""
    t, d = x.shape
    r = min(int(block_rows), t)
    token = jnp.pad(pair_token, (0, r))
    weight = jnp.pad(pair_weight.astype(jnp.float32), (0, r))
    blocks = (rows + r - 1) // r
    ends = jnp.cumsum(blocks)

    def block(b, acc):
        # Block ``b`` is the expert's whose span of the running block
        # totals holds ``b``.
        e = (ends <= b).sum(dtype=jnp.int32)
        i = b - (ends[e] - blocks[e])
        at = starts[e] + i * r
        tok = jax.lax.dynamic_slice_in_dim(token, at, r)
        wgt = jax.lax.dynamic_slice_in_dim(weight, at, r)
        wgt = jnp.where(i * r + jnp.arange(r) < rows[e], wgt, 0.0)
        xe = x[tok]
        h = jax.nn.silu(jnp.dot(xe, w1[e])) * jnp.dot(xe, w3[e])
        y = jnp.dot(h, w2[e])
        return acc.at[tok].add(y.astype(jnp.float32) * wgt[:, None])

    return jax.lax.fori_loop(0, ends[-1], block,
                             jnp.zeros((t, d), jnp.float32))
