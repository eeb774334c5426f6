"""Decoder LM of the DeepSeek-V3.2 family for the serving engine: latent
attention whose cache row is the latent itself, a learned selection of the
keys each query attends, grouped sigmoid routing over held experts.

What differs from :class:`~distributed_training_tpu.models.gpt.TransformerLM`
and what each piece forces on the serving path:

- **RMSNorm, rotary positions (YaRN), gated SiLU FFNs, no bias, no position
  table.** ``max_len`` is a position limit, not a table's length.
- **Latent attention (MLA).** A 1536-wide query latent, and a key/value
  latent ``[c_kv (512) | k_rope (64)]`` that IS the cache row: one pool
  ``latent_pages`` a layer where the dense model keeps K and V ``[rows,
  heads x head size]`` each. The row is held a whole number of 128-lane
  tiles wide (576 -> 640, zeros behind): the TPU lays a ``[rows, 576]``
  leaf out rows-minor, and the scatter of a step's rows then copies the
  whole pool twice (the relayout PR 25 found under the K/V pools); at 640
  the leaf is rows-major, a page is one run of tiles and the write is in
  place. Two forms of the same mathematics:
  *per-head* (keys and values expanded from the latent: cheap per key when
  many queries share the keys — the prefill chunk) and *absorbed* (the
  expansion folded into the query and the output: cheap when one query
  reads few keys — the decode row).
- **The indexer (learned sparse attention).** Its own 128-wide key per
  token, cached beside the latent in ``index_pages [rows, 128]`` under the
  same page table; a query's index scores over its live keys pick the exact
  top ``index_topk`` of them, and attention runs over those alone.
- **Experts.** :class:`~distributed_training_tpu.models.moe.HeldExpertsMlp`:
  routed over all experts, computed for the held ones.

A model of the family may lack the query latent (``q_rank=None``: one
matrix ``wq [d, heads, nope + rope]``) and the indexer (``index_topk=None``:
no ``index_*`` leaf, one pool a layer, every query attends every earlier
key; ``sarvam_mla`` is such a model). The lanes of that dense case are at
the end of this text.

Two lanes, chosen from the call's width alone (:meth:`DeepseekV32LM.
paged_lane`): ``sparse-gather`` for a narrow window (the decode lane's one
row a slot: index scores over the slot's table, the selection as a mask
(:func:`exact_topk_mask`: a threshold found by counting, no sort), the
mask's positions in ascending order (:func:`mask_positions`), those latent
rows gathered, absorbed attention) and ``masked-blocks`` for a wide one
(the prefill chunk: index scores and per-head attention over key blocks
of ``key_block`` rows, only as many blocks as the chunk's positions reach,
the selection as a mask, softmax accumulated online — so nothing of size
heads x chunk x context is ever held, and every pass costs what the live
context costs, not the budget; where the heads are whole lane tiles wide, a
block's attention is one call of the kernel ``ops/masked_attention.py``,
the lane ``masked-blocks-kernel``). Both select the same set: the top
``index_topk`` by score, ties to the lower position.

Without the indexer the same two widths are dense: a narrow window reads
every live row of its slot in the absorbed form — where they lie, through
the shared-row kernel of ``ops/paged_attention.py`` (all heads of a slot
against the one latent row a key is, the row's first ``kv_rank`` lanes the
value: ``dense-latent-kernel``), or, where that kernel does not fit (toy
widths, windows whose rows x heads pass 128), over a gather of the slot's
page budget (``dense-latent-gather``); the chunk takes ``masked-blocks``
with the causal mask alone and no index pass.
"""

from __future__ import annotations

import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from distributed_training_tpu.models.moe import (
    GatedMlp,
    HeldExpertsMlp,
    model_expert_lane,
)
from distributed_training_tpu.ops import masked_attention, paged_attention
from distributed_training_tpu.parallel.ring_attention import (
    PagedKV,
    paged_write,
)

HIGHEST = jax.lax.Precision.HIGHEST
# A decode window at most this wide takes the sparse-gather lane.
NARROW_WINDOW = 8
LANES = 128      # a pool row is a whole number of these wide


def paged_lane(t_in: int, sparse: bool) -> str:
    """The attention formulation a paged call ``t_in`` rows wide takes, of
    a model with the indexer (``sparse``) or without."""
    if t_in > NARROW_WINDOW:
        return "masked-blocks"
    return "sparse-gather" if sparse else "dense-latent-gather"


KERNEL_LANE = "masked-blocks-kernel"   # masked-blocks, a block in the kernel
DENSE_KERNEL_LANE = "dense-latent-kernel"   # live rows read where they lie


def lane_name(model, t_in: int, sparse: bool, page_size: int | None) -> str:
    """What a latent model's ``paged_lane`` answers: :func:`paged_lane`'s
    name, with ``-kernel`` where the kernel serves the shapes. ``model``
    gives the widths (``num_heads``, ``kv_rank``, ``nope_dim``,
    ``rope_dim``, ``v_dim``, ``key_block``, ``dtype``)."""
    lane = paged_lane(t_in, sparse)
    if lane == "masked-blocks" and masked_attention.kernel_fits(
            1, t_in, model.key_block, model.nope_dim, model.rope_dim,
            model.v_dim, model.dtype):
        return KERNEL_LANE
    if lane == "dense-latent-gather" and dense_kernel_fits(
            t_in, model.num_heads, model.kv_rank, model.rope_dim, page_size,
            model.dtype):
        return DENSE_KERNEL_LANE
    return lane


def dense_kernel_fits(t_in: int, num_heads: int, kv_rank: int, rope_dim: int,
                      page_size: int | None, dtype) -> bool:
    """Whether a dense window ``t_in`` rows wide reads its slot's live rows
    through the shared-row kernel of ``ops/paged_attention.py``: the pool's
    row is ``[c_kv | k_rope]`` padded to whole lane tiles, its first
    ``kv_rank`` lanes the value."""
    width = kv_rank + rope_dim
    return page_size is not None and paged_attention.kernel_fits(
        t_in, num_heads, width + -width % LANES, int(page_size), dtype,
        value_lanes=kv_rank)


def yarn_frequencies(dim: int, base: float, factor: float, original: int,
                     beta_fast: float, beta_slow: float) -> np.ndarray:
    """The ``dim / 2`` rotary frequencies under YaRN scaling: frequencies
    that turn more than ``beta_fast`` times over the original context stay,
    those that turn fewer than ``beta_slow`` times are divided by
    ``factor``, and a linear ramp joins the two."""
    i = np.arange(dim // 2, dtype=np.float64)
    f = base ** (-2.0 * i / dim)

    def corr(turns):
        return dim * math.log(original / (2 * math.pi * turns)) \
            / (2 * math.log(base))

    low = max(math.floor(corr(beta_fast)), 0)
    high = min(math.ceil(corr(beta_slow)), dim - 1)
    ramp = np.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    smooth = 1.0 - ramp
    return (f / factor * (1.0 - smooth) + f * smooth).astype(np.float32)


def yarn_softmax_scale(head_dim: int, factor: float, mscale: float) -> float:
    m = 0.1 * mscale * math.log(factor) + 1.0
    return head_dim ** -0.5 * m * m


def rotate_interleaved(x, cos, sin):
    """Rotate the pairs ``(2j, 2j + 1)`` of the last axis; ``cos`` / ``sin``
    ``[..., dim / 2]`` broadcast against ``x[..., 0::2]``. In float32."""
    x = x.astype(jnp.float32)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(x.shape)


def rotate_half_split(x, cos, sin):
    """Rotate the pairs ``(j, j + dim / 2)`` of the last axis. In float32."""
    x = x.astype(jnp.float32)
    a, b = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)


# Widths, in runs, at which :func:`exact_topk_mask` is compiled when told
# how many runs are live: a call takes the first that covers them.
LIVE_RUNS = (4, 8, 12)


def exact_topk_mask(scores, k: int, live=None):
    """Boolean mask of the ``k`` highest entries of each row (last axis)
    of float32 ``scores``; a -inf entry is never among them, so a row
    with fewer than ``k`` finite entries keeps just those. Ties go to the
    lower index: the finite part of the set ``lax.top_k`` returns,
    without a sort.

    The k-th highest value is found by bisection on the scores' bits (32
    counting passes); entries equal to it are taken in index order until
    ``k`` are chosen (the index of the last one taken is found by a second
    bisection, over the bits of an index, which is skipped where no row
    holds more ties than it may take).

    ``live = (n_live, width)`` says that only the first ``n_live`` (traced)
    runs of ``width`` entries can hold a finite score: the passes then
    read a prefix of the rows that covers those runs, one of a few static
    lengths (:data:`LIVE_RUNS`), and the mask is the same."""
    if live is None:
        return _topk_mask(scores, k)
    n_live, width = live
    n = scores.shape[-1]
    runs = sorted({min(r, n // width) for r in (*LIVE_RUNS, n // width)})

    def prefix(r):
        def mask(s):
            head = _topk_mask(s[..., :r * width], k)
            return jnp.pad(head, [(0, 0)] * (s.ndim - 1)
                           + [(0, n - r * width)])
        return mask

    covering = sum((n_live > r).astype(jnp.int32) for r in runs[:-1])
    return jax.lax.switch(covering, [prefix(r) for r in runs], scores)


def _topk_mask(scores, k: int):
    n = scores.shape[-1]
    bits = jax.lax.bitcast_convert_type(scores + 0.0, jnp.int32)
    # a key that orders as the floats do, as unsigned integers
    key = jnp.where(bits < 0, ~bits, bits | jnp.int32(-2 ** 31))
    key = jax.lax.bitcast_convert_type(key, jnp.uint32)
    rows = scores.shape[:-1]

    def bisect(i, t):
        cand = t | (jnp.uint32(1) << (jnp.uint32(31) - i.astype(jnp.uint32)))
        enough = (key >= cand[..., None]).sum(-1, dtype=jnp.int32) >= k
        return jnp.where(enough, cand, t)

    kth = jax.lax.fori_loop(0, 32, bisect, jnp.zeros(rows, jnp.uint32))
    # fewer than k finite entries: all of them, and -inf ties with nothing
    neg_inf = jnp.uint32(0x007FFFFF)                  # the key of -inf
    kth = jnp.maximum(kth, neg_inf)[..., None]
    above = key > kth
    tied = (key == kth) & (kth > neg_inf)
    left = k - above.sum(-1, dtype=jnp.int32)             # ties to take
    index = jnp.arange(n, dtype=jnp.int32)
    index_bits = n.bit_length()           # candidates reach n itself

    def last_tie(i, t):
        """The largest ``t`` with at most ``left`` ties below index ``t``."""
        cand = t | (jnp.int32(1) << (index_bits - 1 - i))
        fits = (tied & (index < cand[..., None])).sum(
            -1, dtype=jnp.int32) <= left
        return jnp.where(fits, cand, t)

    def in_index_order():
        upto = jax.lax.fori_loop(0, index_bits, last_tie,
                                 jnp.zeros(rows, jnp.int32))
        return above | (tied & (index < upto[..., None]))

    # every row may take all its ties (the usual case: the k-th value once)
    all_fit = (tied.sum(-1, dtype=jnp.int32) <= left).all()
    return jax.lax.cond(all_fit, lambda: above | tied, in_index_order)


# Entries a run of :func:`mask_positions`: one lane tile.
RUN = 128


def mask_positions(mask, k: int):
    """Where the set entries of each row (last axis) of boolean ``mask``
    lie, in ascending order: ``chosen [..., k]`` int32 and ``keep [...,
    k]``, false behind the row's count (``chosen`` is 0 there). Of a row
    with more than ``k`` set entries, the first ``k``.

    No sort, scatter or gather, which cost the TPU more than the
    arithmetic: the row is cut into runs of :data:`RUN`; entry ``j`` of
    the output lies in the run whose span of the running totals holds
    ``j`` (a comparison against every run's), and its place in that run
    is how many of the run's counts are at most ``j`` less the run's
    start. The counts and the totals are products with a triangle of
    ones, a run's counts come to ``j`` through a one-hot product: both
    exact, a count of at most :data:`RUN` being a bfloat16 and the sums
    float32."""
    lead, n = mask.shape[:-1], mask.shape[-1]
    runs = jnp.pad(mask, [(0, 0)] * len(lead) + [(0, -n % RUN)]).reshape(
        *lead, -1, RUN).astype(jnp.bfloat16)

    def running(x):            # inclusive sums along the last axis
        size = x.shape[-1]
        upto = jnp.arange(size)[:, None] <= jnp.arange(size)
        return jnp.einsum("...c,cd->...d", x, upto.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)

    within = running(runs)                 # [..., runs, RUN]: set so far
    totals = within[..., -1]
    ends = running(totals.astype(jnp.bfloat16)).astype(jnp.int32)
    starts = ends - totals.astype(jnp.int32)
    j = jnp.arange(k, dtype=jnp.int32)[:, None]
    # [..., k, runs]: the run that holds output j (none behind the count)
    holds = (starts[..., None, :] <= j) & (j < ends[..., None, :])
    run = (holds * jnp.arange(runs.shape[-2], dtype=jnp.int32)).sum(-1)
    offset = j[:, 0] - (holds * starts[..., None, :]).sum(-1)
    counts = jnp.einsum("...kr,...rc->...kc", holds.astype(jnp.bfloat16),
                        within.astype(jnp.bfloat16),
                        preferred_element_type=jnp.float32)
    place = (counts <= offset[..., None].astype(jnp.float32)).sum(
        -1, dtype=jnp.int32)
    keep = j[:, 0] < ends[..., -1:]
    return jnp.where(keep, run * RUN + place, 0), keep


def by_page(pool, page_size: int):
    """A pool ``[rows, width]`` as ``[pages, page_size, width]``: indexed by
    page id it gathers whole pages (one contiguous tile each), not rows."""
    return pool.reshape(-1, page_size, pool.shape[-1])


def local_key_blocks(rows, key_block: int):
    """A call's own rows as its keys (no cache), cut into blocks: ``rows``
    are arrays ``[B, T, width]`` (cache rows and, with an indexer, its
    keys). Returns ``(fetch, kb, n_blocks)``: ``fetch(j)`` is block ``j``
    of each, ``[B, kb, width]``."""
    t = rows[0].shape[1]
    kb = min(int(key_block), t)
    pad = -t % kb
    padded = [jnp.pad(a, ((0, 0), (0, pad), (0, 0))) for a in rows]

    def fetch(j):
        return tuple(jax.lax.dynamic_slice_in_dim(a, j * kb, kb, axis=1)
                     for a in padded)

    return fetch, kb, (t + pad) // kb


def write_paged_rows(module: nn.Module, written, pages: PagedKV,
                     page_size: int, pool_pages: int):
    """Write a call's rows into ``module``'s pools in place through the
    page table: ``written`` is ``((leaf name, rows [B, T, width]), ...)``,
    one ``cache`` leaf ``[pool_pages x page_size, width]`` each, declared
    here and written by the shared writer (``parallel/ring_attention.py::
    paged_write``: a page at a time for a chunk, a row at a time for the
    narrow lanes); rows that do not exist go to the null page. Returns the
    pools as written."""
    pools = [module.variable("cache", name, jnp.zeros,
                             (pool_pages * page_size, rows.shape[-1]),
                             rows.dtype)
             for name, rows in written]
    pools_all = paged_write([pool.value for pool in pools],
                            [rows for _, rows in written], pages, page_size)
    if not module.is_initializing():
        for pool, rows_all in zip(pools, pools_all):
            pool.value = rows_all
    return pools_all


def paged_key_blocks(pools_all, table, page_size: int, key_block: int):
    """A slot's cached rows as key blocks, gathered by page: ``(fetch, kb,
    n_blocks)`` as :func:`local_key_blocks` gives them, block ``j`` holding
    the rows of table entries ``j x pages a block ..``."""
    b = table.shape[0]
    ppb = max(int(key_block) // page_size, 1)      # pages a key block
    n_blocks = -(-table.shape[1] // ppb)
    padded = jnp.pad(table, ((0, 0), (0, n_blocks * ppb - table.shape[1])))
    paged = [by_page(a, page_size) for a in pools_all]

    def fetch(j):
        tbl = jax.lax.dynamic_slice_in_dim(padded, j * ppb, ppb, 1)
        return tuple(a[tbl].reshape(b, ppb * page_size, -1) for a in paged)

    return fetch, ppb * page_size, n_blocks


def index_scores(q_i, w_i, k_i):
    """``I[b, t, s] = sum_h w_i[b, t, h] * relu(q_i[b, t, h] . k_i[b, s])``
    in float32: q_i [B, T, Hi, D], w_i [B, T, Hi], k_i [B, S, D]."""
    s = jnp.einsum("bthd,bsd->bths", q_i, k_i,
                   preferred_element_type=jnp.float32)
    return (jax.nn.relu(s) * w_i[..., None]).sum(2)


def per_head_block(q_nope, q_rope, latent, wkv_b, scale: float):
    """The per-head form on one block of keys: scores ``[B, H, T, S]``
    (float32, scaled, unmasked) and values ``[B, S, H, v]``. ``q_nope`` /
    ``q_rope`` [B, T, H, nope / rope] are the query, the rotated part
    apart; ``latent`` [B, S, >= kv_rank + rope] the cache row ``[c_kv |
    k_rope | padding]``; ``wkv_b`` [kv_rank, H, nope + v] expands ``c_kv``
    to every head's key and value. The rotated key, shared by the heads,
    is set behind each head's own: one product over ``nope + rope`` writes
    the scores once."""
    rank = wkv_b.shape[0]
    nope, rope = q_nope.shape[-1], q_rope.shape[-1]
    kv = jnp.einsum("bsc,chd->bshd", latent[..., :rank], wkv_b)
    k_rope = jnp.broadcast_to(latent[:, :, None, rank:rank + rope],
                              (*kv.shape[:3], rope))
    k = jnp.concatenate([kv[..., :nope], k_rope], axis=-1)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    s = jnp.einsum("bthd,bshd->bhts", q, k,
                   preferred_element_type=jnp.float32)
    return s * scale, kv[..., nope:]


def attend_absorbed(q_nope, q_rope, rows, keep, wkv_b, scale: float):
    """The absorbed form: each query over its own keys. ``rows`` [B, T, S,
    >= kv_rank + rope] are the cache rows query ``[b, t]`` reads, ``keep``
    [B, T, S] which of them count. The key expansion is folded into the
    query (``q' = q_nope W_k^T``, kv_rank wide) and the value expansion
    into the output (``(softmax . c_kv) W_v``), so a key costs ``2 kv_rank
    + rope`` multiply-adds a head and nothing is expanded per key. Returns
    ``[B, T, H, v]``."""
    rank = wkv_b.shape[0]
    nope, rope = q_nope.shape[-1], q_rope.shape[-1]
    q_abs = jnp.einsum("bthd,chd->bthc", q_nope, wkv_b[..., :nope])
    q = jnp.concatenate([q_abs, q_rope], axis=-1)
    s = jnp.einsum("bthc,btsc->bhts", q, rows[..., :rank + rope],
                   preferred_element_type=jnp.float32) * scale
    p = jax.nn.softmax(jnp.where(keep[:, None], s, -jnp.inf), axis=-1)
    o = jnp.einsum("bhts,btsc->bthc", p.astype(rows.dtype), rows[..., :rank])
    return jnp.einsum("bthc,chv->bthv", o, wkv_b[..., nope:])


class RMSNorm(nn.Module):
    eps: float = 1e-6
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        x32 = x.astype(jnp.float32)
        y = x32 * jax.lax.rsqrt((x32 * x32).mean(-1, keepdims=True)
                                + self.eps)
        return (y * scale.astype(jnp.float32)).astype(self.dtype)


class SparseLatentAttention(nn.Module):
    """MLA, with the indexer's selection where ``index_topk`` is set and
    dense over every earlier key where it is None; with a query latent
    where ``q_rank`` is set; with a head-wise output gate where
    ``head_gate`` is set (``models/ling_hybrid.py``'s); see the module
    docstring."""

    num_heads: int
    q_rank: int | None
    kv_rank: int
    nope_dim: int
    rope_dim: int
    v_dim: int
    index_heads: int | None
    index_dim: int | None
    index_topk: int | None
    rope: tuple          # (base, factor, original, beta_fast, beta_slow, mscale)
    rope_scaled: bool
    norm_eps: float = 1e-6
    key_block: int = 1024
    dtype: Any = jnp.float32
    kv_page_size: int | None = None
    kv_pages: int | None = None
    # a head-wise output gate: each head's output times sigmoid(x . w_h)
    head_gate: bool = False

    def _frequencies(self):
        base, factor, original, fast, slow, _ = self.rope
        if self.rope_scaled:
            return yarn_frequencies(self.rope_dim, base, factor, original,
                                    fast, slow)
        i = np.arange(self.rope_dim // 2, dtype=np.float64)
        return (base ** (-2.0 * i / self.rope_dim)).astype(np.float32)

    @nn.compact
    def __call__(self, x, positions, pages: PagedKV | None = None):
        b, t, d = x.shape
        h, dt = self.num_heads, self.dtype
        init = nn.initializers.normal(0.02)
        qk_dim = self.nope_dim + self.rope_dim
        sparse = self.index_topk is not None
        if sparse and self.q_rank is None:
            raise ValueError(
                "this module's indexer makes its query from the query "
                "latent, so index_topk needs q_rank; an indexer without a "
                "query latent is models/keye_vl2.py's")
        if self.q_rank is None:
            wq = self.param("wq", init, (d, h, qk_dim)).astype(dt)
        else:
            wq_a = self.param("wq_a", init, (d, self.q_rank)).astype(dt)
            wq_b = self.param("wq_b", init,
                              (self.q_rank, h, qk_dim)).astype(dt)
        wkv_a = self.param("wkv_a", init,
                           (d, self.kv_rank + self.rope_dim)).astype(dt)
        wkv_b = self.param("wkv_b", init, (self.kv_rank, h, self.nope_dim
                                           + self.v_dim)).astype(dt)
        wo = self.param("wo", init, (h, self.v_dim, d)).astype(dt)
        if sparse:
            wi_q = self.param("index_wq", init, (
                self.q_rank, self.index_heads, self.index_dim)).astype(dt)
            wi_k = self.param("index_wk", init,
                              (d, self.index_dim)).astype(dt)
            wi_w = self.param("index_weights", init, (d, self.index_heads))
        _, factor, _, _, _, mscale = self.rope
        scale = (yarn_softmax_scale(qk_dim, factor, mscale)
                 if self.rope_scaled else qk_dim ** -0.5)

        with jax.named_scope("mla.project"):
            angles = positions.astype(jnp.float32)[..., None] \
                * jnp.asarray(self._frequencies())
            cos, sin = jnp.cos(angles), jnp.sin(angles)      # [B, T, rope/2]
            if self.q_rank is None:
                q = jnp.einsum("btd,dhe->bthe", x, wq)
            else:
                c_q = RMSNorm(self.norm_eps, dt, name="q_norm")(
                    jnp.dot(x, wq_a))
                q = jnp.einsum("btr,rhd->bthd", c_q, wq_b)
            q_nope = q[..., :self.nope_dim]
            q_rope = rotate_interleaved(
                q[..., self.nope_dim:], cos[:, :, None],
                sin[:, :, None]).astype(dt)
            kv = jnp.dot(x, wkv_a)
            c_kv = RMSNorm(self.norm_eps, dt, name="kv_norm")(
                kv[..., :self.kv_rank])
            k_rope = rotate_interleaved(kv[..., self.kv_rank:], cos,
                                        sin).astype(dt)
            # the cache row, a whole number of lane tiles wide
            width = self.kv_rank + self.rope_dim
            latent = jnp.concatenate(
                [c_kv, k_rope, jnp.zeros((b, t, -width % LANES), dt)], axis=-1)
            index = None       # (q_i, w_i, k_i): the indexer's, if any
            if sparse:
                q_i = jnp.einsum("btr,rhd->bthd", c_q, wi_q)
                q_i = jnp.concatenate([
                    rotate_half_split(q_i[..., :self.rope_dim],
                                      cos[:, :, None],
                                      sin[:, :, None]).astype(dt),
                    q_i[..., self.rope_dim:]], axis=-1)
                k_i = nn.LayerNorm(epsilon=self.norm_eps, dtype=dt,
                                   name="index_k_norm")(jnp.dot(x, wi_k))
                k_i = jnp.concatenate([
                    rotate_half_split(k_i[..., :self.rope_dim], cos,
                                      sin).astype(dt),
                    k_i[..., self.rope_dim:]], axis=-1)
                w_i = jnp.dot(x.astype(jnp.float32),
                              wi_w.astype(jnp.float32), precision=HIGHEST) \
                    * (self.index_heads ** -0.5 * self.index_dim ** -0.5)
                index = (q_i, w_i, k_i)

        if pages is None:
            valid = jnp.ones((b, t), bool)
            keys = (latent,) if index is None else (latent, index[2])
            out = self._masked_blocks(
                q_nope, q_rope, index, positions, valid, wkv_b, scale,
                *local_key_blocks(keys, self.key_block))
        else:
            out = self._paged(q_nope, q_rope, index, latent, wkv_b, scale,
                              pages)
        if self.head_gate:
            w_gate = self.param("gate", init, (d, h))
            out = out * jax.nn.sigmoid(jnp.dot(
                x, w_gate.astype(dt), preferred_element_type=jnp.float32)
            )[..., None]
        return jnp.einsum("bthv,hvd->btd", out.astype(dt), wo)

    # -- where the keys come from --------------------------------------------
    def _paged(self, q_nope, q_rope, index, latent, wkv_b, scale,
               pages: PagedKV):
        """Write this call's rows into the layer's pools in place (the
        latent rows, and the indexer's keys where there is one), then
        attend through the page table in the lane the call's width
        selects."""
        t = latent.shape[1]
        if self.kv_page_size is None or self.kv_pages is None:
            raise ValueError("pages= passed but the model was not cloned "
                             "with kv_page_size / kv_pages")
        ps = int(self.kv_page_size)
        written = [("latent_pages", latent)]
        if index is not None:
            written.append(("index_pages", index[2]))
        pools_all = write_paged_rows(self, written, pages, ps,
                                     int(self.kv_pages))
        table, positions, valid = pages

        lane = paged_lane(t, index is not None)
        if lane == "masked-blocks":
            out = self._masked_blocks(
                q_nope, q_rope, index, positions, valid, wkv_b, scale,
                *paged_key_blocks(pools_all, table, ps, self.key_block))
        elif lane == "sparse-gather":
            out = self._sparse_gather(q_nope, q_rope, *index[:2], positions,
                                      table, *pools_all, wkv_b, scale)
        elif dense_kernel_fits(t, self.num_heads, self.kv_rank,
                               self.rope_dim, ps, self.dtype):
            out = self._dense_kernel(q_nope, q_rope, pages, pools_all[0],
                                     wkv_b, scale)
        else:
            out = self._dense_gather(q_nope, q_rope, positions, table,
                                     pools_all[0], wkv_b, scale)
        overflow = positions >= table.shape[1] * ps
        return jnp.where(overflow[:, :, None, None], jnp.nan, out)

    # -- the narrow window's lanes -------------------------------------------
    def _sparse_gather(self, q_nope, q_rope, q_i, w_i, positions, table,
                       lat_all, idx_all, wkv_b, scale):
        b = table.shape[0]
        ps = int(self.kv_page_size)
        l_all = table.shape[1] * ps
        with jax.named_scope("dsa.index"):
            keys = by_page(idx_all, ps)[table].reshape(b, l_all, -1)
            s = index_scores(q_i, w_i, keys)                   # [B, T, L]
            s = jnp.where(jnp.arange(l_all) <= positions[..., None],
                          s + 0.0, -jnp.inf)
        with jax.named_scope("dsa.select"):
            k = min(self.index_topk, l_all)
            chosen, keep = mask_positions(exact_topk_mask(s, k), k)
            chosen_rows = jnp.take_along_axis(
                table[:, None, :], chosen // ps, axis=2) * ps + chosen % ps
        with jax.named_scope("mla.attend"):
            return attend_absorbed(q_nope, q_rope, lat_all[chosen_rows],
                                   keep, wkv_b, scale)

    def _dense_gather(self, q_nope, q_rope, positions, table, lat_all,
                      wkv_b, scale):
        """Every query over every earlier row of its slot, the slot's whole
        page budget gathered: the absorbed form in XLA."""
        b, t = positions.shape
        ps = int(self.kv_page_size)
        l_all = table.shape[1] * ps
        with jax.named_scope("mla.attend"):
            rows = by_page(lat_all, ps)[table].reshape(b, 1, l_all, -1)
            keep = jnp.arange(l_all) <= positions[..., None]
            return attend_absorbed(
                q_nope, q_rope,
                jnp.broadcast_to(rows, (b, t, *rows.shape[2:])), keep,
                wkv_b, scale)

    def _dense_kernel(self, q_nope, q_rope, pages: PagedKV, lat_all, wkv_b,
                      scale):
        """The absorbed form with the live pages read where they lie: the
        key expansion folded into the query (as wide as a pool row), the
        kernel's weighted sum of latent rows expanded to the heads'
        values."""
        b, t, h = q_nope.shape[:3]
        table, positions, valid = pages
        with jax.named_scope("mla.attend"):
            q_abs = jnp.einsum("bthd,chd->bthc", q_nope,
                               wkv_b[..., :self.nope_dim])
            pad = lat_all.shape[-1] - self.kv_rank - self.rope_dim
            q = jnp.concatenate(
                [q_abs, q_rope, jnp.zeros((b, t, h, pad), q_abs.dtype)],
                axis=-1)
            o = paged_attention.paged_latent_attention(
                q, lat_all, table, positions, valid,
                value_lanes=self.kv_rank, page_size=int(self.kv_page_size),
                scale=float(scale))
            return jnp.einsum("bthc,chv->bthv", o,
                              wkv_b[..., self.nope_dim:])

    # -- the chunk's lane ----------------------------------------------------
    def _masked_blocks(self, q_nope, q_rope, index, positions, valid,
                       wkv_b, scale, fetch, kb: int, n_blocks: int):
        b, t, h = q_nope.shape[:3]
        # key blocks that some existing row's position reaches
        n_live = jnp.minimum(
            jnp.max(jnp.where(valid, positions, 0)) // kb + 1, n_blocks)
        kpos = jnp.arange(kb)

        # keep_block(j): which keys of block j each query attends, [B, T,
        # kb]; with ``row`` that one sequence's [T, kb]
        if index is None:
            def keep_block(j, row=None):   # causal: every earlier key
                at = positions if row is None else positions[row]
                return j * kb + kpos <= at[..., None]
        else:
            q_i, w_i, _ = index

            def index_block(j, scores):
                s = index_scores(q_i, w_i, fetch(j)[1])
                s = jnp.where(j * kb + kpos <= positions[..., None], s + 0.0,
                              -jnp.inf)
                return jax.lax.dynamic_update_slice_in_dim(scores, s,
                                                           j * kb, 2)

            with jax.named_scope("dsa.index"):
                scores = jax.lax.fori_loop(
                    0, n_live, index_block,
                    jnp.full((b, t, n_blocks * kb), -jnp.inf, jnp.float32))
            with jax.named_scope("dsa.select"):
                mask = exact_topk_mask(scores, self.index_topk,
                                       (n_live, kb))

            def keep_block(j, row=None):
                if row is None:
                    return jax.lax.dynamic_slice_in_dim(mask, j * kb, kb, 2)
                return jax.lax.dynamic_slice_in_dim(mask[row], j * kb, kb, 1)

        if self.chunk_kernel(b, t, kb):
            with jax.named_scope("mla.attend"):
                return self._attend_blocks_kernel(
                    q_nope, q_rope, keep_block, wkv_b, scale, fetch, kb,
                    n_live)

        def attend_block(j, carry):
            o, m, l = carry
            s, v = per_head_block(q_nope, q_rope, fetch(j)[0], wkv_b, scale)
            s = jnp.where(keep_block(j)[:, None], s, -jnp.inf)  # [B, H, T, S]
            m_new = jnp.maximum(m, s.max(-1))
            m_safe = jnp.where(m_new == -jnp.inf, 0.0, m_new)
            p = jnp.exp(s - m_safe[..., None])
            fade = jnp.exp(m - m_safe)
            l = l * fade + p.sum(-1)
            o = o * fade[..., None] + jnp.einsum(
                "bhts,bshv->bhtv", p.astype(v.dtype), v,
                preferred_element_type=jnp.float32)
            return o, m_new, l

        with jax.named_scope("mla.attend"):
            o, _, l = jax.lax.fori_loop(
                0, n_live, attend_block,
                (jnp.zeros((b, h, t, self.v_dim), jnp.float32),
                 jnp.full((b, h, t), -jnp.inf, jnp.float32),
                 jnp.zeros((b, h, t), jnp.float32)))
            return jnp.swapaxes(o / l[..., None], 1, 2)      # [B, T, H, v]

    def chunk_kernel(self, b: int, t: int, kb: int) -> bool:
        """Whether a masked-blocks call of these shapes attends its blocks
        in the kernel (``ops/masked_attention.py``) or in XLA."""
        return masked_attention.kernel_fits(
            b, t, kb, self.nope_dim, self.rope_dim, self.v_dim, self.dtype)

    def _attend_blocks_kernel(self, q_nope, q_rope, keep_block, wkv_b,
                              scale, fetch, kb: int, n_live):
        """The per-head form, a key block a kernel call: the same products
        and the same online softmax as ``attend_block``, the scores in
        VMEM. One sequence (``kernel_fits``)."""
        t, h = q_nope.shape[1:3]
        rank = wkv_b.shape[0]
        q_nope, q_rope = (jnp.swapaxes(q[0], 0, 1) for q in (q_nope, q_rope))
        # every head's key and value expansion side by side: [rank, H x dim]
        w_k = wkv_b[..., :self.nope_dim].reshape(rank, -1)
        w_v = wkv_b[..., self.nope_dim:].reshape(rank, -1)

        def attend_block(j, state):
            latent = fetch(j)[0][0]                        # [kb, width]
            c_kv = latent[:, :rank]
            keep = keep_block(j, row=0)
            return tuple(masked_attention.masked_attention_block(
                q_nope, q_rope, jnp.dot(c_kv, w_k),
                latent[:, rank:rank + self.rope_dim], jnp.dot(c_kv, w_v),
                keep.astype(jnp.int8), state, scale=scale))

        state = jax.lax.fori_loop(
            0, n_live, attend_block,
            masked_attention.init_state(t, h, self.v_dim))
        return masked_attention.finish(state, h)[None]     # [1, T, H, v]


class DeepseekV32Block(nn.Module):
    """``x + Attn(RMS(x))``, then ``x + FFN(RMS(x))``: a dense gated FFN in
    the leading layers, the held experts' share plus the shared expert in
    the rest."""

    attn: dict
    dense_dim: int | None        # set: a leading dense layer
    moe: dict | None
    norm_eps: float
    dtype: Any

    @nn.compact
    def __call__(self, x, positions, pages=None):
        y = RMSNorm(self.norm_eps, self.dtype, name="attn_norm")(x)
        x = x + SparseLatentAttention(
            **self.attn, norm_eps=self.norm_eps, dtype=self.dtype,
            name="attn")(y, positions, pages)
        y = RMSNorm(self.norm_eps, self.dtype, name="ffn_norm")(x)
        if self.dense_dim is not None:
            return x + GatedMlp(self.dense_dim, dtype=self.dtype,
                                name="ffn")(y)
        valid = None if pages is None else pages.valid
        return x + HeldExpertsMlp(**self.moe, dtype=self.dtype,
                                  name="ffn")(y, valid)


class DeepseekV32LM(nn.Module):
    """The model as the serving engine drives it: ``apply(tokens, positions,
    decode=True, pages=PagedKV)`` with a mutable ``cache`` collection (a
    latent pool a layer, and an index pool beside it where the model has
    the indexer), ``clone(cache_len, kv_page_size, kv_pages, kv_dtype)``,
    ``max_len`` (the position limit), and what the engine asks a model
    about itself: :meth:`paged_lane`, :meth:`attended_rows`,
    ``step_counters``. ``decode=False`` is the plain forward over the call's
    own rows (no cache), through the masked-blocks lane. ``q_rank`` and the
    three ``index_*`` sizes are None in a model without the query latent or
    without the indexer."""

    vocab_size: int
    num_layers: int
    first_dense: int
    hidden_dim: int
    dense_dim: int
    expert_dim: int
    num_heads: int
    q_rank: int | None
    kv_rank: int
    nope_dim: int
    rope_dim: int
    v_dim: int
    index_heads: int | None
    index_dim: int | None
    index_topk: int | None
    num_experts: int             # the router's width
    held: tuple                  # (first, count) of the experts held here
    experts_per_token: int
    n_group: int
    topk_group: int
    routed_scale: float
    shared_experts: int = 1
    rope: tuple = (10000.0, 40.0, 4096, 32.0, 1.0, 1.0)
    max_len: int = 163840
    norm_eps: float = 1e-6
    key_block: int = 1024
    dtype: Any = jnp.float32
    logits_dtype: Any = jnp.float32
    cache_len: int | None = None
    kv_page_size: int | None = None
    kv_pages: int | None = None
    kv_dtype: str | None = None

    # HeldExpertsMlp sows them
    step_counters = ("expert_rows", "expert_rows_max", "experts_hit")
    # The engine keeps the per-leaf tree: a launch is hidden under a
    # 15-20 ms device step, so fewer buffers would gain nothing.
    stacked_blocks = None
    # Every cache leaf is a paged pool: no per-slot state.
    slot_state = ()

    def paged_lane(self, t_in: int, page_size: int | None = None,
                   kv_dtype: str | None = None) -> str:
        """The attention formulation a paged call ``t_in`` rows wide takes
        (the width alone decides; pools are in the compute dtype)."""
        del kv_dtype
        return lane_name(self, t_in, self.index_topk is not None, page_size)

    expert_lane = model_expert_lane

    def attended_rows(self, live: int) -> int:
        """Of ``live`` cached rows, how many one query attends."""
        if self.index_topk is None:
            return int(live)
        return min(int(live), self.index_topk)

    def index_rows_scored(self, live: int, budget: int) -> int:
        """Of a decoding slot that holds ``live`` rows of a page budget of
        ``budget``, the rows whose index key its lane reads and scores:
        ``sparse-gather`` goes through the slot's whole table; none without
        the indexer."""
        del live
        return 0 if self.index_topk is None else int(budget)

    @nn.compact
    def __call__(self, tokens, positions=None, train: bool = False,
                 decode: bool = False, pages=None):
        del train
        if self.kv_dtype is not None:
            raise ValueError("the latent and index pools are kept in the "
                             f"compute dtype; kv_dtype={self.kv_dtype!r}")
        if decode and pages is None:
            raise ValueError("decode=True runs through the paged pools: "
                             "pass pages= (serving.Engine does)")
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(tokens.shape[-1]),
                                         tokens.shape)
        embedding = self.param("tok_embed", nn.initializers.normal(0.02),
                               (self.vocab_size, self.hidden_dim))
        x = jnp.take(embedding.astype(self.dtype), tokens, axis=0)
        attn = dict(
            num_heads=self.num_heads, q_rank=self.q_rank,
            kv_rank=self.kv_rank, nope_dim=self.nope_dim,
            rope_dim=self.rope_dim, v_dim=self.v_dim,
            index_heads=self.index_heads, index_dim=self.index_dim,
            index_topk=self.index_topk, rope=tuple(self.rope),
            rope_scaled=self.max_len > self.rope[2],
            key_block=self.key_block, kv_page_size=self.kv_page_size,
            kv_pages=self.kv_pages)
        moe = dict(
            num_experts=self.num_experts, held=tuple(self.held),
            hidden_dim=self.expert_dim, top_k=self.experts_per_token,
            n_group=self.n_group, topk_group=self.topk_group,
            routed_scale=self.routed_scale,
            shared_experts=self.shared_experts)
        for i in range(self.num_layers):
            dense = i < self.first_dense
            x = DeepseekV32Block(
                attn=attn, dense_dim=self.dense_dim if dense else None,
                moe=None if dense else moe, norm_eps=self.norm_eps,
                dtype=self.dtype, name=f"layer{i}")(x, positions, pages)
        x = RMSNorm(self.norm_eps, self.dtype, name="norm_f")(x)
        head = self.param("lm_head", nn.initializers.normal(0.02),
                          (self.hidden_dim, self.vocab_size))
        return jnp.dot(x.astype(self.logits_dtype),
                       head.astype(self.logits_dtype))


def make_deepseek_v32(*, num_classes: int, dtype: Any = jnp.float32,
                      axis_name: str | None = None, **kwargs) -> DeepseekV32LM:
    """Registry factory; ``num_classes`` is the vocabulary held here."""
    del axis_name
    return DeepseekV32LM(vocab_size=num_classes, dtype=dtype, **kwargs)


def make_sarvam_mla(*, num_classes: int, dtype: Any = jnp.float32,
                    axis_name: str | None = None, **kwargs) -> DeepseekV32LM:
    """Registry factory of the ``sarvam_mla`` family: the same model with
    no query latent, no indexer (dense latent attention over every earlier
    key) and a router of one group."""
    del axis_name
    return DeepseekV32LM(
        vocab_size=num_classes, dtype=dtype, q_rank=None, index_heads=None,
        index_dim=None, index_topk=None, n_group=1, topk_group=1, **kwargs)
