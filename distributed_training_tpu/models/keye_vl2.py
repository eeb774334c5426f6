"""Decoder LM of the Keye-VL-2.0 family's language model for the serving
engine: grouped-query attention whose cache rows are per-head keys and
values, a learned selection of the keys each query attends, softmax routing
over held experts.

What differs from :class:`~distributed_training_tpu.models.deepseek_v32.
DeepseekV32LM`, whose selection this model shares (:func:`~distributed_
training_tpu.models.deepseek_v32.index_scores`, ``exact_topk_mask``,
``mask_positions``, ``by_page`` are used from there, not copied):

- **Grouped-query attention.** ``num_heads`` query heads over
  ``num_kv_heads`` key/value heads of ``head_dim`` (32 over 4 of 128: query
  head ``h`` reads key head ``h // 8``), an RMSNorm with a gain over each
  head of q and of k, rotary over the whole head in half-split pairs ``(j,
  j + head_dim / 2)``. The cached key is the normed, rotated key: what a
  later query multiplies as it lies.
- **Three position streams** (``mrope_section``): rotary frequency ``j``
  takes its position from the temporal stream for ``j < 16``, from the
  height stream for the next 24 and from the width stream for the last 24.
  ``positions`` is ``[3, B, T]``, or ``[B, T]`` where the three are equal
  (text: what the serving engine passes); a cache row's place and the
  causal order are the sequence's own (``pages.positions``, or the call's
  row order in the plain forward), never a stream's.
- **The indexer's query comes from the layer's normed input**: there is no
  query latent. ``index_heads`` heads of ``index_dim`` (16 of 64) against
  one cached index key a token (LayerNorm, rotary over the whole index head
  by the temporal stream).
- **Two pools a layer under the slot's one page table**: ``kv_pages``
  ``[rows, 2 x num_kv_heads x head_dim]``, a token's keys and values side by
  side ``[K | V]`` (the decode lane gathers 2048 selected rows a slot: one
  gather of 2 KB rows took 0.60-0.69 ms a layer on the chip where two of 1
  KB took 0.81-0.96), and ``index_pages`` ``[rows, 128]`` — the 64-wide
  index key held a whole lane tile wide, zeros behind: rows of a whole
  number of 128-lane tiles are written in place (PR 25's and PR 27's law),
  and a page of 16 such rows is gathered in 0.22 ms a layer where a page of
  64-wide rows took 0.36.
- **Experts**: :class:`~distributed_training_tpu.models.moe.HeldExpertsMlp`
  routed by softmax (top-k of the probabilities, renormalised; no bias, no
  groups, no scale) with no shared expert; every layer is an expert layer.

Two lanes, chosen from the call's width alone (:meth:`KeyeVL2LM.
paged_lane`), named as DeepSeek's: ``sparse-gather`` for a window of at most
:data:`~distributed_training_tpu.models.deepseek_v32.NARROW_WINDOW` rows
(index scores over the slot's table, the exact top ``index_topk`` as a mask
and its positions, the selected K and V rows gathered, grouped attention of
a key head's 8 query heads over them) and ``masked-blocks`` for the chunk
(index scores and the mask over live key blocks, then each block attended
with the keys and values as they lie in the pools' pages — a key head serves
its query heads without being expanded to them; in the grouped form of the
kernel ``ops/masked_attention.py`` where the shapes are ones it serves:
``masked-blocks-kernel``). Both select the same set: the top ``index_topk``
by index score, ties to the lower position.
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from distributed_training_tpu.models.deepseek_v32 import (
    HIGHEST, KERNEL_LANE, LANES, RMSNorm, by_page, exact_topk_mask,
    index_scores, local_key_blocks, mask_positions, paged_key_blocks,
    paged_lane, rotate_half_split, write_paged_rows)
from distributed_training_tpu.models.moe import HeldExpertsMlp
from distributed_training_tpu.ops import masked_attention
from distributed_training_tpu.parallel.ring_attention import PagedKV


def rotary_angles(positions, dim: int, theta: float, sections=None):
    """Angles ``[B, T, dim / 2]`` (float32) of a rotary head ``dim`` wide:
    frequency ``j`` is ``theta^(-2j / dim)``. ``positions`` is ``[B, T]``, or
    ``[3, B, T]`` with ``sections`` (three counts that sum to ``dim / 2``):
    the first ``sections[0]`` frequencies turn by stream 0, the next by
    stream 1, the rest by stream 2. Without ``sections`` a 3-stream
    ``positions`` turns every frequency by stream 0 (the temporal one)."""
    freq = jnp.asarray((theta ** (-2.0 * np.arange(dim // 2, dtype=np.float64)
                                  / dim)).astype(np.float32))
    p = positions.astype(jnp.float32)
    if p.ndim == 2:
        return p[..., None] * freq
    if sections is None:
        return p[0][..., None] * freq
    if sum(sections) != dim // 2:
        raise ValueError(f"mrope sections {tuple(sections)} do not sum to "
                         f"the head's {dim // 2} frequencies")
    stream = np.repeat(np.arange(3), sections)
    return jnp.moveaxis(p[stream], 0, -1) * freq


def attend_grouped(q, rows, keep, scale: float):
    """Each query over its own gathered rows, a key head serving its group
    of query heads: ``q`` [B, T, KVH, G, hd], ``rows`` [B, T, S, 2 x KVH x
    hd] the cache rows ``[K | V]`` query ``[b, t]`` reads, ``keep`` [B, T,
    S] which of them count. Scores and softmax in float32. Returns [B, T,
    KVH, G, hd]."""
    b, t, kvh, _, hd = q.shape
    k, v = (a.reshape(b, t, -1, kvh, hd) for a in jnp.split(rows, 2, -1))
    s = jnp.einsum("btkgd,btskd->btkgs", q, k,
                   preferred_element_type=jnp.float32) * scale
    p = jax.nn.softmax(jnp.where(keep[:, :, None, None], s, -jnp.inf),
                       axis=-1)
    return jnp.einsum("btkgs,btskd->btkgd", p.astype(v.dtype), v)


class SparseGroupedAttention(nn.Module):
    """Grouped-query attention over the keys the indexer selects; see the
    module docstring."""

    num_heads: int
    num_kv_heads: int
    head_dim: int
    index_heads: int
    index_dim: int
    index_topk: int
    rope_theta: float
    mrope_section: tuple
    norm_eps: float = 1e-6
    key_block: int = 1024
    dtype: Any = jnp.float32
    kv_page_size: int | None = None
    kv_pages: int | None = None

    @nn.compact
    def __call__(self, x, positions, pages: PagedKV | None = None):
        b, t, d = x.shape
        h, kvh, hd, dt = (self.num_heads, self.num_kv_heads, self.head_dim,
                          self.dtype)
        if h % kvh:
            raise ValueError(f"{kvh} key heads do not divide {h} query heads")
        init = nn.initializers.normal(0.02)
        wq = self.param("wq", init, (d, h, hd)).astype(dt)
        wk = self.param("wk", init, (d, kvh, hd)).astype(dt)
        wv = self.param("wv", init, (d, kvh, hd)).astype(dt)
        wo = self.param("wo", init, (h, hd, d)).astype(dt)
        wi_q = self.param("index_wq", init,
                          (d, self.index_heads, self.index_dim)).astype(dt)
        wi_k = self.param("index_wk", init, (d, self.index_dim)).astype(dt)
        wi_w = self.param("index_weights", init, (d, self.index_heads))

        with jax.named_scope("gqa.project"):
            angles = rotary_angles(positions, hd, self.rope_theta,
                                   tuple(self.mrope_section))
            cos, sin = jnp.cos(angles)[:, :, None], jnp.sin(angles)[:, :, None]
            q = RMSNorm(self.norm_eps, dt, name="q_norm")(
                jnp.einsum("btd,dhe->bthe", x, wq))
            q = rotate_half_split(q, cos, sin).astype(dt)
            k = RMSNorm(self.norm_eps, dt, name="k_norm")(
                jnp.einsum("btd,dhe->bthe", x, wk))
            k = rotate_half_split(k, cos, sin).astype(dt)
            v = jnp.einsum("btd,dhe->bthe", x, wv)
            # the cache row: a token's keys and values side by side
            kv = jnp.concatenate([k, v], axis=2).reshape(b, t, -1)
            q = q.reshape(b, t, kvh, h // kvh, hd)
            # the indexer: its query from x (no query latent), one key a token
            angles = rotary_angles(positions, self.index_dim, self.rope_theta)
            cos, sin = jnp.cos(angles), jnp.sin(angles)
            q_i = jnp.einsum("btd,dhe->bthe", x, wi_q)
            q_i = rotate_half_split(q_i, cos[:, :, None],
                                    sin[:, :, None]).astype(dt)
            k_i = nn.LayerNorm(epsilon=self.norm_eps, dtype=dt,
                               name="index_k_norm")(jnp.dot(x, wi_k))
            k_i = rotate_half_split(k_i, cos, sin).astype(dt)
            w_i = jnp.dot(x.astype(jnp.float32), wi_w.astype(jnp.float32),
                          precision=HIGHEST) \
                * (self.index_heads ** -0.5 * self.index_dim ** -0.5)
            # the index key's cache row, a whole lane tile wide
            k_i = jnp.concatenate(
                [k_i, jnp.zeros((b, t, -self.index_dim % LANES), dt)], axis=-1)
        index = (q_i, w_i)
        scale = hd ** -0.5

        if pages is None:
            # the call's own rows are the keys, in the call's order
            order = jnp.broadcast_to(jnp.arange(t), (b, t))
            out = self._masked_blocks(
                q, index, order, jnp.ones((b, t), bool), scale,
                *local_key_blocks((kv, k_i), self.key_block))
        else:
            out = self._paged(q, index, kv, k_i, scale, pages)
        return jnp.einsum("bthv,hvd->btd",
                          out.reshape(b, t, h, hd).astype(dt), wo)

    # -- where the keys come from --------------------------------------------
    def _paged(self, q, index, kv, k_i, scale, pages: PagedKV):
        """Write this call's rows into the layer's two pools in place,
        then attend through the page table in the lane the call's width
        selects."""
        if self.kv_page_size is None or self.kv_pages is None:
            raise ValueError("pages= passed but the model was not cloned "
                             "with kv_page_size / kv_pages")
        ps = int(self.kv_page_size)
        pools_all = write_paged_rows(
            self, (("kv_pages", kv), ("index_pages", k_i)), pages, ps,
            int(self.kv_pages))
        table, positions, valid = pages

        if paged_lane(kv.shape[1], True) == "masked-blocks":
            out = self._masked_blocks(
                q, index, positions, valid, scale,
                *paged_key_blocks(pools_all, table, ps, self.key_block))
        else:
            out = self._sparse_gather(q, index, positions, table, *pools_all,
                                      scale)
        overflow = positions >= table.shape[1] * ps
        return jnp.where(overflow[:, :, None, None, None], jnp.nan, out)

    # -- the narrow window's lane --------------------------------------------
    def _sparse_gather(self, q, index, positions, table, kv_all, idx_all,
                       scale):
        b = table.shape[0]
        ps = int(self.kv_page_size)
        l_all = table.shape[1] * ps
        with jax.named_scope("dsa.index"):
            keys = by_page(idx_all, ps)[table].reshape(b, l_all, -1)
            s = index_scores(*index, keys[..., :self.index_dim])  # [B, T, L]
            s = jnp.where(jnp.arange(l_all) <= positions[..., None],
                          s + 0.0, -jnp.inf)
        with jax.named_scope("dsa.select"):
            n = min(self.index_topk, l_all)
            chosen, keep = mask_positions(exact_topk_mask(s, n), n)
            chosen_rows = jnp.take_along_axis(
                table[:, None, :], chosen // ps, axis=2) * ps + chosen % ps
        with jax.named_scope("gqa.attend"):
            return attend_grouped(q, kv_all[chosen_rows], keep, scale)

    # -- the chunk's lane ----------------------------------------------------
    def _masked_blocks(self, q, index, positions, valid, scale, fetch,
                       kb: int, n_blocks: int):
        """``positions`` [B, T] are the queries' places in their sequences
        (block ``j`` holds the keys at ``j * kb ..``); ``fetch(j)`` gives
        that block's ``[K | V]`` and index-key rows."""
        b, t, kvh, g, hd = q.shape
        # key blocks that some existing row's position reaches
        n_live = jnp.minimum(
            jnp.max(jnp.where(valid, positions, 0)) // kb + 1, n_blocks)
        kpos = jnp.arange(kb)

        def index_block(j, scores):
            s = index_scores(*index, fetch(j)[1][..., :self.index_dim])
            s = jnp.where(j * kb + kpos <= positions[..., None], s + 0.0,
                          -jnp.inf)
            return jax.lax.dynamic_update_slice_in_dim(scores, s, j * kb, 2)

        with jax.named_scope("dsa.index"):
            scores = jax.lax.fori_loop(
                0, n_live, index_block,
                jnp.full((b, t, n_blocks * kb), -jnp.inf, jnp.float32))
        with jax.named_scope("dsa.select"):
            mask = exact_topk_mask(scores, self.index_topk, (n_live, kb))

        if self.chunk_kernel(b, t, kb):
            with jax.named_scope("gqa.attend"):
                return self._attend_blocks_kernel(q, mask, scale, fetch, kb,
                                                  n_live)

        def attend_block(j, carry):
            o, m, l = carry
            k_blk, v_blk = (a.reshape(b, kb, kvh, hd)
                            for a in jnp.split(fetch(j)[0], 2, -1))
            keep = jax.lax.dynamic_slice_in_dim(mask, j * kb, kb, 2)
            s = jnp.einsum("btkgd,bskd->bkgts", q, k_blk,
                           preferred_element_type=jnp.float32) * scale
            s = jnp.where(keep[:, None, None], s, -jnp.inf)
            m_new = jnp.maximum(m, s.max(-1))
            m_safe = jnp.where(m_new == -jnp.inf, 0.0, m_new)
            p = jnp.exp(s - m_safe[..., None])
            fade = jnp.exp(m - m_safe)
            l = l * fade + p.sum(-1)
            o = o * fade[..., None] + jnp.einsum(
                "bkgts,bskd->bkgtd", p.astype(v_blk.dtype), v_blk,
                preferred_element_type=jnp.float32)
            return o, m_new, l

        with jax.named_scope("gqa.attend"):
            o, _, l = jax.lax.fori_loop(
                0, n_live, attend_block,
                (jnp.zeros((b, kvh, g, t, hd), jnp.float32),
                 jnp.full((b, kvh, g, t), -jnp.inf, jnp.float32),
                 jnp.zeros((b, kvh, g, t), jnp.float32)))
            return jnp.moveaxis(o / l[..., None], 3, 1)    # [B, T, KVH, G, hd]

    def chunk_kernel(self, b: int, t: int, kb: int) -> bool:
        """Whether a masked-blocks call of these shapes attends its blocks
        in the kernel (``ops/masked_attention.py``, grouped form) or in
        XLA."""
        return masked_attention.kernel_fits(b, t, kb, self.head_dim, 0,
                                            self.head_dim, self.dtype)

    def _attend_blocks_kernel(self, q, mask, scale, fetch, kb: int, n_live):
        """A key block a kernel call: the keys and values as the pools hold
        them, ``[kb, KVH x hd]``, a key head's column block read by its
        group of query heads. One sequence (``kernel_fits``)."""
        _, t, kvh, g, hd = q.shape
        q = jnp.moveaxis(q[0].reshape(t, kvh * g, hd), 0, 1)    # [H, T, hd]

        def attend_block(j, state):
            k_blk, v_blk = jnp.split(fetch(j)[0][0], 2, -1)
            keep = jax.lax.dynamic_slice_in_dim(mask[0], j * kb, kb, 1)
            return tuple(masked_attention.masked_attention_block(
                q, None, k_blk, None, v_blk, keep.astype(jnp.int8), state,
                scale=scale))

        state = jax.lax.fori_loop(
            0, n_live, attend_block,
            masked_attention.init_state(t, kvh * g, hd))
        return masked_attention.finish(state, kvh * g).reshape(
            1, t, kvh, g, hd)


class KeyeVL2Block(nn.Module):
    """``x + Attn(RMS(x))``, then ``x + MoE(RMS(x))``: every layer is an
    expert layer."""

    attn: dict
    moe: dict
    norm_eps: float
    dtype: Any

    @nn.compact
    def __call__(self, x, positions, pages=None):
        y = RMSNorm(self.norm_eps, self.dtype, name="attn_norm")(x)
        x = x + SparseGroupedAttention(
            **self.attn, norm_eps=self.norm_eps, dtype=self.dtype,
            name="attn")(y, positions, pages)
        y = RMSNorm(self.norm_eps, self.dtype, name="ffn_norm")(x)
        valid = None if pages is None else pages.valid
        return x + HeldExpertsMlp(**self.moe, dtype=self.dtype,
                                  name="ffn")(y, valid)


class KeyeVL2LM(nn.Module):
    """The model as the serving engine drives it (the interface of
    :class:`~distributed_training_tpu.models.deepseek_v32.DeepseekV32LM`):
    ``apply(tokens, positions, decode=True, pages=PagedKV)`` with a mutable
    ``cache`` collection of two pools a layer, ``clone(cache_len,
    kv_page_size, kv_pages, kv_dtype)``, ``max_len``, and what the engine
    asks a model about itself. ``positions`` is ``[B, T]`` or, with three
    streams, ``[3, B, T]``. ``decode=False`` is the plain forward over the
    call's own rows, through the masked-blocks lane."""

    vocab_size: int
    num_layers: int
    hidden_dim: int
    expert_dim: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    index_heads: int
    index_dim: int
    index_topk: int
    num_experts: int             # the router's width
    held: tuple                  # (first, count) of the experts held here
    experts_per_token: int
    rope_theta: float = 10000000.0
    mrope_section: tuple = (16, 24, 24)
    max_len: int = 262144
    norm_eps: float = 1e-6
    key_block: int = 1024
    expert_block_rows: int = 128
    dtype: Any = jnp.float32
    logits_dtype: Any = jnp.float32
    cache_len: int | None = None
    kv_page_size: int | None = None
    kv_pages: int | None = None
    kv_dtype: str | None = None

    # HeldExpertsMlp sows them
    step_counters = ("expert_rows", "expert_rows_max", "experts_hit")

    def paged_lane(self, t_in: int, page_size: int | None = None,
                   kv_dtype: str | None = None) -> str:
        """The attention formulation a paged call ``t_in`` rows wide takes
        (the width alone decides; pools are in the compute dtype)."""
        del page_size, kv_dtype
        lane = paged_lane(t_in, True)
        if lane == "masked-blocks" and masked_attention.kernel_fits(
                1, t_in, self.key_block, self.head_dim, 0, self.head_dim,
                self.dtype):
            return KERNEL_LANE
        return lane

    def attended_rows(self, live: int) -> int:
        """Of ``live`` cached rows, how many one query attends."""
        return min(int(live), self.index_topk)

    def index_rows_scored(self, live: int, budget: int) -> int:
        """Of a decoding slot that holds ``live`` rows of a page budget of
        ``budget``, the rows whose index key its lane reads and scores: the
        ``sparse-gather`` lane goes through the slot's whole table."""
        del live
        return int(budget)

    @nn.compact
    def __call__(self, tokens, positions=None, train: bool = False,
                 decode: bool = False, pages=None):
        del train
        if self.kv_dtype is not None:
            raise ValueError("the key/value and index pools are kept in the "
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
            num_heads=self.num_heads, num_kv_heads=self.num_kv_heads,
            head_dim=self.head_dim, index_heads=self.index_heads,
            index_dim=self.index_dim, index_topk=self.index_topk,
            rope_theta=self.rope_theta,
            mrope_section=tuple(self.mrope_section),
            key_block=self.key_block, kv_page_size=self.kv_page_size,
            kv_pages=self.kv_pages)
        moe = dict(
            num_experts=self.num_experts, held=tuple(self.held),
            hidden_dim=self.expert_dim, top_k=self.experts_per_token,
            scoring="softmax", shared_experts=0,
            block_rows=self.expert_block_rows)
        for i in range(self.num_layers):
            x = KeyeVL2Block(attn=attn, moe=moe, norm_eps=self.norm_eps,
                             dtype=self.dtype, name=f"layer{i}")(
                                 x, positions, pages)
        x = RMSNorm(self.norm_eps, self.dtype, name="norm_f")(x)
        head = self.param("lm_head", nn.initializers.normal(0.02),
                          (self.hidden_dim, self.vocab_size))
        return jnp.dot(x.astype(self.logits_dtype),
                       head.astype(self.logits_dtype))


def make_keye_vl2(*, num_classes: int, dtype: Any = jnp.float32,
                  axis_name: str | None = None, **kwargs) -> KeyeVL2LM:
    """Registry factory; ``num_classes`` is the vocabulary held here."""
    del axis_name
    return KeyeVL2LM(vocab_size=num_classes, dtype=dtype, **kwargs)
