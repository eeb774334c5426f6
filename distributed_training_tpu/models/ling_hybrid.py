"""Decoder LM of the Ling hybrid family (``bailing_hybrid``) for the serving
engine: Kimi Delta Attention (KDA) layers, which carry a recurrent state
per sequence, beside a latent-attention layer in every ``layer_group_size``,
which keeps a paged cache; grouped sigmoid routing over held experts.

**KDA, the linear-attention layer.** With ``x`` the layer's normed input
and heads of ``dk = dv`` (``head_dim``)::

    q~, k~, v~ = W_q x, W_k x, W_v x
    q, k, v    = SiLU(causal depthwise conv_4(.)) per channel
    q <- q / |q| * dk^-1/2,   k <- k / |k|           (per head)
    g    = lower_bound * sigmoid(exp(A_log_h) (W_f x + b))   in [lb, 0]^dk
    beta = sigmoid(W_beta x)                          one per head
    S_t  = (I - beta k k^T) Diag(exp g) S_{t-1} + beta k v^T,   S_0 = 0
    o_t  = S_t^T q_t
    y    = W_o [RMSNorm(o_h) * sigmoid(w_h . x)]_h    (head-wise gate)

Three forms of the one recurrence, float32 throughout:

- the **chunk** form (:func:`kda_chunk`, the prefill chunk and the plain
  forward): the WY form of the gated delta rule over sub-chunks of
  :data:`SUB_CHUNK` rows. Inside a sub-chunk the rows' interactions are
  matrix products (a unit lower-triangular system solved by a product of
  four factors); only the state runs from one sub-chunk to the next.
  Decays are taken relative to the sub-chunk's start, so no exponent
  exceeds ``|lower_bound| x SUB_CHUNK = 80`` in magnitude (float32 holds
  e^88);
- the **one-step** form for the decode lane: the Pallas kernel
  ``ops/kda_decode.py::kda_decode`` (the state read once and written once
  per decoding slot, the others untouched);
- the token-by-token recurrence is the reference's
  (``benchmark/reference/ling_hybrid.py``).

**Two kinds of cache.** A KDA layer's ``cache`` leaves are per-slot slabs
with a leading slot axis: ``kda_state [slots, H, dk, dv]`` float32 and
``kda_conv [slots, conv - 1, 3 H dk]`` (the last inputs of the q, k, v
convolutions), updated in place. The model says so (``slot_state``); the
engine then clones it with ``state_slots`` and hands the chunk lane's call
``slots``, the chunk's slot; without ``slots`` row ``b`` is slot ``b``, as
in the decode lane. The latent layer's pool is paged, as
``models/deepseek_v32.py``'s dense model keeps it. A chunk that starts at
position 0 starts from zeros, so a reseated slot needs no reset; the
decode lane advances the decoding slots' slabs and leaves every other
slab as it was. A state cannot be rebuilt from a page chain or rolled
back, which is why the engine refuses the prefix cache and speculation
for such a model.

**Latent attention** is ``SparseLatentAttention`` with no query latent, no
indexer, plain rotary (``rope_scaling`` null) and the head-wise gate;
**experts** are ``HeldExpertsMlp`` with DeepSeek's grouped router.
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from distributed_training_tpu.models.deepseek_v32 import (
    RMSNorm,
    SparseLatentAttention,
    lane_name,
)
from distributed_training_tpu.models.moe import (
    GatedMlp,
    HeldExpertsMlp,
    model_expert_lane,
)
from distributed_training_tpu.ops import kda_decode as kda_ops

HIGHEST = jax.lax.Precision.HIGHEST
SUB_CHUNK = 16        # rows whose interactions are one matrix product
L2_EPS = 1e-6         # in |q| and |k|


def _mm(spec, a, b):
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _unit_lower_inverse(n):
    """``(I + N)^-1`` for strictly lower ``N [..., L, L]``, L <= 16:
    ``(I - N)(I + N^2)(I + N^4)(I + N^8)`` (N^16 = 0)."""
    size = n.shape[-1]
    eye = jnp.eye(size, dtype=n.dtype)
    inv, power = eye - n, n
    for _ in range(max(size - 1, 1).bit_length() - 1):
        power = _mm("...ij,...jk->...ik", power, power)
        inv = _mm("...ij,...jk->...ik", inv, eye + power)
    return inv


def kda_chunk(q, k, v, g, beta, state):
    """The recurrence over one sequence's rows from ``state``: ``q`` /
    ``k`` / ``g`` [T, H, dk], ``v`` [T, H, dv], ``beta`` [T, H] float32,
    ``state`` [H, dk, dv]. A row with ``g = 0`` and ``beta = 0`` leaves
    the state as it is (padding). Returns ``(o [T, H, dv], state)``."""
    t, h, dk = q.shape
    size = min(SUB_CHUNK, t)
    pad = -t % size

    def subs(a):                                       # [n, H, L, ...]
        a = jnp.pad(a, [(0, pad)] + [(0, 0)] * (a.ndim - 1))
        return jnp.moveaxis(a.reshape(-1, size, *a.shape[1:]), 1, 2)

    q, k, v, g, beta = map(subs, (q, k, v, g, beta))
    cum = jnp.cumsum(g, axis=2)                        # G_t from the start
    last = cum[:, :, -1:]
    qd, kd = q * jnp.exp(cum), k * jnp.exp(cum)
    ki = k * jnp.exp(-cum)
    kl = k * jnp.exp(last - cum)                       # decayed to the end
    rows = jnp.arange(size)
    a = _mm("nhtc,nhsc->nhts", kd, ki)
    n = jnp.where(rows[:, None] > rows, a, 0.0) * beta[..., None]
    solve = _unit_lower_inverse(n)
    w = _mm("nhts,nhsc->nhtc", solve, beta[..., None] * kd)
    u = _mm("nhts,nhsv->nhtv", solve, beta[..., None] * v)
    m = jnp.where(rows[:, None] >= rows, _mm("nhtc,nhsc->nhts", qd, ki), 0.0)

    def step(s, xs):
        w, u, qd, m, kl, last = xs
        delta = u - _mm("htc,hcv->htv", w, s)
        o = _mm("htc,hcv->htv", qd, s) + _mm("hts,hsv->htv", m, delta)
        s = jnp.exp(last[:, 0])[..., None] * s \
            + _mm("htc,htv->hcv", kl, delta)
        return s, o

    state, o = jax.lax.scan(step, state.astype(jnp.float32),
                            (w, u, qd, m, kl, last))
    o = jnp.moveaxis(o, 2, 1).reshape(-1, h, o.shape[-1])
    return o[:t], state


def causal_conv(x, tail, w):
    """Causal depthwise convolution: ``x`` [B, T, C] after ``tail`` [B,
    K - 1, C] (the inputs before it), ``w`` [K, C]; ``y_t = sum_j w_j
    x_{t - K + 1 + j}`` in float32. Returns ``(y, ext)``, ``ext`` the
    inputs tail first [B, K - 1 + T, C]."""
    ext = jnp.concatenate([tail.astype(x.dtype), x], axis=1)
    size, t = w.shape[0], x.shape[1]
    w = w.astype(jnp.float32)
    y = sum(ext[:, j:j + t].astype(jnp.float32) * w[j] for j in range(size))
    return y, ext


def _l2_normed(x):
    return x * jax.lax.rsqrt((x * x).sum(-1, keepdims=True) + L2_EPS)


class KimiDeltaAttention(nn.Module):
    """A KDA layer (the module docstring's equations). ``state_slots`` set:
    a ``decode=True`` call reads and writes the per-slot slabs of its
    batch rows' ``slots``; unset, the call is the plain forward from a
    zero state."""

    num_heads: int
    head_dim: int
    conv_size: int = 4
    lower_bound: float = -5.0
    norm_eps: float = 1e-6
    dtype: Any = jnp.float32
    state_slots: int | None = None

    @nn.compact
    def __call__(self, x, positions, valid=None, slots=None,
                 cached: bool = False):
        b, t, d = x.shape
        h, hd, dt = self.num_heads, self.head_dim, self.dtype
        width = h * hd
        init = nn.initializers.normal(0.02)
        w_qkv = [self.param(n, init, (d, width)).astype(dt)
                 for n in ("wq", "wk", "wv")]
        convs = [self.param(n, init, (self.conv_size, width))
                 for n in ("q_conv", "k_conv", "v_conv")]
        w_f = self.param("wf", init, (d, width)).astype(dt)
        f_bias = self.param("f_bias", init, (width,))
        a_log = self.param("a_log", init, (h,))
        w_beta = self.param("wbeta", init, (d, h)).astype(dt)
        w_gate = self.param("gate", init, (d, h)).astype(dt)
        w_o = self.param("wo", init, (width, d)).astype(dt)
        if valid is None:
            valid = jnp.ones((b, t), bool)
        f32 = jnp.float32

        with jax.named_scope("kda.project"):
            qkv = jnp.concatenate([jnp.dot(x, w) for w in w_qkv], axis=-1)
            f = jnp.dot(x, w_f, preferred_element_type=f32) + f_bias
            g = self.lower_bound * jax.nn.sigmoid(
                jnp.exp(a_log.astype(f32)).repeat(hd) * f)
            beta = jax.nn.sigmoid(jnp.dot(x, w_beta, preferred_element_type=f32))
            # a row that does not exist neither decays nor writes the state
            g = jnp.where(valid[..., None], g, 0.0).reshape(b, t, h, hd)
            beta = jnp.where(valid[..., None], beta, 0.0)

        if cached:
            if self.state_slots is None:
                raise ValueError("a cached call needs state_slots: clone the "
                                 "model with it (serving.Engine does)")
            if slots is None:
                slots = jnp.arange(b, dtype=jnp.int32)
            state_all = self.variable(
                "cache", "kda_state", jnp.zeros,
                (self.state_slots, h, hd, hd), f32)
            conv_all = self.variable(
                "cache", "kda_conv", jnp.zeros,
                (self.state_slots, self.conv_size - 1, 3 * width), dt)
            # a chunk that starts at position 0 starts from zeros
            fresh = (positions[:, 0] == 0) & (t > 1)
            tail = jnp.where(fresh[:, None, None], 0, conv_all.value[slots])
        else:
            tail = jnp.zeros((b, self.conv_size - 1, 3 * width), dt)

        with jax.named_scope("kda.conv"):
            w_conv = jnp.concatenate(convs, axis=-1)
            y, ext = causal_conv(qkv, tail, w_conv)
            y = jax.nn.silu(y).reshape(b, t, 3, h, hd)
            q = _l2_normed(y[:, :, 0]) * hd ** -0.5
            k = _l2_normed(y[:, :, 1])
            v = y[:, :, 2]
            # the last conv_size - 1 inputs up to each row's last valid one
            count = valid.sum(axis=1, dtype=jnp.int32)
            new_tail = jax.vmap(lambda e, c: jax.lax.dynamic_slice_in_dim(
                e, c, self.conv_size - 1, 0))(ext, count)

        with jax.named_scope("kda.recur"):
            if not cached:
                o, _ = jax.vmap(kda_chunk)(
                    q, k, v, g, beta, jnp.zeros((b, h, hd, hd), f32))
            elif t == 1:
                o, state_all.value = kda_ops.kda_decode(
                    q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0],
                    state_all.value, slots, valid[:, 0])
                o = o[:, None]
            else:
                s0 = jnp.where(fresh[:, None, None, None], 0.0,
                               state_all.value[slots])
                o, s = jax.vmap(kda_chunk)(q, k, v, g, beta, s0)
                keep = (count > 0)[:, None, None, None]
                state_all.value = state_all.value.at[slots].set(
                    jnp.where(keep, s, state_all.value[slots]))
        if cached:
            conv_all.value = conv_all.value.at[slots].set(jnp.where(
                (count > 0)[:, None, None], new_tail, conv_all.value[slots]))

        with jax.named_scope("kda.out"):
            o = RMSNorm(self.norm_eps, f32, name="o_norm")(o)
            gate = jax.nn.sigmoid(jnp.dot(x, w_gate,
                                          preferred_element_type=f32))
            o = (o * gate[..., None]).astype(dt).reshape(b, t, width)
            return jnp.dot(o, w_o)


class LingHybridBlock(nn.Module):
    """``x + Mixer(RMS(x))``, then ``x + FFN(RMS(x))``: the mixer a KDA
    layer or latent attention, the FFN dense in the leading layers and the
    held experts' share plus the shared expert in the rest."""

    kind: str                    # "kda" or "mla"
    kda: dict
    attn: dict
    dense_dim: int | None
    moe: dict | None
    norm_eps: float
    dtype: Any

    @nn.compact
    def __call__(self, x, positions, pages=None, slots=None,
                 cached: bool = False):
        y = RMSNorm(self.norm_eps, self.dtype, name="attn_norm")(x)
        valid = None if pages is None else pages.valid
        if self.kind == "kda":
            x = x + KimiDeltaAttention(
                **self.kda, norm_eps=self.norm_eps, dtype=self.dtype,
                name="attn")(y, positions, valid, slots, cached)
        else:
            x = x + SparseLatentAttention(
                **self.attn, norm_eps=self.norm_eps, dtype=self.dtype,
                name="attn")(y, positions, pages)
        y = RMSNorm(self.norm_eps, self.dtype, name="ffn_norm")(x)
        if self.dense_dim is not None:
            return x + GatedMlp(self.dense_dim, dtype=self.dtype,
                                name="ffn")(y)
        return x + HeldExpertsMlp(**self.moe, dtype=self.dtype,
                                  name="ffn")(y, valid)


class LingHybridLM(nn.Module):
    """The model as the serving engine drives it: ``apply(tokens,
    positions, decode=True, pages=PagedKV, slots=...)`` with a mutable
    ``cache`` collection (the latent layers' pools and the KDA layers'
    per-slot slabs), ``clone(cache_len, kv_page_size, kv_pages, kv_dtype,
    state_slots)``, ``max_len``, and what the engine asks a model about
    itself (:meth:`paged_lane`, :meth:`attended_rows`,
    :meth:`index_rows_scored`, ``step_counters``, ``stacked_blocks``,
    ``slot_state``). ``kinds`` gives each held layer's mixer, ``"kda"`` or
    ``"mla"``. ``decode=False`` is the plain forward from zero states."""

    vocab_size: int
    kinds: tuple
    first_dense: int
    hidden_dim: int
    dense_dim: int
    expert_dim: int
    num_heads: int
    head_dim: int                # a KDA head's key and value width
    kv_rank: int
    nope_dim: int
    rope_dim: int
    v_dim: int
    num_experts: int             # the router's width
    held: tuple                  # (first, count) of the experts held here
    experts_per_token: int
    n_group: int
    topk_group: int
    routed_scale: float
    shared_experts: int = 1
    conv_size: int = 4
    gate_lower_bound: float = -5.0
    rope_theta: float = 6e6
    max_len: int = 262144
    norm_eps: float = 1e-6
    key_block: int = 1024
    dtype: Any = jnp.float32
    logits_dtype: Any = jnp.float32
    cache_len: int | None = None
    kv_page_size: int | None = None
    kv_pages: int | None = None
    kv_dtype: str | None = None
    state_slots: int | None = None

    # HeldExpertsMlp sows them
    step_counters = ("expert_rows", "expert_rows_max", "experts_hit")
    stacked_blocks = None
    # the cache leaves that are per-slot slabs (a leading slot axis)
    slot_state = ("kda_state", "kda_conv")

    def paged_lane(self, t_in: int, page_size: int | None = None,
                   kv_dtype: str | None = None) -> str:
        """The latent layers' formulation at a paged call ``t_in`` rows
        wide (the KDA layers hold no pages)."""
        del kv_dtype
        return lane_name(self, t_in, False, page_size)

    expert_lane = model_expert_lane

    def attended_rows(self, live: int) -> int:
        """Dense latent attention: a query reads every live row."""
        return int(live)

    def index_rows_scored(self, live: int, budget: int) -> int:
        del live, budget
        return 0

    @nn.compact
    def __call__(self, tokens, positions=None, train: bool = False,
                 decode: bool = False, pages=None, slots=None):
        del train
        if self.kv_dtype is not None:
            raise ValueError("the latent pools are kept in the compute "
                             f"dtype; kv_dtype={self.kv_dtype!r}")
        if decode and pages is None:
            raise ValueError("decode=True runs through the paged pools: "
                             "pass pages= (serving.Engine does)")
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(tokens.shape[-1]),
                                         tokens.shape)
        embedding = self.param("tok_embed", nn.initializers.normal(0.02),
                               (self.vocab_size, self.hidden_dim))
        x = jnp.take(embedding.astype(self.dtype), tokens, axis=0)
        kda = dict(num_heads=self.num_heads, head_dim=self.head_dim,
                   conv_size=self.conv_size,
                   lower_bound=self.gate_lower_bound,
                   state_slots=self.state_slots)
        attn = dict(
            num_heads=self.num_heads, q_rank=None, kv_rank=self.kv_rank,
            nope_dim=self.nope_dim, rope_dim=self.rope_dim, v_dim=self.v_dim,
            index_heads=None, index_dim=None, index_topk=None,
            rope=(float(self.rope_theta), 1.0, self.max_len, 32.0, 1.0, 1.0),
            rope_scaled=False, key_block=self.key_block,
            kv_page_size=self.kv_page_size, kv_pages=self.kv_pages,
            head_gate=True)
        moe = dict(
            num_experts=self.num_experts, held=tuple(self.held),
            hidden_dim=self.expert_dim, top_k=self.experts_per_token,
            n_group=self.n_group, topk_group=self.topk_group,
            routed_scale=self.routed_scale,
            shared_experts=self.shared_experts)
        for i, kind in enumerate(self.kinds):
            dense = i < self.first_dense
            x = LingHybridBlock(
                kind=kind, kda=kda, attn=attn,
                dense_dim=self.dense_dim if dense else None,
                moe=None if dense else moe, norm_eps=self.norm_eps,
                dtype=self.dtype, name=f"layer{i}")(
                    x, positions, pages, slots, decode)
        x = RMSNorm(self.norm_eps, self.dtype, name="norm_f")(x)
        head = self.param("lm_head", nn.initializers.normal(0.02),
                          (self.hidden_dim, self.vocab_size))
        return jnp.dot(x.astype(self.logits_dtype),
                       head.astype(self.logits_dtype))


def make_ling_hybrid(*, num_classes: int, dtype: Any = jnp.float32,
                     axis_name: str | None = None, **kwargs) -> LingHybridLM:
    """Registry factory; ``num_classes`` is the vocabulary held here."""
    del axis_name
    return LingHybridLM(vocab_size=num_classes, dtype=dtype, **kwargs)
