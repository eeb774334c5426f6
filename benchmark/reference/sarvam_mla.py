"""sarvam-105b's forward pass (``model_type: sarvam_mla``) in plain
``jax.numpy`` and float32, at ``highest`` matmul precision: no cache, no
kernel, nothing of the program and nothing of another family's reference.

The equations, as the configuration file states them
(``benchmark/configs/sarvam-105b-ep4.json``; ISSUE 32 writes them out):

- blocks ``x + Attn(RMS(x))``, ``x + FFN(RMS(x))``, RMSNorm with a learned
  gain, a final RMSNorm, an untied output head, no bias anywhere but the
  router's selection bias;
- latent attention with NO query latent: ``q = W_q x`` -> heads x ``[q_nope |
  q_rope]``; ``[c | k_r] = W_kva x``; ``c_kv = RMS(c)``; ``k_rope =
  rot(k_r)``, shared by all heads; ``[k_nope | v] = W_kvb c_kv`` per head;
  scores ``(q_nope . k_nope + q_rope . k_rope) * s`` with ``s = (nope +
  rope)^-1/2 * m^2`` and YaRN's frequencies on the rotated part
  (interleaved pairs ``(2j, 2j+1)``); causal softmax over ALL earlier keys
  (no selection); ``out = W_o concat_h(p . v)``. Computed in this per-head
  form, never absorbed;
- ``use_qk_norm`` is read as the RMSNorm on the compressed latent ``c``
  alone (the file's ``assumed`` says why, and what the other reading is);
- routing: ``s = sigmoid(W_g x)`` over every routed expert, the
  ``num_experts_per_tok`` highest of ``s + b`` (ties to the lower index),
  weights ``s_i / sum s_i * routed_scaling_factor``, no groups; gated SiLU
  experts and one shared expert; dropless; the leading
  ``first_k_dense_replace`` layers dense.

Departures, each stated in the file:

- **the chip's share**: of the router's ``published.num_experts`` outputs,
  experts ``assumed.first_held_expert ..`` + ``num_experts`` are held; the
  routed sum runs over those alone (what the absent ones would add is left
  out, and that partial sum goes on); the shared expert is whole; embedding
  and head have ``vocab_size`` rows (a slice);
- attention one block of queries at a time, feed-forward layers one block
  of tokens at a time, experts upcast one at a time: so that 6 144
  positions fit beside the weights.

``lowp`` is the control of the comparison that decides ``correct``: a
function applied to both operands of every matrix product. ``None`` is the
reference itself.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 128      # queries whose scores are held at once
TOKEN_BLOCK = 2048     # tokens whose feed-forward states are held at once


def sizes(cfg: dict) -> dict:
    """The sizes the equations use, from the file's keys."""
    return {
        "d": int(cfg["hidden_size"]), "layers": int(cfg["num_hidden_layers"]),
        "dense_layers": int(cfg["first_k_dense_replace"]),
        "dense": int(cfg["intermediate_size"]),
        "expert": int(cfg["moe_intermediate_size"]),
        "heads": int(cfg["num_attention_heads"]),
        "kv_rank": int(cfg["kv_lora_rank"]),
        "nope": int(cfg["qk_nope_head_dim"]),
        "rope": int(cfg["qk_rope_head_dim"]), "v": int(cfg["v_head_dim"]),
        "experts": int(cfg["published"]["num_experts"]),
        "held_first": int(cfg["assumed"]["first_held_expert"]),
        "held": int(cfg["num_experts"]),
        "per_token": int(cfg["num_experts_per_tok"]),
        "routed_scale": float(cfg["routed_scaling_factor"]),
        "shared": int(cfg["num_shared_experts"]),
        "rows": int(cfg["vocab_size"]), "eps": float(cfg["rms_norm_eps"]),
    }


def param_shapes(cfg: dict) -> dict:
    s = sizes(cfg)
    d, h = s["d"], s["heads"]
    out = {"tok_embed": (s["rows"], d), "norm_f/scale": (d,),
           "lm_head": (d, s["rows"])}
    for i in range(s["layers"]):
        a = f"layer{i}/attn"
        out.update({
            f"layer{i}/attn_norm/scale": (d,),
            f"layer{i}/ffn_norm/scale": (d,),
            f"{a}/wq": (d, h, s["nope"] + s["rope"]),
            f"{a}/wkv_a": (d, s["kv_rank"] + s["rope"]),
            f"{a}/kv_norm/scale": (s["kv_rank"],),
            f"{a}/wkv_b": (s["kv_rank"], h, s["nope"] + s["v"]),
            f"{a}/wo": (h, s["v"], d)})
        f = f"layer{i}/ffn"
        if i < s["dense_layers"]:
            out.update({f"{f}/w1": (d, s["dense"]), f"{f}/w3": (d, s["dense"]),
                        f"{f}/w2": (s["dense"], d)})
            continue
        out.update({
            f"{f}/router": (d, s["experts"]),
            f"{f}/router_bias": (s["experts"],),
            f"{f}/w1": (s["held"], d, s["expert"]),
            f"{f}/w3": (s["held"], d, s["expert"]),
            f"{f}/w2": (s["held"], s["expert"], d)})
        if s["shared"]:
            width = s["shared"] * s["expert"]
            out.update({f"{f}/shared/w1": (d, width),
                        f"{f}/shared/w3": (d, width),
                        f"{f}/shared/w2": (width, d)})
    return out


@functools.lru_cache(maxsize=None)
def round_to(dtype):
    """A ``lowp``: the operand rounded to ``dtype``, in float32."""
    def f(x):
        return x.astype(dtype).astype(jnp.float32)
    return f


def _mm(spec, a, b, lowp):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    if lowp is not None:
        a, b = lowp(a), lowp(b)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) \
        * scale.astype(jnp.float32)


def _in_blocks(fn, x, size: int):
    """``fn`` over the leading axis of ``x``, ``size`` rows at a time."""
    n = x.shape[0]
    size = min(size, n)
    pad = -n % size
    xb = jnp.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1))
    out = jax.lax.map(fn, xb.reshape(-1, size, *x.shape[1:]))
    return out.reshape(-1, *out.shape[2:])[:n]


def _scaled(cfg: dict) -> bool:
    """YaRN applies when the declared positions exceed the original
    context."""
    return int(cfg["max_position_embeddings"]) > int(
        cfg["rope_scaling"]["original_max_position_embeddings"])


def yarn_frequencies(cfg: dict) -> np.ndarray:
    """The ``qk_rope_head_dim / 2`` rotary frequencies: those that turn
    more than ``beta_fast`` times over the original context stay, those
    that turn fewer than ``beta_slow`` times are divided by ``factor``, a
    linear ramp between."""
    dim, base = int(cfg["qk_rope_head_dim"]), float(cfg["rope_theta"])
    r = cfg["rope_scaling"]
    i = np.arange(dim // 2, dtype=np.float64)
    f = base ** (-2.0 * i / dim)
    if not _scaled(cfg):
        return f.astype(np.float32)
    original = int(r["original_max_position_embeddings"])

    def corr(turns):
        return dim * math.log(original / (2 * math.pi * turns)) \
            / (2 * math.log(base))

    low = max(math.floor(corr(float(r["beta_fast"]))), 0)
    high = min(math.ceil(corr(float(r["beta_slow"]))), dim - 1)
    ramp = np.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    return (f / float(r["factor"]) * ramp + f * (1.0 - ramp)).astype(
        np.float32)


def softmax_scale(cfg: dict) -> float:
    """``(nope + rope)^-1/2 * (0.1 * mscale_all_dim * ln(factor) + 1)^2``."""
    width = int(cfg["qk_nope_head_dim"]) + int(cfg["qk_rope_head_dim"])
    if not _scaled(cfg):
        return width ** -0.5
    r = cfg["rope_scaling"]
    m = 0.1 * float(r["mscale_all_dim"]) * math.log(float(r["factor"])) + 1.0
    return width ** -0.5 * m * m


def _rotate_interleaved(x, cos, sin):
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(x.shape)


def attention(x, p, cfg: dict, lowp=None):
    """``Attn(x)`` for one sequence ``x`` [T, d] (already normed), positions
    0..T-1; ``p`` this layer's attention leaves by short name. Per head:
    every key and value expanded from the latent."""
    s = sizes(cfg)
    t = x.shape[0]
    rank, nope = s["kv_rank"], s["nope"]
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] \
        * jnp.asarray(yarn_frequencies(cfg))
    cos, sin = jnp.cos(angles), jnp.sin(angles)               # [T, rope/2]
    scale = softmax_scale(cfg)

    kv = _mm("td,dc->tc", x, p["wkv_a"], lowp)
    c_kv = _rms(kv[:, :rank], p["kv_norm/scale"], s["eps"])
    k_rope = _rotate_interleaved(kv[:, rank:], cos, sin)      # [T, rope]
    expanded = _mm("tc,chd->thd", c_kv, p["wkv_b"], lowp)
    k_nope, v = expanded[..., :nope], expanded[..., nope:]    # [T, H, .]
    key_pos = jnp.arange(t)

    def block(args):
        xb, cb, sb, pos = args             # [Q, d], [Q, rope/2] x 2, [Q]
        q = _mm("td,dhe->the", xb, p["wq"], lowp)
        q_nope = q[..., :nope]
        q_rope = _rotate_interleaved(q[..., nope:], cb[:, None], sb[:, None])
        a = (_mm("thd,shd->hts", q_nope, k_nope, lowp)
             + _mm("thr,sr->hts", q_rope, k_rope, lowp)) * scale
        causal = key_pos[None, :] <= pos[:, None]
        a = jax.nn.softmax(jnp.where(causal[None], a, -jnp.inf), axis=-1)
        o = _mm("hts,shv->thv", a, v, lowp)
        return _mm("thv,hvd->td", o, p["wo"], lowp)

    size = min(QUERY_BLOCK, t)
    pad = -t % size
    padded = [jnp.pad(a, [(0, pad)] + [(0, 0)] * (a.ndim - 1)).reshape(
        -1, size, *a.shape[1:]) for a in (x, cos, sin, key_pos)]
    out = jax.lax.map(block, tuple(padded))
    return out.reshape(-1, x.shape[-1])[:t]


def route(x, p, cfg: dict, lowp=None):
    """``(experts [T, k], weights [T, k])`` over ALL routed experts: the
    ``k`` highest of ``sigmoid + bias``, weighted by the sigmoid alone."""
    s = sizes(cfg)
    score = jax.nn.sigmoid(_mm("td,de->te", x, p["router"], lowp))
    biased = score + p["router_bias"].astype(jnp.float32)
    experts = jax.lax.top_k(biased, s["per_token"])[1]
    w = jnp.take_along_axis(score, experts, axis=-1)
    return experts, w / w.sum(-1, keepdims=True) * s["routed_scale"]


def _ffn(x, w1, w3, w2, lowp):
    def rows(xb):
        hid = jax.nn.silu(_mm("td,df->tf", xb, w1, lowp)) \
            * _mm("td,df->tf", xb, w3, lowp)
        return _mm("tf,fd->td", hid, w2, lowp)

    return _in_blocks(rows, x, TOKEN_BLOCK)


def expert_layer(x, p, cfg: dict, lowp=None, shared: bool = True):
    """The held experts' part of ``sum_i w_i E_i(x)`` for ``x`` [T, d],
    plus the shared expert unless ``shared`` is False."""
    s = sizes(cfg)
    experts, weights = route(x, p, cfg, lowp)

    def one(acc, args):
        e, w1, w3, w2 = args
        w = jnp.where(experts == e, weights, 0.0).sum(-1)     # [T]
        return acc + w[:, None] * _ffn(x, w1, w3, w2, lowp), None

    held = s["held_first"] + jnp.arange(s["held"])
    out, _ = jax.lax.scan(one, jnp.zeros_like(x),
                          (held, p["w1"], p["w3"], p["w2"]))
    if shared and s["shared"]:
        out = out + _ffn(x, p["shared/w1"], p["shared/w3"], p["shared/w2"],
                         lowp)
    return out


def layer_leaves(params: dict, i: int, part: str = "") -> dict:
    """Layer ``i``'s leaves by short name (``part``: ``attn/`` or ``ffn/``
    for that module's alone)."""
    prefix = f"layer{i}/{part}"
    return {k[len(prefix):]: v for k, v in params.items()
            if k.startswith(prefix)}


def _hidden_one(params: dict, tokens, cfg: dict, lowp):
    s = sizes(cfg)
    x = params["tok_embed"][tokens].astype(jnp.float32)
    for i in range(s["layers"]):
        p = layer_leaves(params, i)
        x = x + attention(_rms(x, p["attn_norm/scale"], s["eps"]),
                          layer_leaves(params, i, "attn/"), cfg, lowp)
        y = _rms(x, p["ffn_norm/scale"], s["eps"])
        ffn = layer_leaves(params, i, "ffn/")
        if i < s["dense_layers"]:
            x = x + _ffn(y, ffn["w1"], ffn["w3"], ffn["w2"], lowp)
        else:
            x = x + expert_layer(y, ffn, cfg, lowp)
    return _rms(x, params["norm_f/scale"], s["eps"])


def hidden(params: dict, tokens, cfg: dict, lowp=None):
    """The final, normed states ``[B, T, width]`` in float32 for int tokens
    ``[B, T]``: everything but the output head. ``params`` is ``{path:
    array}`` as ``param_shapes`` names them, in any float type: each leaf is
    upcast where it is used."""
    return jnp.stack([_hidden_one(params, row, cfg, lowp) for row in tokens])


def head(params: dict, x, cfg: dict, lowp=None):
    """Logits ``[..., vocab_size]`` of states ``[..., width]``."""
    return _mm("...m,mv->...v", x, params["lm_head"], lowp)


def forward(params: dict, tokens, cfg: dict, lowp=None):
    """Logits ``[B, T, vocab_size]`` in float32 for int tokens ``[B, T]``."""
    return head(params, hidden(params, tokens, cfg, lowp), cfg, lowp)
