"""DeepSeek-V3.2-Exp's forward pass in plain ``jax.numpy`` and float32, at
``highest`` matmul precision: no cache, no kernel, nothing of the program.

Follows the released description as the configuration file states it
(``benchmark/configs/deepseek-v3.2-exp-ep16.json``; ISSUE 27 writes the
equations out):

- blocks ``x + Attn(RMS(x))``, ``x + FFN(RMS(x))``, RMSNorm with a gain,
  a final RMSNorm, an untied output head, no bias but the indexer's
  LayerNorm and the router's selection bias;
- rotary positions under YaRN on ``qk_rope_head_dim`` dims; MLA rotates
  interleaved pairs ``(2j, 2j+1)``, the indexer half-split pairs
  ``(j, j+32)``;
- MLA: a query latent (RMS-normed), a key/value latent ``[c_kv | k_r]``
  (``c_kv`` RMS-normed, ``k_r`` rotated, shared by all heads), softmax scale
  ``(nope + rope)^-1/2 * m^2``;
- the indexer: ``I[t, s] = sum_h w[t, h] relu(q_I[t, h] . k_I[s])``; a
  query attends the exact top ``min(index_topk, t + 1)`` keys of ``I[t, .]``
  (ties to the lower position), every other score is -inf;
- routing: sigmoid scores, a bias for selection only, groups scored by
  their two best, the best ``topk_group`` groups kept, ``num_experts_per_tok``
  chosen, weights renormalised and scaled; gated SiLU experts, one shared
  expert; dropless; the leading ``first_k_dense_replace`` layers dense.

Departures, each stated in the file:

- **the chip's share**: of the router's ``published.n_routed_experts``
  outputs, experts ``assumed.first_held_expert ..`` + ``n_routed_experts``
  are held; the routed sum runs over those alone (what the absent ones
  would add is left out, and that partial sum goes on); the shared expert
  is whole; embedding and head have ``vocab_size`` rows (a slice);
- attention in the absorbed form (the key expansion folded into the query:
  the same mathematics as the per-head form) one block of queries at a
  time, feed-forward layers one block of tokens at a time, experts upcast
  one at a time: so that 16 896 positions fit beside the weights;
- index keys and scores in float32 where the release uses FP8 with a
  Hadamard rotation of ``q_I`` and ``k_I`` (an orthogonal change of basis:
  every ``q_I . k_I`` is as it was);
- the multi-token-prediction module is not held.

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
        "q_rank": int(cfg["q_lora_rank"]), "kv_rank": int(cfg["kv_lora_rank"]),
        "nope": int(cfg["qk_nope_head_dim"]),
        "rope": int(cfg["qk_rope_head_dim"]), "v": int(cfg["v_head_dim"]),
        "index_heads": int(cfg["index_n_heads"]),
        "index_dim": int(cfg["index_head_dim"]),
        "index_topk": int(cfg["index_topk"]),
        "experts": int(cfg["published"]["n_routed_experts"]),
        "held_first": int(cfg["assumed"]["first_held_expert"]),
        "held": int(cfg["n_routed_experts"]),
        "per_token": int(cfg["num_experts_per_tok"]),
        "groups": int(cfg["n_group"]), "kept_groups": int(cfg["topk_group"]),
        "routed_scale": float(cfg["routed_scaling_factor"]),
        "shared": int(cfg["n_shared_experts"]),
        "rows": int(cfg["vocab_size"]), "eps": float(cfg["rms_norm_eps"]),
        "index_eps": float(cfg["assumed"]["index_layernorm_epsilon"]),
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
            f"{a}/wq_a": (d, s["q_rank"]), f"{a}/q_norm/scale": (s["q_rank"],),
            f"{a}/wq_b": (s["q_rank"], h, s["nope"] + s["rope"]),
            f"{a}/wkv_a": (d, s["kv_rank"] + s["rope"]),
            f"{a}/kv_norm/scale": (s["kv_rank"],),
            f"{a}/wkv_b": (s["kv_rank"], h, s["nope"] + s["v"]),
            f"{a}/wo": (h, s["v"], d),
            f"{a}/index_wq": (s["q_rank"], s["index_heads"], s["index_dim"]),
            f"{a}/index_wk": (d, s["index_dim"]),
            f"{a}/index_k_norm/scale": (s["index_dim"],),
            f"{a}/index_k_norm/bias": (s["index_dim"],),
            f"{a}/index_weights": (d, s["index_heads"])})
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


def yarn_frequencies(cfg: dict) -> np.ndarray:
    """The rotary frequencies: YaRN's when the configuration's positions
    exceed the original context, plain otherwise."""
    dim, base = int(cfg["qk_rope_head_dim"]), float(cfg["rope_theta"])
    r = cfg["rope_scaling"]
    i = np.arange(dim // 2, dtype=np.float64)
    f = base ** (-2.0 * i / dim)
    original = int(r["original_max_position_embeddings"])
    if int(cfg["max_position_embeddings"]) <= original:
        return f.astype(np.float32)

    def corr(turns):
        return dim * math.log(original / (2 * math.pi * turns)) \
            / (2 * math.log(base))

    low = max(math.floor(corr(float(r["beta_fast"]))), 0)
    high = min(math.ceil(corr(float(r["beta_slow"]))), dim - 1)
    ramp = np.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    smooth = 1.0 - ramp
    return (f / float(r["factor"]) * (1.0 - smooth)
            + f * smooth).astype(np.float32)


def softmax_scale(cfg: dict) -> float:
    width = int(cfg["qk_nope_head_dim"]) + int(cfg["qk_rope_head_dim"])
    r = cfg["rope_scaling"]
    if int(cfg["max_position_embeddings"]) <= int(
            r["original_max_position_embeddings"]):
        return width ** -0.5
    m = 0.1 * float(r["mscale"]) * math.log(float(r["factor"])) + 1.0
    return width ** -0.5 * m * m


def _rotate_interleaved(x, cos, sin):
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(x.shape)


def _rotate_half_split(x, cos, sin):
    a, b = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)


def select_keys(scores, k: int):
    """Boolean ``[Q, S]``: the ``k`` highest entries of each row of
    ``scores`` that are not -inf (ties to the lower index)."""
    q, n = scores.shape
    # + 0.0: a negative zero is a zero (top_k orders -0.0 under +0.0)
    top, chosen = jax.lax.top_k(scores + 0.0, min(k, n))
    mask = jnp.zeros((q, n), bool).at[jnp.arange(q)[:, None], chosen].set(
        top > -jnp.inf)
    return mask


def attention(x, p, cfg: dict, lowp=None):
    """``Attn(x)`` for one sequence ``x`` [T, d] (already normed), positions
    0..T-1; ``p`` this layer's attention leaves by short name."""
    s = sizes(cfg)
    t = x.shape[0]
    rope, rank, nope = s["rope"], s["kv_rank"], s["nope"]
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] \
        * jnp.asarray(yarn_frequencies(cfg))
    cos, sin = jnp.cos(angles), jnp.sin(angles)               # [T, rope/2]
    scale = softmax_scale(cfg)

    # keys: the latent row [c_kv | k_r] and the indexer's key, per token
    kv = _mm("td,dc->tc", x, p["wkv_a"], lowp)
    c_kv = _rms(kv[:, :rank], p["kv_norm/scale"], s["eps"])
    k_r = _rotate_interleaved(kv[:, rank:], cos, sin)
    k_i = _mm("td,dc->tc", x, p["index_wk"], lowp)
    mu = k_i.mean(-1, keepdims=True)
    var = ((k_i - mu) ** 2).mean(-1, keepdims=True)
    k_i = (k_i - mu) * jax.lax.rsqrt(var + s["index_eps"]) \
        * p["index_k_norm/scale"].astype(jnp.float32) \
        + p["index_k_norm/bias"].astype(jnp.float32)
    k_i = jnp.concatenate([_rotate_half_split(k_i[:, :rope], cos, sin),
                           k_i[:, rope:]], axis=-1)
    wk, wv = p["wkv_b"][..., :nope], p["wkv_b"][..., nope:]
    key_pos = jnp.arange(t)

    def block(args):
        xb, cb, sb, pos = args             # [Q, d], [Q, rope/2] x 2, [Q]
        c_q = _rms(_mm("td,dr->tr", xb, p["wq_a"], lowp),
                   p["q_norm/scale"], s["eps"])
        q = _mm("tr,rhd->thd", c_q, p["wq_b"], lowp)
        q_nope = q[..., :nope]
        q_rope = _rotate_interleaved(q[..., nope:], cb[:, None], sb[:, None])
        q_i = _mm("tr,rhd->thd", c_q, p["index_wq"], lowp)
        q_i = jnp.concatenate([
            _rotate_half_split(q_i[..., :rope], cb[:, None], sb[:, None]),
            q_i[..., rope:]], axis=-1)
        w_i = _mm("td,dh->th", xb, p["index_weights"], lowp) \
            * (s["index_heads"] ** -0.5 * s["index_dim"] ** -0.5)
        index = (jax.nn.relu(_mm("thd,sd->ths", q_i, k_i, lowp))
                 * w_i[..., None]).sum(1)                      # [Q, T]
        causal = key_pos[None, :] <= pos[:, None]
        keep = select_keys(jnp.where(causal, index, -jnp.inf),
                           s["index_topk"])
        q_abs = _mm("thd,chd->thc", q_nope, wk, lowp)
        a = (_mm("thc,sc->hts", q_abs, c_kv, lowp)
             + _mm("thr,sr->hts", q_rope, k_r, lowp)) * scale
        a = jax.nn.softmax(jnp.where(keep[None], a, -jnp.inf), axis=-1)
        o = _mm("hts,sc->thc", a, c_kv, lowp)
        o = _mm("thc,chd->thd", o, wv, lowp)
        return _mm("thv,hvd->td", o, p["wo"], lowp)

    size = min(QUERY_BLOCK, t)
    pad = -t % size
    padded = [jnp.pad(a, [(0, pad)] + [(0, 0)] * (a.ndim - 1)).reshape(
        -1, size, *a.shape[1:]) for a in (x, cos, sin, key_pos)]
    out = jax.lax.map(block, tuple(padded))
    return out.reshape(-1, x.shape[-1])[:t]


def route(x, p, cfg: dict, lowp=None):
    """``(experts [T, k], weights [T, k])`` over ALL routed experts."""
    s = sizes(cfg)
    t = x.shape[0]
    score = jax.nn.sigmoid(_mm("td,de->te", x, p["router"], lowp))
    biased = score + p["router_bias"].astype(jnp.float32)
    grouped = biased.reshape(t, s["groups"], -1)
    group_score = jax.lax.top_k(grouped, 2)[0].sum(-1)
    kept = jax.lax.top_k(group_score, s["kept_groups"])[1]
    keep = jnp.zeros((t, s["groups"]), bool).at[
        jnp.arange(t)[:, None], kept].set(True)
    masked = jnp.where(keep[:, :, None], grouped, -jnp.inf).reshape(t, -1)
    experts = jax.lax.top_k(masked, s["per_token"])[1]
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


def _layer(params: dict, i: int) -> dict:
    prefix = f"layer{i}/"
    return {k[len(prefix):]: v for k, v in params.items()
            if k.startswith(prefix)}


def _hidden_one(params: dict, tokens, cfg: dict, lowp):
    s = sizes(cfg)
    x = params["tok_embed"][tokens].astype(jnp.float32)
    for i in range(s["layers"]):
        p = _layer(params, i)
        attn = {k[len("attn/"):]: v for k, v in p.items()
                if k.startswith("attn/")}
        ffn = {k[len("ffn/"):]: v for k, v in p.items()
               if k.startswith("ffn/")}
        x = x + attention(_rms(x, p["attn_norm/scale"], s["eps"]), attn,
                          cfg, lowp)
        y = _rms(x, p["ffn_norm/scale"], s["eps"])
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
