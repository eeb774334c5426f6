"""Ling-3.0-flash's forward pass (``model_type: bailing_hybrid``) in plain
``jax.numpy`` and float32, at ``highest`` matmul precision: no cache, no
kernel, no chunked scan, nothing of the program and nothing of another
family's reference.

The equations, as the configuration file states them
(``benchmark/configs/ling-3.0-flash-ep8.json``):

- blocks ``x + Mix(RMS(x))``, ``x + FFN(RMS(x))``, RMSNorm with a learned
  gain, a final RMSNorm, an untied output head, no bias but the router's
  selection bias and the KDA gate's;
- the mixer of published layer ``i`` is latent attention where ``(i + 1)
  mod layer_group_size == 0`` and Kimi Delta Attention (KDA) elsewhere;
- **KDA**, per head ``h`` of ``head_dim`` keys and values: ``q~, k~, v~ =
  W x``; ``q, k, v = SiLU(causal depthwise conv(.))`` with a window of
  ``short_conv_kernel_size`` and no bias; ``q <- q / |q| * head_dim^-1/2``,
  ``k <- k / |k|``; ``g = kda_lower_bound * sigmoid(exp(A_log_h) (W_f x +
  b))`` per channel; ``beta = sigmoid(W_beta x)`` per head; the state runs
  token by token, ``S_t = (I - beta k k^T) Diag(exp g) S_{t-1} + beta k
  v^T`` from ``S_0 = 0``, ``o_t = S_t^T q_t``; ``y = W_o [RMSNorm(o_h) *
  sigmoid(w_h . x)]_h``;
- **latent attention** with no query latent: ``q = W_q x`` -> heads x
  ``[q_nope | q_rope]``; ``[c | k_r] = W_kva x``; ``c_kv = RMS(c)``;
  ``[k_nope | v] = W_kvb c_kv``; plain rotary of base ``rope_theta`` on
  the rotated parts (interleaved pairs ``(2j, 2j+1)``); scores ``(q . k) *
  (nope + rope)^-1/2``; causal softmax over every earlier key; each head's
  output times ``sigmoid(w_h . x)``; ``out = W_o concat_h(.)``. Per-head
  form, never absorbed;
- routing: ``s = sigmoid(W_g x)`` over every routed expert, ``s + b`` cut
  into ``n_group`` groups scored by the sum of their two highest, the
  ``topk_group`` best groups kept, the ``num_experts_per_tok`` highest of
  ``s + b`` among them (ties to the lower index), weights ``s_i / sum s_i *
  routed_scaling_factor``; gated SiLU experts and one shared expert of
  ``moe_shared_expert_intermediate_size``; dropless; the leading
  ``first_k_dense_replace`` held layers dense.

Departures, each stated in the file: the chip's share (of the router's
``published.num_experts`` outputs, experts ``assumed.first_held_expert
..`` + ``num_experts`` are held; the routed sum runs over those alone; the
shared expert is whole; embedding and head have ``vocab_size`` rows); and
attention and feed-forward layers computed a block of rows at a time, so
that 6 144 positions fit beside the weights.

``lowp`` is the control of the comparison that decides ``correct``: a
function applied to both operands of every matrix product (``round_to``),
or one that leaves them as they are and rounds the KDA state each token
(``state_in``). ``None`` is the reference itself.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 128      # queries whose scores are held at once
TOKEN_BLOCK = 2048     # tokens whose feed-forward states are held at once
L2_EPS = 1e-6          # in |q| and |k|


def layer_kinds(cfg: dict) -> list:
    """Each held layer's mixer, ``"mla"`` or ``"kda"``, by its published
    index."""
    group = int(cfg["layer_group_size"])
    return ["mla" if (i + 1) % group == 0 else "kda"
            for i in cfg["held_layers"]]


def sizes(cfg: dict) -> dict:
    """The sizes the equations use, from the file's keys."""
    return {
        "d": int(cfg["hidden_size"]), "layers": int(cfg["num_hidden_layers"]),
        "kinds": layer_kinds(cfg),
        "dense_layers": int(cfg["first_k_dense_replace"]),
        "dense": int(cfg["intermediate_size"]),
        "expert": int(cfg["moe_intermediate_size"]),
        "shared_width": int(cfg["moe_shared_expert_intermediate_size"]),
        "heads": int(cfg["num_attention_heads"]),
        "head_dim": int(cfg["head_dim"]),
        "conv": int(cfg["short_conv_kernel_size"]),
        "lower_bound": float(cfg["kda_lower_bound"]),
        "kv_rank": int(cfg["kv_lora_rank"]),
        "nope": int(cfg["qk_nope_head_dim"]),
        "rope": int(cfg["qk_rope_head_dim"]), "v": int(cfg["v_head_dim"]),
        "theta": float(cfg["rope_theta"]),
        "experts": int(cfg["published"]["num_experts"]),
        "held_first": int(cfg["assumed"]["first_held_expert"]),
        "held": int(cfg["num_experts"]),
        "per_token": int(cfg["num_experts_per_tok"]),
        "n_group": int(cfg["n_group"]), "topk_group": int(cfg["topk_group"]),
        "routed_scale": float(cfg["routed_scaling_factor"]),
        "shared": int(cfg["num_shared_experts"]),
        "rows": int(cfg["vocab_size"]), "eps": float(cfg["rms_norm_eps"]),
    }


def param_shapes(cfg: dict) -> dict:
    s = sizes(cfg)
    d, h, hd = s["d"], s["heads"], s["head_dim"]
    width = h * hd
    out = {"tok_embed": (s["rows"], d), "norm_f/scale": (d,),
           "lm_head": (d, s["rows"])}
    for i, kind in enumerate(s["kinds"]):
        a = f"layer{i}/attn"
        out.update({f"layer{i}/attn_norm/scale": (d,),
                    f"layer{i}/ffn_norm/scale": (d,), f"{a}/gate": (d, h)})
        if kind == "kda":
            out.update({f"{a}/{n}": (d, width) for n in ("wq", "wk", "wv",
                                                          "wf")})
            out.update({f"{a}/{n}": (s["conv"], width)
                        for n in ("q_conv", "k_conv", "v_conv")})
            out.update({f"{a}/f_bias": (width,), f"{a}/a_log": (h,),
                        f"{a}/wbeta": (d, h), f"{a}/o_norm/scale": (hd,),
                        f"{a}/wo": (width, d)})
        else:
            out.update({
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
            width_s = s["shared"] * s["shared_width"]
            out.update({f"{f}/shared/w1": (d, width_s),
                        f"{f}/shared/w3": (d, width_s),
                        f"{f}/shared/w2": (width_s, d)})
    return out


@functools.lru_cache(maxsize=None)
def round_to(dtype):
    """A ``lowp``: the operand rounded to ``dtype``, in float32."""
    def f(x):
        return x.astype(dtype).astype(jnp.float32)
    return f


@functools.lru_cache(maxsize=None)
def state_in(dtype):
    """A ``lowp`` that rounds no product's operands and stores the KDA
    state in ``dtype`` after each token's update, the output read from
    what is stored: a program that keeps its recurrent state below the
    float32 the configuration states, its arithmetic float32."""
    def f(x):
        return x
    f.state = round_to(dtype)
    return f


def _mm(spec, a, b, lowp):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    if lowp is not None:
        a, b = lowp(a), lowp(b)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) \
        * scale.astype(jnp.float32)


def _l2(x):
    return x * jax.lax.rsqrt((x * x).sum(-1, keepdims=True) + L2_EPS)


def _in_blocks(fn, x, size: int):
    """``fn`` over the leading axis of ``x``, ``size`` rows at a time."""
    n = x.shape[0]
    size = min(size, n)
    pad = -n % size
    xb = jnp.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1))
    out = jax.lax.map(fn, xb.reshape(-1, size, *x.shape[1:]))
    return out.reshape(-1, *out.shape[2:])[:n]


def _gate(x, p, lowp):
    """Each head's output gate ``sigmoid(w_h . x)``: [T, H]."""
    return jax.nn.sigmoid(_mm("td,dh->th", x, p["gate"], lowp))


def kda(x, p, cfg: dict, lowp=None):
    """``KDA(x)`` for one sequence ``x`` [T, d] (already normed), token by
    token from a zero state; ``p`` this layer's attention leaves by short
    name."""
    s = sizes(cfg)
    t, h, hd, k_size = x.shape[0], s["heads"], s["head_dim"], s["conv"]

    def conv(name, w):
        z = _mm("td,dc->tc", x, p[name], lowp)
        zp = jnp.pad(z, ((k_size - 1, 0), (0, 0)))
        w = w.astype(jnp.float32)
        y = sum(zp[j:j + t] * w[j] for j in range(k_size))
        return jax.nn.silu(y).reshape(t, h, hd)

    q = _l2(conv("wq", p["q_conv"])) * hd ** -0.5
    k = _l2(conv("wk", p["k_conv"]))
    v = conv("wv", p["v_conv"])
    f = _mm("td,dc->tc", x, p["wf"], lowp) + p["f_bias"].astype(jnp.float32)
    rate = jnp.repeat(jnp.exp(p["a_log"].astype(jnp.float32)), hd)
    g = (s["lower_bound"] * jax.nn.sigmoid(rate * f)).reshape(t, h, hd)
    beta = jax.nn.sigmoid(_mm("td,dh->th", x, p["wbeta"], lowp))
    stored = getattr(lowp, "state", None)

    def step(state, inp):
        q_t, k_t, v_t, g_t, b_t = inp
        state = state * jnp.exp(g_t)[:, :, None]
        ks = _mm("hk,hkv->hv", k_t, state, lowp)
        state = state + b_t[:, None, None] * k_t[:, :, None] \
            * (v_t - ks)[:, None, :]
        if stored is not None:
            state = stored(state)
        return state, _mm("hk,hkv->hv", q_t, state, lowp)

    _, o = jax.lax.scan(step, jnp.zeros((h, hd, hd), jnp.float32),
                        (q, k, v, g, beta))
    o = _rms(o, p["o_norm/scale"], s["eps"]) * _gate(x, p, lowp)[..., None]
    return _mm("tc,cd->td", o.reshape(t, h * hd), p["wo"], lowp)


def _rotate_interleaved(x, cos, sin):
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(x.shape)


def attention(x, p, cfg: dict, lowp=None):
    """``Attn(x)`` for one sequence ``x`` [T, d] (already normed), positions
    0..T-1: latent attention in the per-head form, every key and value
    expanded from the latent, each head's output gated."""
    s = sizes(cfg)
    t = x.shape[0]
    rank, nope, rope = s["kv_rank"], s["nope"], s["rope"]
    freq = s["theta"] ** (-2.0 * np.arange(rope // 2, dtype=np.float64)
                          / rope)
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] \
        * jnp.asarray(freq.astype(np.float32))
    cos, sin = jnp.cos(angles), jnp.sin(angles)               # [T, rope/2]
    scale = (nope + rope) ** -0.5

    kv = _mm("td,dc->tc", x, p["wkv_a"], lowp)
    c_kv = _rms(kv[:, :rank], p["kv_norm/scale"], s["eps"])
    k_rope = _rotate_interleaved(kv[:, rank:], cos, sin)      # [T, rope]
    expanded = _mm("tc,chd->thd", c_kv, p["wkv_b"], lowp)
    k_nope, v = expanded[..., :nope], expanded[..., nope:]    # [T, H, .]
    key_pos = jnp.arange(t)

    def block(args):
        xb, cb, sb, pos = args             # [Q, d], [Q, rope/2] x 2, [Q]
        q = _mm("td,dhe->the", xb, p["wq"], lowp)
        q_rope = _rotate_interleaved(q[..., nope:], cb[:, None], sb[:, None])
        a = (_mm("thd,shd->hts", q[..., :nope], k_nope, lowp)
             + _mm("thr,sr->hts", q_rope, k_rope, lowp)) * scale
        causal = key_pos[None, :] <= pos[:, None]
        a = jax.nn.softmax(jnp.where(causal[None], a, -jnp.inf), axis=-1)
        o = _mm("hts,shv->thv", a, v, lowp) * _gate(xb, p, lowp)[..., None]
        return _mm("thv,hvd->td", o, p["wo"], lowp)

    size = min(QUERY_BLOCK, t)
    pad = -t % size
    padded = [jnp.pad(a, [(0, pad)] + [(0, 0)] * (a.ndim - 1)).reshape(
        -1, size, *a.shape[1:]) for a in (x, cos, sin, key_pos)]
    out = jax.lax.map(block, tuple(padded))
    return out.reshape(-1, x.shape[-1])[:t]


def route(x, p, cfg: dict, lowp=None):
    """``(experts [T, k], weights [T, k])`` over ALL routed experts: the
    groups scored by their two highest ``sigmoid + bias``, the best
    ``topk_group`` kept, the ``k`` highest among them, weighted by the
    sigmoid alone."""
    s = sizes(cfg)
    score = jax.nn.sigmoid(_mm("td,de->te", x, p["router"], lowp))
    biased = score + p["router_bias"].astype(jnp.float32)
    t, e = biased.shape
    grouped = biased.reshape(t, s["n_group"], e // s["n_group"])
    group_score = jax.lax.top_k(grouped, 2)[0].sum(-1)
    kept = jax.lax.top_k(group_score, s["topk_group"])[1]
    in_kept = (jnp.arange(s["n_group"])[None, :, None]
               == kept[:, None, :]).any(-1)                  # [T, G]
    masked = jnp.where(in_kept[:, :, None], grouped, -jnp.inf).reshape(t, e)
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


def layer_leaves(params: dict, i: int, part: str = "") -> dict:
    """Layer ``i``'s leaves by short name (``part``: ``attn/`` or ``ffn/``
    for that module's alone)."""
    prefix = f"layer{i}/{part}"
    return {k[len(prefix):]: v for k, v in params.items()
            if k.startswith(prefix)}


def _hidden_one(params: dict, tokens, cfg: dict, lowp):
    s = sizes(cfg)
    x = params["tok_embed"][tokens].astype(jnp.float32)
    for i, kind in enumerate(s["kinds"]):
        p = layer_leaves(params, i)
        mix = kda if kind == "kda" else attention
        x = x + mix(_rms(x, p["attn_norm/scale"], s["eps"]),
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
