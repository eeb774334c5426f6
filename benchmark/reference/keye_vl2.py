"""The language model of Keye-VL-2.0-30B-A3B: its forward pass in plain
``jax.numpy`` and float32, at ``highest`` matmul precision: no cache, no
kernel, nothing of the program.

Follows the configuration file (``benchmark/configs/keye-vl-2.0-30b-a3b-pp8
.json``; ISSUE 34 writes the equations out):

- blocks ``x + Attn(RMS(x))``, ``x + MoE(RMS(x))``, RMSNorm with a gain, a
  final RMSNorm, an untied output head, no bias but the indexer's LayerNorm;
  every layer is an expert layer;
- grouped-query attention: ``num_attention_heads`` query heads over
  ``num_key_value_heads`` key/value heads of ``head_dim`` (query head ``h``
  reads key head ``h // group``), an RMSNorm with a gain over each head of
  q and of k, rotary over the whole head in half-split pairs ``(j, j +
  head_dim / 2)``, softmax scale ``head_dim^-1/2``;
- three position streams (``rope_scaling.mrope_section``): rotary frequency
  ``j`` of the attention heads takes its position from stream 0 for the
  first ``section[0]`` frequencies, stream 1 for the next ``section[1]``,
  stream 2 for the rest; for text the three are the token's position;
- the indexer (``sa_config``): ``q_I = W x`` in ``indexer_num_heads`` heads
  of ``indexer_head_dim`` from the layer's normed input, one key ``k_I =
  LN(W x)`` a token, both rotated over the whole index head (half-split,
  by stream 0), ``I[t, s] = sum_h w[t, h] relu(q_I[t, h] . k_I[s])``; a
  query attends the exact top ``min(topk, t + 1)`` earlier keys of ``I[t,
  .]`` (ties to the lower position), every other score is -inf;
- routing: softmax over all experts in float32, the ``num_experts_per_tok``
  highest (ties to the lower index), weights renormalised over the chosen;
  gated SiLU experts, no shared expert, no bias, no scale; dropless.

Departures, each stated in the file:

- **the chip's share**: of the router's ``published.num_experts`` outputs,
  experts ``assumed.first_held_expert ..`` + ``num_experts`` are held (all
  of them in the benchmark's configuration); the routed sum runs over those
  alone, and what absent ones would add is left out;
- attention one block of queries at a time, expert layers one block of
  tokens at a time, experts upcast one at a time: so that 16 896 positions
  fit beside the weights;
- the vision tower is not held: inputs are token ids.

``lowp`` is the control of the comparison that decides ``correct``: a
function applied to both operands of every matrix product. ``None`` is the
reference itself.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 128      # queries whose scores are held at once
TOKEN_BLOCK = 2048     # tokens whose feed-forward states are held at once


def sizes(cfg: dict) -> dict:
    """The sizes the equations use, from the file's keys."""
    sa = cfg["sa_config"]
    return {
        "d": int(cfg["hidden_size"]), "layers": int(cfg["num_hidden_layers"]),
        "expert": int(cfg["moe_intermediate_size"]),
        "heads": int(cfg["num_attention_heads"]),
        "kv_heads": int(cfg["num_key_value_heads"]),
        "head_dim": int(cfg["head_dim"]),
        "index_heads": int(sa["indexer_num_heads"]),
        "index_dim": int(sa["indexer_head_dim"]),
        "index_topk": int(sa["topk"]),
        "experts": int(cfg["published"]["num_experts"]),
        "held_first": int(cfg["assumed"]["first_held_expert"]),
        "held": int(cfg["num_experts"]),
        "per_token": int(cfg["num_experts_per_tok"]),
        "rows": int(cfg["vocab_size"]), "eps": float(cfg["rms_norm_eps"]),
        "index_eps": float(cfg["assumed"]["index_layernorm_epsilon"]),
        "theta": float(cfg["rope_theta"]),
        "sections": tuple(int(n)
                          for n in cfg["rope_scaling"]["mrope_section"]),
    }


def param_shapes(cfg: dict) -> dict:
    s = sizes(cfg)
    d, h, kvh, hd = s["d"], s["heads"], s["kv_heads"], s["head_dim"]
    out = {"tok_embed": (s["rows"], d), "norm_f/scale": (d,),
           "lm_head": (d, s["rows"])}
    for i in range(s["layers"]):
        a, f = f"layer{i}/attn", f"layer{i}/ffn"
        out.update({
            f"layer{i}/attn_norm/scale": (d,),
            f"layer{i}/ffn_norm/scale": (d,),
            f"{a}/wq": (d, h, hd), f"{a}/wk": (d, kvh, hd),
            f"{a}/wv": (d, kvh, hd), f"{a}/wo": (h, hd, d),
            f"{a}/q_norm/scale": (hd,), f"{a}/k_norm/scale": (hd,),
            f"{a}/index_wq": (d, s["index_heads"], s["index_dim"]),
            f"{a}/index_wk": (d, s["index_dim"]),
            f"{a}/index_k_norm/scale": (s["index_dim"],),
            f"{a}/index_k_norm/bias": (s["index_dim"],),
            f"{a}/index_weights": (d, s["index_heads"]),
            f"{f}/router": (d, s["experts"]),
            f"{f}/w1": (s["held"], d, s["expert"]),
            f"{f}/w3": (s["held"], d, s["expert"]),
            f"{f}/w2": (s["held"], s["expert"], d)})
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


def _frequencies(dim: int, theta: float) -> np.ndarray:
    return (theta ** (-2.0 * np.arange(dim // 2, dtype=np.float64)
                      / dim)).astype(np.float32)


def _rotate_half_split(x, cos, sin):
    a, b = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)


def select_keys(scores, k: int):
    """Boolean ``[Q, S]``: the ``k`` highest entries of each row of
    ``scores`` that are not -inf (ties to the lower index)."""
    q, n = scores.shape
    # + 0.0: a negative zero is a zero (top_k orders -0.0 under +0.0)
    top, chosen = jax.lax.top_k(scores + 0.0, min(k, n))
    return jnp.zeros((q, n), bool).at[jnp.arange(q)[:, None], chosen].set(
        top > -jnp.inf)


def attention(x, p, cfg: dict, lowp=None, positions=None):
    """``Attn(x)`` for one sequence ``x`` [T, d] (already normed); ``p`` this
    layer's attention leaves by short name; ``positions`` [3, T] the three
    position streams (None: all three are 0..T-1)."""
    s = sizes(cfg)
    t = x.shape[0]
    kvh, hd = s["kv_heads"], s["head_dim"]
    group = s["heads"] // kvh
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(t), (3, t))
    positions = positions.astype(jnp.float32)
    # frequency j of an attention head turns by the stream of its section
    stream = np.repeat(np.arange(3), s["sections"])
    angles = positions[stream].T * jnp.asarray(_frequencies(hd, s["theta"]))
    cos, sin = jnp.cos(angles), jnp.sin(angles)               # [T, hd/2]
    # the index head turns by the temporal stream
    angles_i = positions[0][:, None] \
        * jnp.asarray(_frequencies(s["index_dim"], s["theta"]))
    cos_i, sin_i = jnp.cos(angles_i), jnp.sin(angles_i)

    k = _rms(_mm("td,dhe->the", x, p["wk"], lowp), p["k_norm/scale"],
             s["eps"])
    k = _rotate_half_split(k, cos[:, None], sin[:, None])     # [T, KVH, hd]
    v = _mm("td,dhe->the", x, p["wv"], lowp)
    k_i = _mm("td,dc->tc", x, p["index_wk"], lowp)
    mu = k_i.mean(-1, keepdims=True)
    var = ((k_i - mu) ** 2).mean(-1, keepdims=True)
    k_i = (k_i - mu) * jax.lax.rsqrt(var + s["index_eps"]) \
        * p["index_k_norm/scale"].astype(jnp.float32) \
        + p["index_k_norm/bias"].astype(jnp.float32)
    k_i = _rotate_half_split(k_i, cos_i, sin_i)
    key_pos = jnp.arange(t)

    def block(args):
        xb, cb, sb, cib, sib, pos = args
        q = _rms(_mm("td,dhe->the", xb, p["wq"], lowp), p["q_norm/scale"],
                 s["eps"])
        q = _rotate_half_split(q, cb[:, None], sb[:, None])
        q = q.reshape(-1, kvh, group, hd)
        q_i = _rotate_half_split(_mm("td,dhe->the", xb, p["index_wq"], lowp),
                                 cib[:, None], sib[:, None])
        w_i = _mm("td,dh->th", xb, p["index_weights"], lowp) \
            * (s["index_heads"] ** -0.5 * s["index_dim"] ** -0.5)
        index = (jax.nn.relu(_mm("thd,sd->ths", q_i, k_i, lowp))
                 * w_i[..., None]).sum(1)                      # [Q, T]
        causal = key_pos[None, :] <= pos[:, None]
        keep = select_keys(jnp.where(causal, index, -jnp.inf),
                           s["index_topk"])
        a = _mm("tkgd,skd->kgts", q, k, lowp) * hd ** -0.5
        a = jax.nn.softmax(jnp.where(keep[None, None], a, -jnp.inf), axis=-1)
        o = _mm("kgts,skd->tkgd", a, v, lowp)
        return _mm("thv,hvd->td", o.reshape(-1, s["heads"], hd), p["wo"],
                   lowp)

    size = min(QUERY_BLOCK, t)
    pad = -t % size
    padded = [jnp.pad(a, [(0, pad)] + [(0, 0)] * (a.ndim - 1)).reshape(
        -1, size, *a.shape[1:])
        for a in (x, cos, sin, cos_i, sin_i, key_pos)]
    out = jax.lax.map(block, tuple(padded))
    return out.reshape(-1, x.shape[-1])[:t]


def route(x, p, cfg: dict, lowp=None):
    """``(experts [T, k], weights [T, k])`` over ALL routed experts."""
    s = sizes(cfg)
    prob = jax.nn.softmax(_mm("td,de->te", x, p["router"], lowp), axis=-1)
    w, experts = jax.lax.top_k(prob, s["per_token"])
    return experts, w / w.sum(-1, keepdims=True)


def _ffn(x, w1, w3, w2, lowp):
    def rows(xb):
        hid = jax.nn.silu(_mm("td,df->tf", xb, w1, lowp)) \
            * _mm("td,df->tf", xb, w3, lowp)
        return _mm("tf,fd->td", hid, w2, lowp)

    return _in_blocks(rows, x, TOKEN_BLOCK)


def expert_layer(x, p, cfg: dict, lowp=None):
    """The held experts' part of ``sum_i w_i E_i(x)`` for ``x`` [T, d]."""
    s = sizes(cfg)
    experts, weights = route(x, p, cfg, lowp)

    def one(acc, args):
        e, w1, w3, w2 = args
        w = jnp.where(experts == e, weights, 0.0).sum(-1)     # [T]
        return acc + w[:, None] * _ffn(x, w1, w3, w2, lowp), None

    held = s["held_first"] + jnp.arange(s["held"])
    out, _ = jax.lax.scan(one, jnp.zeros_like(x),
                          (held, p["w1"], p["w3"], p["w2"]))
    return out


def _under(params: dict, prefix: str) -> dict:
    return {k[len(prefix):]: v for k, v in params.items()
            if k.startswith(prefix)}


def _hidden_one(params: dict, tokens, cfg: dict, lowp, positions):
    s = sizes(cfg)
    x = params["tok_embed"][tokens].astype(jnp.float32)
    for i in range(s["layers"]):
        p = _under(params, f"layer{i}/")
        x = x + attention(_rms(x, p["attn_norm/scale"], s["eps"]),
                          _under(p, "attn/"), cfg, lowp, positions)
        x = x + expert_layer(_rms(x, p["ffn_norm/scale"], s["eps"]),
                             _under(p, "ffn/"), cfg, lowp)
    return _rms(x, params["norm_f/scale"], s["eps"])


def hidden(params: dict, tokens, cfg: dict, lowp=None, positions=None):
    """The final, normed states ``[B, T, width]`` in float32 for int tokens
    ``[B, T]``: everything but the output head. ``params`` is ``{path:
    array}`` as ``param_shapes`` names them, in any float type: each leaf is
    upcast where it is used. ``positions`` [3, B, T] are the three position
    streams (None: text, each token's own position in all three)."""
    return jnp.stack([
        _hidden_one(params, row, cfg, lowp,
                    None if positions is None else positions[:, i])
        for i, row in enumerate(tokens)])


def head(params: dict, x, cfg: dict, lowp=None):
    """Logits ``[..., vocab_size]`` of states ``[..., width]``."""
    return _mm("...m,mv->...v", x, params["lm_head"], lowp)


def forward(params: dict, tokens, cfg: dict, lowp=None, positions=None):
    """Logits ``[B, T, vocab_size]`` in float32 for int tokens ``[B, T]``."""
    return head(params, hidden(params, tokens, cfg, lowp, positions), cfg,
                lowp)
