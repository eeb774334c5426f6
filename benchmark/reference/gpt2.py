"""GPT-2 in plain ``jax.numpy`` and float32: forward, loss, gradients and
Adam, with no kernel, no cache and no batching tricks.

Follows Radford et al. 2019 as the configuration file states it: pre-norm
blocks, learned positions, tanh-GELU, causal softmax attention scaled by
1/sqrt(head size). Departures of the configuration as run from the
published one (``assumed`` in the file): an untied, bias-free output head
over ``padded_vocab_size`` rows, and LayerNorm epsilon as the file gives
it. Parameter names and shapes are the layout the weights are made in
(``param_shapes``); the program is handed the same tree.

``lowp`` is the control of the comparison that decides ``correct``: a
function applied to both operands of every matrix product (rounding them
to a lower precision). ``None`` is the reference itself.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def param_shapes(cfg: dict) -> dict:
    d = int(cfg["n_embd"])
    h = int(cfg["n_head"])
    hd = d // h
    m = int(cfg.get("n_inner") or 4 * d)
    rows = int(cfg["assumed"]["padded_vocab_size"])
    s = {"tok_embed/embedding": (rows, d),
         "pos_embed": (int(cfg["n_positions"]), d),
         "ln_f/scale": (d,), "ln_f/bias": (d,),
         "lm_head/kernel": (d, rows)}
    for i in range(int(cfg["n_layer"])):
        b = f"block{i}"
        s.update({
            f"{b}/ln1/scale": (d,), f"{b}/ln1/bias": (d,),
            f"{b}/attn/qkv/kernel": (d, 3, h, hd),
            f"{b}/attn/qkv/bias": (3, h, hd),
            f"{b}/attn/out/kernel": (h, hd, d), f"{b}/attn/out/bias": (d,),
            f"{b}/ln2/scale": (d,), f"{b}/ln2/bias": (d,),
            f"{b}/mlp/fc1/kernel": (d, m), f"{b}/mlp/fc1/bias": (m,),
            f"{b}/mlp/fc2/kernel": (m, d), f"{b}/mlp/fc2/bias": (d,)})
    return s


def fine_leaves(flat: dict) -> dict:
    """``{path: array}`` with the fused query/key/value leaves cut into
    their three parameters (``.../qkv/bias[k]``): they are three things, and
    a key's bias has no gradient under softmax, so it is judged apart."""
    out = {}
    for path, a in flat.items():
        if path.endswith("attn/qkv/kernel"):
            for i, part in enumerate("qkv"):
                out[f"{path}[{part}]"] = a[:, i]
        elif path.endswith("attn/qkv/bias"):
            for i, part in enumerate("qkv"):
                out[f"{path}[{part}]"] = a[i]
        else:
            out[path] = a
    return out


@functools.lru_cache(maxsize=None)
def round_to(dtype):
    """A ``lowp``: the operand rounded to ``dtype``, in float32. Gradients
    pass straight through the rounding (they are not themselves rounded: a
    float8 cotangent without scaling would underflow to nought)."""
    def f(x):
        r = x.astype(dtype).astype(jnp.float32)
        return x + jax.lax.stop_gradient(r - x)
    return f


def _mm(spec, a, b, lowp):
    if lowp is not None:
        a, b = lowp(a), lowp(b)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _ln(x, scale, bias, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * scale + bias


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        0.7978845608028654 * (x + 0.044715 * x ** 3)))


def _block(x, p, eps, lowp):
    """One decoder block; ``p`` holds this block's leaves by short name."""
    t = x.shape[1]
    hd = p["attn/qkv/kernel"].shape[-1]
    y = _ln(x, p["ln1/scale"], p["ln1/bias"], eps)
    qkv = _mm("btm,mshd->sbhtd", y, p["attn/qkv/kernel"], lowp) \
        + p["attn/qkv/bias"][:, None, :, None, :]
    q, k, v = qkv[0], qkv[1], qkv[2]
    s = _mm("bhqd,bhkd->bhqk", q, k, lowp) / jnp.sqrt(jnp.float32(hd))
    mask = jnp.tril(jnp.ones((t, t), bool))
    s = jnp.where(mask, s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1)
    o = _mm("bhqk,bhkd->bhqd", a, v, lowp)
    x = x + _mm("bhtd,hdm->btm", o, p["attn/out/kernel"], lowp) \
        + p["attn/out/bias"]
    y = _ln(x, p["ln2/scale"], p["ln2/bias"], eps)
    y = _gelu(_mm("btm,mf->btf", y, p["mlp/fc1/kernel"], lowp)
              + p["mlp/fc1/bias"])
    return x + _mm("btf,fm->btm", y, p["mlp/fc2/kernel"], lowp) \
        + p["mlp/fc2/bias"]


def _stack_blocks(params: dict, n_layer: int) -> dict:
    names = [k[len("block0/"):] for k in params if k.startswith("block0/")]
    return {n: jnp.stack([params[f"block{i}/{n}"] for i in range(n_layer)])
            for n in names}


def hidden(params: dict, tokens, cfg: dict, lowp=None):
    """The final, normed states ``[B, T, width]`` in float32 for int tokens
    ``[B, T]``: everything but the output head. ``params`` is ``{path:
    array}`` as ``param_shapes`` names them (any float type; computed in
    float32)."""
    p = {k: v.astype(jnp.float32) for k, v in params.items()
         if k != "lm_head/kernel"}
    eps = float(cfg["layer_norm_epsilon"])
    t = tokens.shape[1]
    x = p["tok_embed/embedding"][tokens] + p["pos_embed"][:t][None]
    stacked = _stack_blocks(p, int(cfg["n_layer"]))

    @jax.checkpoint
    def body(x, blk):
        return _block(x, blk, eps, lowp), None

    x, _ = jax.lax.scan(body, x, stacked)
    return _ln(x, p["ln_f/scale"], p["ln_f/bias"], eps)


def head(params: dict, x, cfg: dict, lowp=None):
    """Logits ``[..., padded_vocab_size]`` of states ``[..., width]`` that
    :func:`hidden` gave: any block of positions at a time."""
    return _mm("...m,mv->...v", x,
               params["lm_head/kernel"].astype(jnp.float32), lowp)


def forward(params: dict, tokens, cfg: dict, lowp=None):
    """Logits ``[B, T, padded_vocab_size]`` in float32 for int tokens
    ``[B, T]``."""
    return head(params, hidden(params, tokens, cfg, lowp), cfg, lowp)


def loss(params: dict, tokens, targets, cfg: dict, lowp=None):
    """Mean next-token cross-entropy over all positions of all rows."""
    logits = forward(params, tokens, cfg, lowp)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, targets[..., None], -1)[..., 0]
    return (lse - picked).mean()


def loss_and_grads(params: dict, tokens, targets, cfg: dict, lowp=None,
                   block_rows: int = 4, rows=None):
    """Loss and gradients of the mean over ``rows`` (default: all rows),
    accumulated over blocks of ``block_rows`` rows so that it fits."""
    n = tokens.shape[0] if rows is None else int(rows)
    fn = _block_grad_fn(_freeze(cfg), lowp)
    total, grads = 0.0, None
    for lo in range(0, n, block_rows):
        hi = min(lo + block_rows, n)
        l, g = fn(params, tokens[lo:hi], targets[lo:hi])
        w = (hi - lo) / n
        total = total + w * l
        grads = (jax.tree.map(lambda a: w * a, g) if grads is None else
                 jax.tree.map(lambda acc, a: acc + w * a, grads, g))
    return total, grads


def _freeze(cfg: dict):
    return (int(cfg["n_layer"]), float(cfg["layer_norm_epsilon"]))


@functools.lru_cache(maxsize=8)
def _block_grad_fn(frozen, lowp):
    cfg = {"n_layer": frozen[0], "layer_norm_epsilon": frozen[1]}
    return jax.jit(jax.value_and_grad(
        lambda p, tok, tgt: loss(p, tok, tgt, cfg, lowp)))


# Adam as optax.adam(1e-3) computes it: b1 0.9, b2 0.999, eps 1e-8 outside
# the root, bias-corrected, no weight decay, no clipping, constant rate.
ADAM = {"lr": 1e-3, "b1": 0.9, "b2": 0.999, "eps": 1e-8}


@jax.jit
def adam_update(params, grads, mu, nu, count):
    b1, b2, lr, eps = ADAM["b1"], ADAM["b2"], ADAM["lr"], ADAM["eps"]
    count = count + 1
    mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
    nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, nu, grads)
    c1 = 1 - b1 ** count
    c2 = 1 - b2 ** count
    params = jax.tree.map(
        lambda p, m, v: p - lr * (m / c1) / (jnp.sqrt(v / c2) + eps),
        params, mu, nu)
    return params, mu, nu, count


def train_steps(params: dict, batches, cfg: dict, lowp=None,
                block_rows: int = 4, rows=None, skip_update: bool = False):
    """Follow ``batches`` (each ``{"tokens", "targets"}``) from ``params``.

    Returns ``{"losses": [...], "grad_norms": {path: norm of the FIRST
    step's gradient}, "change_norms": {path: norm of (params after the last
    step - params at the start)}}``, paths as :func:`fine_leaves` cuts them. ``rows`` and ``skip_update`` plant the
    faults the comparison has to catch (half of the batch left out; a step
    that returns its state unchanged)."""
    p0 = params
    p = params
    mu = jax.tree.map(jnp.zeros_like, params)
    nu = jax.tree.map(jnp.zeros_like, params)
    count = jnp.zeros((), jnp.float32)
    losses, grad_norms = [], None
    for b in batches:
        l, g = loss_and_grads(p, b["tokens"], b["targets"], cfg, lowp,
                              block_rows, rows)
        losses.append(float(l))
        if grad_norms is None:
            grad_norms = {k: float(jnp.linalg.norm(v.ravel()))
                          for k, v in fine_leaves(g).items()}
        if not skip_update:
            p, mu, nu, count = adam_update(p, g, mu, nu, count)
    change = {k: float(jnp.linalg.norm(v.ravel())) for k, v in fine_leaves(
        {k: p[k] - p0[k] for k in p0}).items()}
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": change}
