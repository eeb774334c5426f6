"""Weights from the seed, made on the device in one jitted call.

The benchmark makes the weights and hands them to the program and to the
plain reference alike; neither side makes its own. A leaf's values depend
on the seed and on the leaf's path alone (not on the tree's order or on a
sharding), so the reference can draw any leaf again later.
"""

from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp

INIT_STD = 0.02   # GPT-2's initializer range


def _leaf(key, path: str, shape, dtype):
    k = jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)
    x = INIT_STD * jax.random.normal(k, shape, jnp.float32)
    if path.endswith("/scale"):      # LayerNorm gains sit around one
        x = 1.0 + x
    return x.astype(dtype)


def seed_key(seed: int):
    """A key from any whole-number seed (the driver's exceed 32 bits)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


def make(seed: int, shapes: dict, dtype, out_shardings=None) -> dict:
    """``{path: array}`` for ``shapes = {path: shape}``; every matrix,
    table and bias normal(0, 0.02), every norm gain 1 + normal(0, 0.02)
    (biases are not zero, so that their gradients count). Drawn in float32
    and cast to ``dtype``, the type the weights are used in."""
    paths = sorted(shapes)

    def build(key):
        return {p: _leaf(key, p, tuple(shapes[p]), dtype) for p in paths}

    fn = jax.jit(build, out_shardings=out_shardings)
    return fn(seed_key(seed))


def flatten(tree, prefix: str = "") -> dict:
    """A nested dict of arrays as ``{"a/b/c": leaf}``."""
    out = {}
    for k, v in tree.items():
        p = f"{prefix}/{k}" if prefix else str(k)
        if hasattr(v, "items"):
            out.update(flatten(v, p))
        else:
            out[p] = v
    return out


def unflatten(flat: dict) -> dict:
    tree: dict = {}
    for path, v in flat.items():
        node = tree
        parts = path.split("/")
        for part in parts[:-1]:
            node = node.setdefault(part, {})
        node[parts[-1]] = v
    return tree
