"""The held experts' part of an expert layer on the chip: the grouped
SwiGLU kernel ``ops/expert_ffn.py::expert_ffn`` against the forms it was
chosen over, at the four serving families' decode and chunk passes.

Forms, all over the same (token, expert) pairs sorted by held expert:

- ``walk128``: ``expert_walk`` in blocks of 128 rows (the layer before the
  kernel);
- ``walk_tile``: the same walk in blocks of the pass's row tile
  (``expert_ffn.row_tile``), the control;
- ``kernel``: ``expert_ffn``;
- ``ragged_dot``: ``jax.lax.ragged_dot`` over the pairs' rows gathered in
  sorted order (every pair's row, held or not), un-sorted and summed over
  ``top_k`` afterwards;
- ``gmm``: the same with ``jax.experimental.pallas.ops.tpu.megablox.gmm``.

Routing is uniform: each token picks ``top_k`` distinct experts of the
router's, and the chip holds the first ``held`` of them. Every form is
first checked against the kernel (compiled, not interpret mode), then
timed in bursts of calls.

Needs a TPU: with none it exits non-zero and times nothing.

    python tools/expert_ffn_bench.py [--passes ling.decode,keye.chunk]

Prints one JSON line per (pass, form): ms a call (median of ``--repeats``
bursts of ``--calls`` calls) and the hit experts' weights read once over
that time.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from distributed_training_tpu.ops import expert_ffn as ef  # noqa: E402
from distributed_training_tpu.runtime.backend import (  # noqa: E402
    enable_compile_cache,
    require_tpu,
)

# pass -> (tokens, hidden size, expert width, held experts, router's
# experts, experts a token): the serving cells' shapes
PASSES = {
    "ling.decode": (128, 2560, 768, 64, 512, 8),
    "ling.chunk": (1024, 2560, 768, 64, 512, 8),
    "keye.decode": (16, 2048, 768, 128, 128, 8),
    "keye.chunk": (1024, 2048, 768, 128, 128, 8),
    "sarvam.decode": (64, 4096, 2048, 32, 128, 8),
    "sarvam.chunk": (1024, 4096, 2048, 32, 128, 8),
    "dsv32.decode": (16, 7168, 2048, 16, 256, 8),
    "dsv32.chunk": (1024, 7168, 2048, 16, 256, 8),
}


def pairs(t: int, held: int, router: int, top_k: int, seed: int):
    """Uniform routing of ``t`` tokens, sorted by held expert as the layer
    sorts it: ``(pair_token, pair_weight, rows, starts, inverse)``."""
    ks = jax.random.split(jax.random.key(seed), 2)
    experts = jnp.argsort(jax.random.uniform(ks[0], (t, router)),
                          axis=-1)[:, :top_k]
    w = jax.random.uniform(ks[1], (t, top_k), minval=0.1)
    w = w / w.sum(-1, keepdims=True)
    local = jnp.where(experts < held, experts, held).reshape(-1)
    rows = (local[:, None] == jnp.arange(held)[None, :]).sum(
        0, dtype=jnp.int32)
    order = jnp.argsort(local, stable=True)
    return ((order // top_k).astype(jnp.int32), w.reshape(-1)[order], rows,
            jnp.cumsum(rows) - rows, jnp.argsort(order))


def _tiling(k: int, n: int) -> tuple:
    def widest(x):
        return next(s for s in (512, 256, 128) if x % s == 0)
    return (128, widest(k), widest(n))


def grouped(product, x, w1, w3, w2, token, weight, rows, inverse):
    """A form that multiplies the sorted pairs' rows by ``product(lhs,
    rhs, sizes)``: every pair's row gathered, the sum taken back to the
    tokens by un-sorting."""
    t, d = x.shape
    p = token.shape[0]
    xs = x[token]
    live = jnp.arange(p) < rows.sum()
    a = product(xs, w1, rows)
    b = product(xs, w3, rows)
    h = (jax.nn.silu(a) * b).astype(x.dtype)
    y = product(h, w2, rows)
    y = jnp.where(live[:, None], y * weight[:, None], 0.0)
    return y[inverse].reshape(t, p // t, d).sum(1)


def ragged(lhs, rhs, sizes):
    return jax.lax.ragged_dot(lhs, rhs, sizes,
                              preferred_element_type=jnp.float32)


def megablox(lhs, rhs, sizes):
    from jax.experimental.pallas.ops.tpu.megablox import gmm
    rest = lhs.shape[0] - sizes.sum()
    return gmm(lhs, rhs, jnp.concatenate([sizes, rest[None]]),
               preferred_element_type=jnp.float32,
               tiling=_tiling(*rhs.shape[1:]))


def forms(tile: int) -> dict:
    def walk(rows_):
        return lambda x, w1, w3, w2, tok, wt, rows, starts, inv: \
            ef.expert_walk(x, w1, w3, w2, tok, wt, rows, starts,
                           block_rows=rows_)

    def kernel(x, w1, w3, w2, tok, wt, rows, starts, inv):
        return ef.expert_ffn(x, w1, w3, w2, tok, wt, rows, starts,
                             row_tile=tile)

    def of(product):
        return lambda x, w1, w3, w2, tok, wt, rows, starts, inv: grouped(
            product, x, w1, w3, w2, tok, wt, rows, inv)

    return {"walk128": walk(128), "walk_tile": walk(tile), "kernel": kernel,
            "ragged_dot": of(ragged), "gmm": of(megablox)}


def time_form(fn, args, calls: int, repeats: int) -> float:
    jax.block_until_ready(fn(*args))
    per_call = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        for _ in range(calls):
            out = fn(*args)
        jax.block_until_ready(out)
        per_call.append((time.perf_counter() - t0) / calls * 1e3)
    return statistics.median(per_call)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--passes", default=",".join(PASSES))
    ap.add_argument("--forms", default="walk128,walk_tile,kernel,"
                                       "ragged_dot,gmm")
    ap.add_argument("--calls", type=int, default=10)
    ap.add_argument("--repeats", type=int, default=5)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    device = require_tpu("expert_ffn_bench")
    enable_compile_cache()
    failed = False
    for name in args.passes.split(","):
        t, d, h, held, router, top_k = PASSES[name]
        tile = ef.row_tile(t, top_k, router)
        ks = jax.random.split(jax.random.key(args.seed + 1), 4)
        x = jax.random.normal(ks[0], (t, d), jnp.bfloat16)
        w1, w3 = (0.02 * jax.random.normal(k, (held, d, h), jnp.bfloat16)
                  for k in ks[1:3])
        w2 = 0.02 * jax.random.normal(ks[3], (held, h, d), jnp.bfloat16)
        tok, wt, rows, starts, inv = pairs(t, held, router, top_k,
                                           args.seed)
        call = (x, w1, w3, w2, tok, wt, rows, starts, inv)
        hit = int((rows > 0).sum())
        least = hit * 3 * d * h * 2
        table = forms(tile)
        want = None
        for form in ["kernel"] + [f for f in args.forms.split(",")
                                  if f != "kernel"]:
            fn = jax.jit(table[form])
            line = {"pass": name, "form": form, "t": t, "d": d, "hidden": h,
                    "held": held, "row_tile": tile, "hit_experts": hit,
                    "pairs_held": int(rows.sum()), "device": device["kind"]}
            try:
                got = jax.block_until_ready(fn(*call))
            except Exception as e:  # a form the chip's compiler refuses
                print(json.dumps({**line, "error": str(e)[:400]}),
                      flush=True)
                continue
            if want is None:
                want = got
            diff = float(jnp.abs(got - want).max()
                         / jnp.maximum(jnp.abs(want).max(), 1e-30))
            failed |= not np.isfinite(diff) or diff > 2e-2
            ms = time_form(fn, call, args.calls, args.repeats)
            print(json.dumps({**line, "ms": ms,
                              "weights_gb_per_s": least / ms / 1e6,
                              "max_rel_diff": diff}), flush=True)
        del w1, w3, w2, want
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
