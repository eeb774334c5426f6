"""The decode step of Kimi Delta Attention on the chip: the Pallas kernel
``ops/kda_decode.py::kda_decode`` against the same step in XLA
(``kda_step`` over every slot, the state kept where a row is not live),
at one KDA layer of the serving cell's shapes. Both are first checked
against each other (the compiled kernel, not interpret mode), then timed
with the state donated, so that each updates it in place.

Needs a TPU: with none it exits non-zero and times nothing.

    python tools/kda_decode_bench.py [--slots 128] [--heads 32] [--live 128,64]

Prints one JSON line per (form, live slots): ms a call (median of
``--repeats`` bursts of ``--calls`` calls), and the bytes the kernel's
least moves (the state read and written once per live slot) over that
time.
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

from distributed_training_tpu.ops.kda_decode import (  # noqa: E402
    kda_decode,
    kda_step,
)
from distributed_training_tpu.runtime.backend import (  # noqa: E402
    enable_compile_cache,
    require_tpu,
)


def inputs(slots: int, heads: int, dim: int, seed: int = 0):
    ks = jax.random.split(jax.random.key(seed), 6)
    q = jax.random.normal(ks[0], (slots, heads, dim), jnp.float32)
    k = jax.random.normal(ks[1], (slots, heads, dim), jnp.float32)
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (slots, heads, dim), jnp.float32)
    g = -5.0 * jax.nn.sigmoid(jax.random.normal(ks[3], (slots, heads, dim)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (slots, heads)))
    state = 0.1 * jax.random.normal(ks[5], (slots, heads, dim, dim))
    return q, k, v, g, beta, state


@jax.jit
def xla_form(q, k, v, g, beta, state, live):
    o, s = kda_step(q, k, v, g, beta, state)
    keep = live[:, None, None, None]
    return jnp.where(live[:, None, None], o, 0.0), jnp.where(keep, s, state)


@jax.jit
def kernel_form(q, k, v, g, beta, state, live):
    slots = jnp.arange(state.shape[0], dtype=jnp.int32)
    return kda_decode(q, k, v, g, beta, state, slots, live)


def time_form(fn, args, state, calls: int, repeats: int) -> float:
    step = jax.jit(fn, donate_argnums=(5,))
    q, k, v, g, beta, live = args
    _, state = step(q, k, v, g, beta, state, live)      # compile, warm
    jax.block_until_ready(state)
    per_call = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        for _ in range(calls):
            _, state = step(q, k, v, g, beta, state, live)
        jax.block_until_ready(state)
        per_call.append((time.perf_counter() - t0) / calls * 1e3)
    return statistics.median(per_call)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--slots", type=int, default=128)
    ap.add_argument("--heads", type=int, default=32)
    ap.add_argument("--dim", type=int, default=128)
    ap.add_argument("--live", default="128,64")
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--repeats", type=int, default=7)
    args = ap.parse_args(argv)
    device = require_tpu("kda_decode_bench")
    enable_compile_cache()
    q, k, v, g, beta, state = inputs(args.slots, args.heads, args.dim)
    failed = False
    for n_live in (int(x) for x in args.live.split(",")):
        live = jnp.arange(args.slots) < n_live
        o_k, s_k = kernel_form(q, k, v, g, beta, state, live)
        o_x, s_x = xla_form(q, k, v, g, beta, state, live)
        err = max(float(jnp.abs(o_k - o_x).max()),
                  float(jnp.abs(s_k - s_x).max()))
        failed |= not np.isfinite(err) or err > 1e-4
        least = n_live * 2 * args.heads * args.dim ** 2 * 4
        for name, fn in (("kernel", kernel_form), ("xla", xla_form)):
            ms = time_form(fn, (q, k, v, g, beta, live), jnp.copy(state),
                           args.calls, args.repeats)
            print(json.dumps({
                "form": name, "slots": args.slots, "live": n_live,
                "heads": args.heads, "dim": args.dim, "ms": ms,
                "state_gb_per_s": least / ms / 1e6, "max_abs_diff": err,
                "device": device["kind"]}), flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
