"""Probe: can a Pallas conv3x3 with a fused BN-stats epilogue beat
XLA's conv + separate stats pass?

The committed R50 profile (profiles/r50_b256.json) shows 13.6 ms/step of
loop fusions (BN stat reductions, BN-apply/ReLU chains, residual adds) at
~92% of HBM peak beside 79.4 ms of conv fusions at ~85% — both at the
bandwidth bound, so the only winnable bytes are PASSES REMOVED, not
faster math. A conv kernel that emits its own channel sum/sum-of-squares
while the output tile is still in VMEM deletes the stats re-read of the
conv output (one full activation pass per conv). This probe measures that
hypothesis at ResNet-50 stage shapes before any integration:

    python tools/conv_fusion_probe.py                # all shapes
    python tools/conv_fusion_probe.py --shapes s0 s1

Per shape it times (20 iters, host-fetch barrier):
  xla_conv        — lax.conv alone (floor)
  xla_conv_stats  — conv + mean/var reduction (the graph being replaced)
  pallas_fused    — the Pallas kernel emitting out + sum + sumsq
and checks the kernel against the XLA oracle first.

Kernel design: input pre-padded NHWC (padding is done once by XLA and is
reused by every (dy,dx) tap), grid over batch; per program the 3x3 conv
is 9 shifted [H*W, Cin] x [Cin, Cout] MXU matmuls accumulated in fp32
VMEM, stats accumulate per-program partials that XLA sums outside (same
partial-accumulation layout as the flash backward's dq).
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# (label, N, H, W, Cin, Cout) — ResNet-50 3x3 conv shapes at batch 256.
SHAPES = {
    "s0": ("stage0 3x3", 256, 56, 56, 64, 64),
    "s1": ("stage1 3x3", 256, 28, 28, 128, 128),
    "s2": ("stage2 3x3", 256, 14, 14, 256, 256),
}


def _conv_kernel(x_ref, w_ref, o_ref, s_ref, ss_ref, acc, *, h, w, cin, cout,
                 bn):
    """One batch-block: out = conv3x3(x), plus per-program channel
    sum/sumsq partials of the output."""
    for n in range(bn):
        acc[:] = jnp.zeros_like(acc)
        for dy in range(3):
            for dx in range(3):
                xs = x_ref[n, dy:dy + h, dx:dx + w, :].reshape(h * w, cin)
                acc[:] += jax.lax.dot_general(
                    xs, w_ref[dy, dx], (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
        o_ref[n] = acc[:].reshape(h, w, cout).astype(o_ref.dtype)
        started = jnp.float32(n > 0)
        s_ref[0] = s_ref[0] * started + jnp.sum(acc[:], axis=0, keepdims=True)
        ss_ref[0] = ss_ref[0] * started + jnp.sum(acc[:] * acc[:], axis=0,
                                                  keepdims=True)


def pallas_conv3x3_stats(x, w, *, bn=1):
    """x [N,H,W,Cin] (unpadded), w [3,3,Cin,Cout] ->
    (out [N,H,W,Cout], sum [Cout], sumsq [Cout])."""
    n, h, wd, cin = x.shape
    cout = w.shape[-1]
    xp = jnp.pad(x, ((0, 0), (1, 1), (1, 1), (0, 0)))
    grid = (n // bn,)
    out, s, ss = pl.pallas_call(
        functools.partial(_conv_kernel, h=h, w=wd, cin=cin, cout=cout, bn=bn),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, h + 2, wd + 2, cin), lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((3, 3, cin, cout), lambda i: (0, 0, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bn, h, wd, cout), lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((1, 1, cout), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, 1, cout), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, h, wd, cout), x.dtype),
            jax.ShapeDtypeStruct((n // bn, 1, cout), jnp.float32),
            jax.ShapeDtypeStruct((n // bn, 1, cout), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((h * wd, cout), jnp.float32)],
    )(xp, w)
    return out, s.sum(axis=(0, 1)), ss.sum(axis=(0, 1))


def xla_conv(x, w):
    return jax.lax.conv_general_dilated(
        x, w, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"),
        preferred_element_type=jnp.float32).astype(x.dtype)


def xla_conv_stats(x, w):
    out = jax.lax.conv_general_dilated(
        x, w, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"),
        preferred_element_type=jnp.float32)
    s = jnp.sum(out, axis=(0, 1, 2))
    ss = jnp.sum(out * out, axis=(0, 1, 2))
    return out.astype(x.dtype), s, ss


def bench(fn, args, iters=20, warmup=3):
    jfn = jax.jit(fn)
    for _ in range(warmup):
        r = jfn(*args)
    jax.tree.map(lambda a: np.asarray(jax.tree.leaves(r)[-1][..., :1]), None)
    float(jnp.sum(jax.tree.leaves(r)[-1]))
    t0 = time.perf_counter()
    for _ in range(iters):
        r = jfn(*args)
    float(jnp.sum(jax.tree.leaves(r)[-1]))
    return (time.perf_counter() - t0) / iters * 1e3


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", nargs="+", default=list(SHAPES),
                    choices=list(SHAPES))
    ap.add_argument("--bn", type=int, default=1, help="batch block")
    ap.add_argument("--verify-only", action="store_true")
    args = ap.parse_args()

    from distributed_training_tpu.runtime.backend import (
        enable_compile_cache,
        require_tpu,
    )

    enable_compile_cache()
    device = require_tpu("conv_fusion_probe")
    print(f"platform: {device['platform']} ({device['kind']})",
          file=sys.stderr)

    for key in args.shapes:
        label, n, h, w, cin, cout = SHAPES[key]
        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.randn(n, h, w, cin), jnp.bfloat16)
        wts = jnp.asarray(rng.randn(3, 3, cin, cout) * 0.05, jnp.bfloat16)

        ref_out, ref_s, ref_ss = jax.jit(xla_conv_stats)(x, wts)
        got_out, got_s, got_ss = jax.jit(
            functools.partial(pallas_conv3x3_stats, bn=args.bn))(x, wts)
        np.testing.assert_allclose(
            np.asarray(got_out, np.float32), np.asarray(ref_out, np.float32),
            atol=0.5, rtol=5e-2)
        np.testing.assert_allclose(np.asarray(got_s), np.asarray(ref_s),
                                   rtol=2e-2, atol=n * h * w * 0.05)
        np.testing.assert_allclose(np.asarray(got_ss), np.asarray(ref_ss),
                                   rtol=2e-2)
        print(f"verify {key}: ok", file=sys.stderr)
        if args.verify_only:
            continue

        t_conv = bench(xla_conv, (x, wts))
        t_conv_stats = bench(xla_conv_stats, (x, wts))
        t_pallas = bench(functools.partial(
            pallas_conv3x3_stats, bn=args.bn), (x, wts))
        print(json.dumps({
            "shape": f"{label} [{n},{h},{w},{cin}]->{cout}",
            "xla_conv_ms": round(t_conv, 3),
            "xla_conv_stats_ms": round(t_conv_stats, 3),
            "pallas_fused_ms": round(t_pallas, 3),
            "fused_vs_conv_stats": round(t_conv_stats / t_pallas, 3),
        }))


if __name__ == "__main__":
    main()
