"""Pallas kernel microbenchmark + on-chip correctness check.

Times the flash-attention kernel (fwd+bwd through the custom VJP) on the
real device, and first verifies the COMPILED path (not interpret mode)
against exact attention — the Mosaic-acceptance check the CPU test suite
cannot provide (tests run in interpret mode; see ops/flash_attention.py
LSE_LANES note): a small multi-block case, then every backward variant
(fused, split, the (out, lse) hop primitive) at the bench shapes with
their auto blocks, then one fused-Adam call at a real parameter shape.
Needs a TPU: with none it exits non-zero and times nothing.

Usage:
    python tools/flash_kernel_bench.py            # verify + bench defaults
    python tools/flash_kernel_bench.py --no-verify --shapes gpt
    python tools/flash_kernel_bench.py --blocks 512x1024 ...

Prints one JSON line per verified (shape, variant) and one per benched
shape with ms per fwd+bwd call; exits non-zero if any verification failed.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from distributed_training_tpu.ops.flash_attention import (
    flash_attention,
    flash_attention_lse,
)
from distributed_training_tpu.runtime.backend import (
    enable_compile_cache,
    require_tpu,
)

# (label, bh, t, d) — bh = batch*heads flattened.
SHAPES = {
    "gpt": ("B16 H12 T1024 D64", 192, 1024, 64),
    "t4096": ("B4 H8 T4096 D64", 32, 4096, 64),
    "t16k": ("B2 H12 T16384 D64", 24, 16384, 64),
    # Iso-FLOP head-dim scaling probes (bh·d constant): if per-FLOP time is
    # flat from d=64 to d=128, the MXU's 128-wide contraction is NOT the
    # limiting resource at d=64 (the matmuls hide under the VPU softmax);
    # if d=128 is ~2x faster per FLOP, head-packing would pay.
    "gpt_d128": ("B16 H6 T1024 D128 (iso-FLOP probe)", 96, 1024, 128),
    "gpt_d32": ("B16 H24 T1024 D32 (iso-FLOP probe)", 384, 1024, 32),
}


def exact_attention(q, k, v, causal=True):
    s = jnp.einsum("...qd,...kd->...qk", q, k) / np.sqrt(q.shape[-1])
    if causal:
        t = q.shape[-2]
        s = jnp.where(jnp.triu(jnp.ones((t, t), bool), 1), -jnp.inf, s)
    p = jax.nn.softmax(s.astype(jnp.float32), -1)
    return jnp.einsum("...qk,...kd->...qd", p.astype(v.dtype), v)


def verify_compiled(flash_kwargs):
    """Compiled-kernel (Mosaic) correctness vs exact attention, fwd + grads.

    Two passes: the requested/default blocks (single-block grid at T=512),
    and an explicit 128x128 multi-block tiling (nq=nk=4) — the fused
    backward's partial-dq HBM accumulation, dead-tile zeroing, and
    cross-q dk/dv scratch only engage at nk>1, and interpret-mode CPU
    tests cannot stand in for Mosaic acceptance of that path.
    """
    rng = np.random.RandomState(0)
    q, k, v = (jnp.asarray(rng.randn(4, 512, 64), jnp.bfloat16)
               for _ in range(3))

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v).astype(jnp.float32) ** 2)

    ref_out = exact_attention(q, k, v)
    ref_g = jax.grad(loss(lambda q, k, v: exact_attention(q, k, v)),
                     argnums=(0, 1, 2))(q, k, v)
    multiblock = dict(block_q=128, block_k=128,
                      bwd_block_q=128, bwd_block_k=128)
    for label, kwargs in (("requested blocks", flash_kwargs),
                          ("multi-block 128x128", multiblock)):
        got_out = flash_attention(q, k, v, causal=True, **kwargs)
        np.testing.assert_allclose(
            np.asarray(got_out, np.float32), np.asarray(ref_out, np.float32),
            atol=2e-2, rtol=2e-2, err_msg=label)
        got_g = jax.grad(
            loss(lambda q, k, v: flash_attention(q, k, v, causal=True,
                                                 **kwargs)),
            argnums=(0, 1, 2))(q, k, v)
        for name, a, b in zip("qkv", ref_g, got_g):
            np.testing.assert_allclose(
                np.asarray(b, np.float32), np.asarray(a, np.float32),
                atol=2e-1, rtol=5e-2, err_msg=f"{label} d{name}")
        print(f"verify [{label}]: compiled fwd+bwd matches exact attention",
              file=sys.stderr)


def _rel_err(got, want) -> float:
    """max|got − want| over max|want| (fp32): one scale-free number per
    tensor, so one bf16 tolerance serves T=1024 and T=16384 alike."""
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want)) / (np.max(np.abs(want)) + 1e-30))


def verify_shape(label, bh, t, d, flash_kwargs, variant, tol=4e-2):
    """One backward variant, compiled at the FULL bench shape: every
    output finite, and batch·head row 0 within ``tol`` of exact attention
    (the [T, T] oracle only fits one row at T=16384; rows are independent
    grid steps of the same compiled kernel). ``variant``: ``fused`` (the
    model path), ``split`` (the two-kernel backward) or ``lse`` (the
    (out, lse) ring-hop primitive, lse cotangent live)."""
    import distributed_training_tpu.ops.flash_attention as fa

    rng = np.random.RandomState(0)
    q, k, v = (jnp.asarray(rng.randn(bh, t, d), jnp.bfloat16)
               for _ in range(3))

    def ref(q, k, v):
        s = jnp.einsum("...qd,...kd->...qk", q, k,
                       preferred_element_type=jnp.float32) / np.sqrt(d)
        s = jnp.where(jnp.triu(jnp.ones((t, t), bool), 1), -jnp.inf, s)
        lse = jax.nn.logsumexp(s, axis=-1)
        p = jnp.exp(s - lse[..., None])
        return jnp.einsum("...qk,...kd->...qd", p.astype(v.dtype), v), lse

    def got(q, k, v):
        if variant == "lse":
            return flash_attention_lse(q, k, v, causal=True, **flash_kwargs)
        return flash_attention(q, k, v, causal=True, **flash_kwargs), None

    def loss(fn):
        def f(q, k, v):
            out, lse = fn(q, k, v)
            total = jnp.sum(out.astype(jnp.float32) ** 2)
            if variant == "lse":
                total = total + jnp.sum(lse)
            return total, out
        return jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2), has_aux=True))

    fa._USE_SPLIT_BWD = variant == "split"
    try:
        (_, out), grads = loss(got)(q, k, v)
        (_, ref_out), ref_grads = loss(ref)(q[:1], k[:1], v[:1])
    finally:
        fa._USE_SPLIT_BWD = False
    errs = {"out": _rel_err(out[:1], ref_out)}
    for name, g, rg in zip(("dq", "dk", "dv"), grads, ref_grads):
        errs[name] = _rel_err(g[:1], rg)
    finite = all(bool(jnp.isfinite(x.astype(jnp.float32)).all())
                 for x in (out, *grads))
    ok = finite and all(e < tol for e in errs.values())
    print(json.dumps({
        "verify": label, "variant": variant, "ok": ok, "finite": finite,
        "rel_err": {n: round(e, 5) for n, e in errs.items()},
        "blocks": flash_kwargs or "auto"}), flush=True)
    return ok


def verify_fused_adam(shape=(768, 3072), tol=1e-5):
    """The fused-Adam kernel compiled at a real parameter shape (GPT-2-
    small's MLP fc1) against the same update in plain jnp."""
    from distributed_training_tpu.ops.fused_adam import (
        fused_adam_kernel_update,
    )

    rng = np.random.RandomState(0)
    p, g, m = (jnp.asarray(rng.randn(*shape), jnp.float32) for _ in range(3))
    v = jnp.asarray(rng.rand(*shape), jnp.float32)
    lr, step, b1, b2, eps = 3e-4, 7, 0.9, 0.999, 1e-8
    new_p, new_m, new_v = fused_adam_kernel_update(
        p, g, m, v, jnp.float32(lr), jnp.int32(step),
        b1=b1, b2=b2, eps=eps)
    ref_m = b1 * m + (1 - b1) * g
    ref_v = b2 * v + (1 - b2) * g * g
    ref_p = p - lr * (ref_m / (1 - b1 ** step)) / (
        jnp.sqrt(ref_v / (1 - b2 ** step)) + eps)
    errs = {"p": _rel_err(new_p, ref_p), "m": _rel_err(new_m, ref_m),
            "v": _rel_err(new_v, ref_v)}
    ok = all(e < tol for e in errs.values())
    print(json.dumps({"verify": f"fused_adam {shape}", "ok": ok,
                      "rel_err": errs}), flush=True)
    return ok


def bench_shape(label, bh, t, d, flash_kwargs, iters=20, warmup=3):
    rng = np.random.RandomState(0)
    q, k, v = (jnp.asarray(rng.randn(bh, t, d), jnp.bfloat16)
               for _ in range(3))

    @jax.jit
    def fwd_bwd(q, k, v):
        def loss(q, k, v):
            return jnp.sum(
                flash_attention(q, k, v, causal=True,
                                **flash_kwargs).astype(jnp.float32))
        l, grads = jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)
        return l, grads

    for _ in range(warmup):
        l, g = fwd_bwd(q, k, v)
    float(l)
    t0 = time.perf_counter()
    for _ in range(iters):
        l, g = fwd_bwd(q, k, v)
    float(l)  # host fetch of the last call's loss = the barrier
    ms = (time.perf_counter() - t0) / iters * 1e3
    # Causal attention FLOPs: ~0.5 * 4 matmuls fwd + equivalent bwd.
    flops = 0.5 * (2 + 5) * 2 * bh * t * t * d
    print(json.dumps({
        "shape": label, "ms": round(ms, 2),
        "tflops_per_sec": round(flops / (ms / 1e3) / 1e12, 1),
        "blocks": flash_kwargs or "auto",
    }))
    return ms


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", nargs="+", default=list(SHAPES),
                    choices=list(SHAPES))
    ap.add_argument("--no-verify", action="store_true")
    ap.add_argument("--blocks", default=None,
                    help="fwd blocks as QxK (e.g. 1024x2048); default auto")
    ap.add_argument("--bwd-blocks", default=None,
                    help="bwd blocks as QxK (e.g. 512x1024); default auto")
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--split-bwd", action="store_true",
                    help="A/B: run the pre-round-4 two-kernel backward "
                         "instead of the fused one")
    ap.add_argument("--exp2", action="store_true",
                    help="A/B: softmax exponentials as native 2^x with "
                         "log2(e) folded into the score scale (probes "
                         "whether Mosaic's exp already uses the pow2 unit)")
    args = ap.parse_args()

    if args.split_bwd:
        import distributed_training_tpu.ops.flash_attention as fa
        fa._USE_SPLIT_BWD = True
        print("backward: SPLIT (two-kernel)", file=sys.stderr)
    if args.exp2:
        import distributed_training_tpu.ops.flash_attention as fa
        fa._USE_EXP2 = True
        print("softmax exp: exp2 (log2-domain recurrence)", file=sys.stderr)

    kwargs = {}
    if args.blocks:
        bq, bk = map(int, args.blocks.split("x"))
        kwargs.update(block_q=bq, block_k=bk)
    if args.bwd_blocks:
        bq, bk = map(int, args.bwd_blocks.split("x"))
        kwargs.update(bwd_block_q=bq, bwd_block_k=bk)

    enable_compile_cache()
    device = require_tpu("flash_kernel_bench")
    print(f"platform: {device['platform']} ({device['kind']})",
          file=sys.stderr)
    failed = []
    if not args.no_verify:
        verify_compiled(kwargs)
        for s in args.shapes:
            for variant in ("fused", "split", "lse"):
                # One refused shape must not hide the others' verdicts:
                # report every (shape, variant), fail at the end.
                try:
                    ok = verify_shape(*SHAPES[s], kwargs, variant)
                except Exception as e:  # noqa: BLE001 - Mosaic/XLA refusal
                    traceback.print_exc()
                    print(json.dumps({
                        "verify": SHAPES[s][0], "variant": variant,
                        "ok": False,
                        "error": f"{type(e).__name__}: {e}"[:4000]}),
                        flush=True)
                    ok = False
                if not ok:
                    failed.append(f"{s}/{variant}")
        if not verify_fused_adam():
            failed.append("fused_adam")
    for s in args.shapes:
        bench_shape(*SHAPES[s], kwargs, iters=args.iters)
    if failed:
        raise SystemExit(f"verification FAILED: {', '.join(failed)}")


if __name__ == "__main__":
    main()
