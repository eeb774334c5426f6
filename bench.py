"""Benchmark: the two headline training-throughput metrics.

A bare ``python bench.py`` emits BOTH legs, one JSON line each — the image
leg (ResNet-50 synthetic-ImageNet images/sec/chip, the BASELINE.json
north-star: target ≥6000 on v4-8) followed by the LM leg (GPT-2-small
tokens/sec). Per-leg flags isolate one leg: ``--image``, ``--lm``,
``--data-only``, ``--data-concurrent``.

Every device leg needs a TPU: with none it exits non-zero and prints no
result line (``runtime/backend.py::require_tpu``) — a CPU timing is never
printed under a device metric's name. ``--data-only`` is the one host-side
leg and touches no device.

    {"metric": ..., "value": N, "unit": "images/sec/chip", "vs_baseline": N,
     "mfu": N, "model_flops_per_sec": N,
     "step_time_p50_ms": N, "step_time_p95_ms": N}
    {"metric": ..., "value": N, "unit": "tokens/sec", "vs_baseline": N, ...}

The observability fields (round 6) are additive; ``mfu`` is null when the
chip's ``device_kind`` has no peak in ``observability/flops.py`` unless
``$OBS_PEAK_FLOPS`` supplies one.

Measures the steady-state jitted train step (fwd + bwd + Adam update, bf16
compute) on device-resident synthetic ImageNet batches — the same compute
graph as real training; input-pipeline overlap is benchmarked separately by
the data-layer tests. The per-step host sync the reference suffers
(``loss.item()``, SURVEY.md §2.5) is absent by construction: the loop only
blocks on the final step's output.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax

from distributed_training_tpu.runtime.backend import (
    enable_compile_cache,
    require_tpu,
)

BASELINE_IMAGES_PER_SEC_PER_CHIP = 6000.0


def observability_fields(step_flops: float | None, per_step_ms: list,
                         n_devices: int, total_steps: int,
                         total_seconds: float) -> dict:
    """The additive observability fields both legs emit (round 6):
    ``mfu`` + ``model_flops_per_sec`` from the analytic step FLOPs
    (``observability/flops.py``; mfu is null when the chip's peak is
    unknown unless $OBS_PEAK_FLOPS overrides), and
    step-time p50/p95 over the per-sync-window averages (the sync fetches
    are the honest execution barriers — see the barrier comment in
    bench_image — so between-sync step times are window means, not
    dispatch times)."""
    from distributed_training_tpu.observability import (
        device_peak_flops,
        percentile,
    )
    from distributed_training_tpu.observability.flops import mfu as _mfu

    out: dict = {"mfu": None}
    if per_step_ms:
        out["step_time_p50_ms"] = round(percentile(per_step_ms, 50), 3)
        out["step_time_p95_ms"] = round(percentile(per_step_ms, 95), 3)
    if step_flops and total_seconds > 0:
        fps = step_flops * total_steps / total_seconds
        out["model_flops_per_sec"] = round(fps, 1)
        u = _mfu(fps, n_devices, device_peak_flops())
        if u is not None:
            out["mfu"] = round(u, 4)
    return out


class _WindowTimer:
    """Per-sync-window step times: ``mark(k)`` after every host fetch
    records the window's mean per-step ms over the k steps it covered."""

    def __init__(self):
        self._last = time.perf_counter()
        self.per_step_ms: list[float] = []

    def mark(self, steps_in_window: int) -> None:
        now = time.perf_counter()
        if steps_in_window > 0:
            self.per_step_ms.append(
                (now - self._last) / steps_in_window * 1e3)
        self._last = now


def build(model_name: str, batch_size: int, image_size: int, num_classes: int,
          zero_stage: int = 0, remat: bool = False,
          remat_policy: str | None = None, param_dtype: str = "fp32",
          grad_accum: int = 1, cpu_offload: bool = False):
    from distributed_training_tpu.config import PrecisionConfig
    from distributed_training_tpu.models import get_model
    from distributed_training_tpu.parallel.sharding import (
        place_state,
        state_shardings,
    )
    from distributed_training_tpu.runtime.mesh import MeshConfig, create_mesh
    from distributed_training_tpu.train.precision import LossScaleState
    from distributed_training_tpu.train.step import make_train_step
    from distributed_training_tpu.train.train_state import init_train_state

    mesh = create_mesh(MeshConfig(data=-1))
    kwargs = {}
    if remat or remat_policy:
        kwargs["remat"] = True
        if remat_policy:
            kwargs["remat_policy"] = remat_policy
    if param_dtype == "bf16":
        # Lever: bf16 master params + bf16 SGD momentum — halves the
        # weight/opt-state HBM traffic per step (fine for throughput
        # measurement; convergence-critical runs keep fp32 masters).
        kwargs["param_dtype"] = jnp.bfloat16
    model = get_model(model_name, num_classes=num_classes, dtype=jnp.bfloat16,
                      **kwargs)
    # SGD+momentum per the BASELINE.json north-star spec ("forward, backward,
    # gradient all-reduce, SGD+momentum update"); Adam measures within noise
    # of this (the step is HBM-bound in the convs, not the optimizer).
    tx = optax.sgd(0.1, momentum=0.9)
    state = init_train_state(
        model, jax.random.PRNGKey(0),
        (batch_size, image_size, image_size, 3), tx,
        loss_scale=LossScaleState.create(PrecisionConfig(dtype="bf16")))
    state = place_state(state, state_shardings(
        state, mesh, zero_stage=zero_stage, cpu_offload=cpu_offload))
    step = make_train_step(mesh, zero_stage=zero_stage, donate=True,
                           grad_accum_steps=grad_accum,
                           cpu_offload=cpu_offload)
    # The model instance rides along so the MFU accounting reads dims off
    # the architecture actually benched (observability.forward_flops).
    return mesh, state, step, model


def bench_data_only(args) -> None:
    """Host input-pipeline throughput: can the host feed the device rate?

    Two paths, mirroring real training:
    - ``imagefolder``: JPEG decode (PIL) + resize/crop/flip per example via
      the threaded :class:`ImageFolderLoader` — the DALI-analogue path. A
      synthetic on-disk tree is generated once (real JPEG bytes, so decode
      cost is real).
    - ``augment``: in-memory arrays through the C++ (ctypes) fused
      pad/crop/flip/normalize augmentation — the CIFAR-style path.

    Prints ONE JSON line: host images/sec for the requested path and
    ``vs_baseline`` against ``DEVICE_RATE`` below, i.e. >= 1.0 means the
    host is not the bottleneck.
    """
    import shutil
    import tempfile

    # R50 img/s/chip the host must feed: the builder's device-resident
    # number from before jax 0.9.0 (not measured on the current stack).
    DEVICE_RATE = 2580.0
    batch = args.data_batch_size  # decoupled from the device bench's
    # effective-batch default so host numbers stay comparable across rounds

    if args.data_path:
        if not os.path.isdir(args.data_path):
            raise SystemExit(
                f"--data-path {args.data_path} does not exist; omit it to "
                f"bench against a generated synthetic JPEG tree")
        root, cleanup = args.data_path, None
    else:
        from PIL import Image

        root = tempfile.mkdtemp(prefix="bench_imagefolder_")
        cleanup = root
        rng = np.random.RandomState(0)
        n_images = args.data_images
        per_class = n_images // 8
        for c in range(8):
            d = os.path.join(root, "train", f"class{c}")
            os.makedirs(d)
            for i in range(per_class):
                # Real JPEG bytes at ImageNet-ish dims: decode cost is real.
                arr = rng.randint(0, 255, (256, 256, 3), dtype=np.uint8)
                Image.fromarray(arr).save(
                    os.path.join(d, f"im{i}.jpg"), quality=85)

    def timed_epoch(loader):
        loader.set_epoch(0)
        for _ in loader:  # warm epoch (thread spin-up, page cache)
            pass
        loader.set_epoch(1)
        t0 = time.perf_counter()
        n = 0
        for b in loader:
            n += len(b["label"])
        return n / (time.perf_counter() - t0)

    try:
        folder_rate = cached_rate = None
        if args.data_mode in ("imagefolder", "cached", "both"):
            from distributed_training_tpu.data.imagefolder import (
                ImageFolderLoader,
                scan_imagefolder,
            )

            paths, labels, _ = scan_imagefolder(os.path.join(root, "train"))
            if args.data_mode != "cached":
                folder_rate = timed_epoch(ImageFolderLoader(
                    paths, labels, global_batch_size=batch,
                    image_size=args.image_size, augment="pad_crop_flip",
                    train=True, num_workers=args.data_workers,
                    process_index=0, process_count=1))
            if args.data_mode in ("cached", "both"):
                from distributed_training_tpu.data.decoded_cache import (
                    DecodedCacheLoader,
                    build_decoded_cache,
                )

                cache = os.path.join(root, ".decoded_cache",
                                     f"train_{args.image_size}")
                t0 = time.perf_counter()
                build_decoded_cache(
                    paths, labels, cache, image_size=args.image_size,
                    num_workers=args.data_workers)
                build_s = time.perf_counter() - t0
                cached_rate = timed_epoch(DecodedCacheLoader(
                    cache, global_batch_size=batch,
                    augment="pad_crop_flip", train=True,
                    process_index=0, process_count=1))
                print(json.dumps({
                    "note": "decoded-cache one-time build",
                    "images": len(paths), "seconds": round(build_s, 1),
                }), file=sys.stderr)

        augment_rate = None
        if args.data_mode in ("augment", "both"):
            from distributed_training_tpu.data.pipeline import ShardedDataLoader

            rng = np.random.RandomState(0)
            images = rng.rand(4096, 32, 32, 3).astype(np.float32)
            labels = rng.randint(0, 10, 4096).astype(np.int32)
            augment_rate = timed_epoch(ShardedDataLoader(
                images, labels, global_batch_size=batch,
                augment="pad_crop_flip", train=True,
                process_index=0, process_count=1))
    finally:
        if cleanup:
            shutil.rmtree(cleanup, ignore_errors=True)

    # Primary = the rate the device would actually be fed in steady state:
    # the cached path when measured, else live decode, else augment.
    primary = next(r for r in (cached_rate, folder_rate, augment_rate)
                   if r is not None)
    extras = {}
    if cached_rate is not None and primary is not cached_rate:
        extras["cached_images_per_sec"] = round(cached_rate, 1)
    if folder_rate is not None and primary is not folder_rate:
        extras["jpeg_decode_images_per_sec"] = round(folder_rate, 1)
    if augment_rate is not None and primary is not augment_rate:
        extras["augment_images_per_sec"] = round(augment_rate, 1)
    print(json.dumps({
        "metric": f"host input pipeline ({args.data_mode}; {os.cpu_count()} "
                  f"core(s), {args.data_workers} threads, batch "
                  f"{batch})",
        "value": round(primary, 2),
        "unit": "images/sec (host)",
        "vs_baseline": round(primary / DEVICE_RATE, 4),
        **extras,
    }))


def bench_data_concurrent(args) -> None:
    """Host pipeline measured CONCURRENT with training (round 4).

    The --data-only numbers measure the loader on an idle host; the real
    question is whether the host feeds the chip while the training loop,
    dispatch, and metric fetches compete for the same core(s). This mode
    trains ResNet-50 end-to-end on REAL batches from the decoded cache
    (multi-worker assembly + double-buffered device prefetch) and
    simultaneously runs a second flat-out loader in a stress thread:

    - ``value`` = end-to-end train img/s on real data (vs the
      device-resident synthetic bound the image leg measures);
    - ``spare_host_images_per_sec`` = what the stress loader sustained
      DURING training — the headroom available to feed additional chips.
    """
    import shutil
    import tempfile
    import threading

    from distributed_training_tpu.data.decoded_cache import (
        DecodedCacheLoader,
        build_decoded_cache,
    )
    from distributed_training_tpu.data.prefetch import DevicePrefetcher

    platform = require_tpu("bench --data-concurrent")["platform"]

    from PIL import Image

    n_chips_probe = jax.device_count()
    # A global batch larger than the dataset would make every epoch yield
    # zero batches (drop_last) and the feed loop spin forever.
    min_images = 2 * args.batch_size * n_chips_probe
    if args.data_images < min_images:
        print(f"bench: --data-images {args.data_images} < 2x the global "
              f"batch; raising to {min_images}", file=sys.stderr)
        args.data_images = min_images

    root = tempfile.mkdtemp(prefix="bench_concurrent_")
    try:
        rng = np.random.RandomState(0)
        paths, labels = [], []
        for i in range(args.data_images):
            arr = rng.randint(0, 255, (256, 256, 3), dtype=np.uint8)
            p = os.path.join(root, f"im{i}.jpg")
            Image.fromarray(arr).save(p, quality=85)
            paths.append(p)
            labels.append(i % 8)
        cache = os.path.join(root, f"cache_{args.image_size}")
        build_decoded_cache(paths, labels, cache,
                            image_size=args.image_size,
                            num_workers=args.data_workers)

        n_chips = jax.device_count()
        batch = args.batch_size * n_chips
        mesh, state, step, _ = build(
            args.model, batch, args.image_size, 8,
            grad_accum=1)
        from distributed_training_tpu.parallel.sharding import batch_sharding

        shardings = {"image": batch_sharding(mesh, 4),
                     "label": batch_sharding(mesh, 1)}

        def loader():
            return DecodedCacheLoader(
                cache, global_batch_size=batch, augment="pad_crop_flip",
                train=True, process_index=0, process_count=1,
                num_workers=args.data_workers)

        def batches():
            ld = loader()
            epoch = 0
            while True:
                ld.set_epoch(epoch)
                yield from ld
                epoch += 1

        place = lambda b: jax.device_put(b, shardings)  # noqa: E731
        key = jax.random.PRNGKey(0)

        # Stress loader: counts host images assembled while training runs.
        stress_count = [0]
        stop = threading.Event()

        def stress():
            ld = loader()
            epoch = 100
            while not stop.is_set():
                ld.set_epoch(epoch)
                for b in ld:
                    stress_count[0] += len(b["label"])
                    if stop.is_set():
                        return
                epoch += 1

        it = iter(DevicePrefetcher(batches(), place, depth=2))
        for _ in range(args.warmup):
            state, metrics = step(state, next(it), key)
        if args.warmup:
            float(metrics["loss"])

        t = threading.Thread(target=stress, daemon=True)
        t0 = time.perf_counter()
        if args.data_stress:
            t.start()
        for i in range(args.steps):
            state, metrics = step(state, next(it), key)
            if args.sync_interval > 0 and (i + 1) % args.sync_interval == 0:
                float(metrics["loss"])
        float(metrics["loss"])
        dt = time.perf_counter() - t0
        stop.set()
        if args.data_stress:
            t.join(timeout=30)

        img_s = args.steps * batch / dt / n_chips
        result = {
            "metric": f"{args.model} end-to-end train on decoded cache "
                      f"(real batches, {args.data_workers} workers, "
                      f"prefetch 2, batch {args.batch_size}/chip, "
                      f"{n_chips} {platform} chip(s))"
                      + (" + concurrent stress loader"
                         if args.data_stress else ""),
            "value": round(img_s, 2),
            "unit": "images/sec/chip",
            "vs_baseline": round(img_s / BASELINE_IMAGES_PER_SEC_PER_CHIP, 4),
        }
        if args.data_stress:
            result["spare_host_images_per_sec"] = round(
                stress_count[0] / dt, 1)
        print(json.dumps(result))
    finally:
        shutil.rmtree(root, ignore_errors=True)


def bench_lm(args) -> None:
    """GPT-2-small train throughput in tokens/sec.

    Same methodology as the image bench: steady-state jitted step on
    device-resident batches, host-fetch barrier every sync interval.
    """
    from distributed_training_tpu.config import PrecisionConfig
    from distributed_training_tpu.models import get_model
    from distributed_training_tpu.runtime.mesh import MeshConfig, create_mesh
    from distributed_training_tpu.train.lm_step import (
        make_lm_batch,
        make_tp_lm_train_step,
    parse_logits_dtype,
    )
    from distributed_training_tpu.train.precision import LossScaleState
    from distributed_training_tpu.train.train_state import init_train_state

    platform = require_tpu("bench --lm")["platform"]

    if args.tp < 1 or jax.device_count() % args.tp:
        raise SystemExit(f"--tp {args.tp} must be >= 1 and divide the "
                         f"device count (= {jax.device_count()})")
    mesh = create_mesh(MeshConfig(data=-1, model=args.tp))
    model = get_model(
        "transformer_lm", num_classes=50304, dtype=jnp.bfloat16,
        num_layers=12, num_heads=12, hidden_dim=768,
        max_len=args.seq_len, attn_impl=args.attn_impl,
        logits_dtype=parse_logits_dtype(args.logits_dtype),
        head_bias=args.head_bias)
    if args.lm_optimizer == "hybrid_adam":
        from distributed_training_tpu.ops.fused_adam import fused_adam

        tx = fused_adam(3e-4)
    else:
        tx = optax.adamw(3e-4)
    state = init_train_state(
        model, jax.random.PRNGKey(0), (1, 8), tx,
        loss_scale=LossScaleState.create(PrecisionConfig(dtype="bf16")),
        input_dtype=jnp.int32)
    step = make_tp_lm_train_step(mesh, model=model, donate=True,
                                 ce_chunk=args.ce_chunk,
                                 accuracy_metric=not args.no_accuracy,
                                 ce_save_probs=args.ce_save_probs,
                                 tp_overlap=args.tp_overlap)
    toks = np.random.RandomState(0).randint(
        0, 50304, (args.lm_batch, args.seq_len + 1)).astype(np.int32)
    batch = jax.device_put(
        {k: jnp.asarray(v) for k, v in make_lm_batch(toks).items()},
        step.batch_shardings)
    key = jax.random.PRNGKey(0)

    steps_per_call = max(1, args.steps_per_call)
    if steps_per_call > 1:
        # Same dispatch-amortization lever as the image bench default: N
        # steps compiled into one dispatch (real training amortizes
        # per-step dispatch with async input pipelines and periodic
        # logging).
        import functools

        from jax import lax

        inner = step
        state, _ = inner(state, batch, key)  # prime the lazy jit

        @functools.partial(jax.jit, donate_argnums=(0,))
        def multi(state, batch, key):
            def body(s, _):
                s, m = inner(s, batch, key)
                return s, m["loss"]
            state, losses = lax.scan(body, state, None,
                                     length=steps_per_call)
            return state, {"loss": losses[-1]}

        step = multi
        args.steps = max(1, args.steps // steps_per_call)
        args.warmup = max(1, args.warmup // steps_per_call)

    for _ in range(args.warmup):
        state, m = step(state, batch, key)
    if args.warmup:
        float(m["loss"])
    t0 = time.perf_counter()
    wt = _WindowTimer()
    win = 0
    for i in range(args.steps):
        state, m = step(state, batch, key)
        win += steps_per_call
        if args.sync_interval > 0 and (i + 1) % args.sync_interval == 0:
            float(m["loss"])
            wt.mark(win)
            win = 0
    float(m["loss"])
    wt.mark(win)
    dt = time.perf_counter() - t0
    tok_s = (args.lm_batch * args.seq_len * args.steps * steps_per_call) / dt
    from distributed_training_tpu.observability import (
        forward_flops,
        train_step_flops,
    )

    # Dims read off the model instance built above — a hand-copied set
    # here would silently drift if the bench config ever changes.
    step_flops = train_step_flops(forward_flops(
        model, seq_len=args.seq_len, batch=args.lm_batch))
    # vs_baseline compares against round 1's 94.6k tok/s, which was
    # measured at exactly B16 T1024 flash on TPU — any other config is
    # incomparable.
    is_baseline_config = (args.lm_batch == 16
                          and args.seq_len == 1024
                          and args.attn_impl == "flash"
                          and not args.ce_chunk and not args.no_accuracy
                          and args.lm_optimizer == "adamw"
                          and args.logits_dtype == "bf16"
                          and not args.head_bias
                          and not args.ce_save_probs
                          and args.tp == 1 and not args.tp_overlap
                          and steps_per_call == 1)
    result = {
        "metric": f"GPT-2-small train throughput (bf16 "
                  f"{'HybridAdam' if args.lm_optimizer == 'hybrid_adam' else 'AdamW'}, B"
                  f"{args.lm_batch} T{args.seq_len} {args.attn_impl}"
                  f"{', logits:fp32' if args.logits_dtype == 'fp32' else ''}"
                  f"{', head-bias' if args.head_bias else ''}"
                  f"{', chunked CE' if args.ce_chunk else ''}"
                  f"{', ce-probs' if args.ce_save_probs else ''}"
                  f"{', no-acc-metric' if args.no_accuracy else ''}"
                  f"{', tp:' + str(args.tp) if args.tp > 1 else ''}"
                  f"{', tp-overlap' if args.tp_overlap else ''}"
                  f"{', steps/call:' + str(steps_per_call) if steps_per_call > 1 else ''}, "
                  f"{jax.device_count()} {platform} chip(s))",
        "value": round(tok_s, 1),
        "unit": "tokens/sec",
        "vs_baseline": (round(tok_s / 94_600, 4)
                        if is_baseline_config else None),
        **observability_fields(step_flops, wt.per_step_ms,
                               jax.device_count(),
                               args.steps * steps_per_call, dt),
    }
    print(json.dumps(result))


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="resnet50")
    # Defaults are the round-2 best throughput config on one v5e chip:
    # effective batch 512 as 2x256 microbatches (one optimizer update per
    # 512 — DeepSpeed-style accumulation) with 15 steps compiled per
    # dispatch.
    ap.add_argument("--batch-size", type=int, default=512,
                    help="per-chip EFFECTIVE batch size")
    ap.add_argument("--image-size", type=int, default=224)
    ap.add_argument("--num-classes", type=int, default=1000)
    ap.add_argument("--zero-stage", type=int, default=0, choices=[0, 1, 2, 3],
                    help="ZeRO placement for the benched step")
    ap.add_argument("--cpu-offload", action="store_true", default=False,
                    help="ZeRO-Offload: optimizer-state shard in pinned "
                         "host memory (requires --zero-stage >= 1)")
    ap.add_argument("--remat", action="store_true", default=False,
                    help="activation-checkpoint blocks (fits larger batches)")
    ap.add_argument("--remat-policy", default=None, choices=[None, "conv"],
                    help="'conv': save only conv outputs, recompute BN/ReLU "
                         "in backward (memory-traffic lever)")
    ap.add_argument("--param-dtype", default="fp32", choices=["fp32", "bf16"],
                    help="bf16: halve weight+momentum HBM traffic")
    ap.add_argument("--input-dtype", default="fp32",
                    choices=["fp32", "bf16", "uint8"],
                    help="batch image dtype (bf16/uint8 cut host->HBM input "
                         "bytes; uint8 decodes on device like the cache path)")
    ap.add_argument("--grad-accum", type=int, default=2,
                    help="microbatch scan inside the step (batch-size is the "
                         "effective batch)")
    ap.add_argument("--steps-per-call", type=int, default=15,
                    help="compile N train steps into ONE dispatch "
                         "(lax.scan over the step; the same device batch "
                         "repeats). Removes per-step host dispatch from the "
                         "measurement — the pure device-throughput number a "
                         "deployment with an async input pipeline would "
                         "see")
    ap.add_argument("--warmup", type=int, default=5)
    ap.add_argument("--steps", type=int, default=45)
    ap.add_argument("--sync-interval", type=int, default=15,
                    help="fetch the loss to host every N steps (the honest "
                         "execution barrier; see comment in main)")
    ap.add_argument("--data-only", action="store_true", default=False,
                    help="bench the HOST input pipeline instead of the "
                         "device step (no TPU touched)")
    ap.add_argument("--data-stress", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="run the flat-out stress loader during "
                         "--data-concurrent (measures spare host capacity; "
                         "on a 1-core host it competes with the trainer)")
    ap.add_argument("--data-concurrent", action="store_true", default=False,
                    help="train on REAL decoded-cache batches while a "
                         "stress loader measures spare host capacity "
                         "(the concurrent-with-training measurement "
                         "--data-only cannot give)")
    ap.add_argument("--data-mode", default="both",
                    choices=["imagefolder", "cached", "augment", "both"])
    ap.add_argument("--data-path", default=None,
                    help="existing imagefolder root (<root>/train/...); "
                         "default generates a synthetic JPEG tree")
    ap.add_argument("--data-images", type=int, default=2048,
                    help="synthetic-tree size for --data-only")
    ap.add_argument("--data-workers", type=int, default=os.cpu_count() or 8)
    ap.add_argument("--data-batch-size", type=int, default=256,
                    help="--data-only loader batch (kept at the round-1 "
                         "value so host numbers stay comparable)")
    ap.add_argument("--lm", action="store_true", default=False,
                    help="bench ONLY the GPT-2-small LM step (tokens/sec); "
                         "a bare run emits the image leg then the LM leg")
    ap.add_argument("--image", action="store_true", default=False,
                    help="bench ONLY the image step (a bare run emits both "
                         "legs)")
    ap.add_argument("--tp", type=int, default=1,
                    help="LM leg: tensor-parallel (model axis) size; the "
                         "remaining devices form the data axis")
    ap.add_argument("--tp-overlap", action="store_true", default=False,
                    help="LM leg: ring-overlapped tensor parallelism "
                         "(latency-hiding collective matmul; ppermute "
                         "rings instead of monolithic TP collectives)")
    ap.add_argument("--lm-batch", type=int, default=16)
    ap.add_argument("--seq-len", type=int, default=1024)
    ap.add_argument("--attn-impl", default="flash",
                    choices=["flash", "exact"])
    ap.add_argument("--ce-chunk", type=int, default=None)
    ap.add_argument("--ce-save-probs", action="store_true", default=False,
                    help="CE backward from saved bf16 softmax probs "
                         "instead of re-reading logits + re-running exp "
                         "in both head matmul fusions; wins under "
                         "--logits-dtype fp32 only (warns under bf16, "
                         "where it measured slower)")
    ap.add_argument("--logits-dtype", default="bf16",
                    choices=["fp32", "bf16"],
                    help="head/logits dtype. Default bf16 since round 5 "
                         "(halves [B,T,vocab] HBM traffic; CE reduces in "
                         "fp32; 8-epoch chip A/B tracks fp32 to the 4th "
                         "decimal)")
    ap.add_argument("--head-bias", action=argparse.BooleanOptionalAction,
                    default=False,
                    help="lm_head bias. Default off since round 5 (GPT-2 "
                         "parity: its real head has none; the bias grad "
                         "is a full HBM pass over the logits)")
    ap.add_argument("--no-accuracy", action="store_true", default=False,
                    help="drop the per-step train-accuracy metric key "
                         "(since round 5 it derives from the CE row max "
                         "at ~zero cost; this flag is loss-only parity "
                         "with the reference, not a throughput lever)")
    ap.add_argument("--lm-optimizer", default="adamw",
                    choices=["adamw", "hybrid_adam"],
                    help="hybrid_adam: the Pallas fused-Adam kernel "
                         "(one HBM pass over p/g/m/v per tensor)")
    return ap


def main():
    args = build_parser().parse_args()

    if args.data_only:
        bench_data_only(args)
        return
    enable_compile_cache()
    if args.data_concurrent:
        bench_data_concurrent(args)
        return
    if args.lm:
        bench_lm(args)
        return
    if args.image:
        bench_image(args)
        return
    # Bare run: BOTH headline legs, one JSON line each (image, then LM), so
    # a single `python bench.py` witnesses the full metric surface. Each
    # leg gets its own copy — the benches mutate their args
    # (steps-per-call rounding).
    import copy

    bench_image(copy.deepcopy(args))
    bench_lm(copy.deepcopy(args))


def bench_image(args):
    platform = require_tpu("bench --image")["platform"]

    n_chips = jax.device_count()
    global_batch = args.batch_size * n_chips

    mesh, state, step, model = build(
        args.model, global_batch, args.image_size, args.num_classes,
        zero_stage=args.zero_stage, remat=args.remat,
        remat_policy=args.remat_policy, param_dtype=args.param_dtype,
        grad_accum=args.grad_accum, cpu_offload=args.cpu_offload)

    rng = np.random.RandomState(0)
    images = rng.rand(global_batch, args.image_size, args.image_size, 3)
    if args.input_dtype == "uint8":
        images = jnp.asarray((images * 255).astype(np.uint8))
    else:
        images = jnp.asarray(
            images, jnp.bfloat16 if args.input_dtype == "bf16"
            else jnp.float32)
    batch = {
        "image": images,
        "label": jnp.asarray(
            rng.randint(0, args.num_classes, global_batch), jnp.int32),
    }
    key = jax.random.PRNGKey(0)

    steps_per_call = max(1, args.steps_per_call)
    if args.cpu_offload and steps_per_call > 1:
        # The scan-of-steps carry cannot mix memory spaces (the offloaded
        # opt state is pinned_host at step boundaries); offload streams
        # host<->device every step regardless, so amortizing dispatch this
        # way is moot — run per-step.
        print("bench: --cpu-offload forces --steps-per-call 1",
              file=sys.stderr)
        steps_per_call = 1
    if steps_per_call > 1:
        import functools

        from jax import lax

        inner = step  # the cached jitted single step
        # Prime the inner jit's sharding cache with concrete arrays before
        # tracing the outer scan.
        state, _ = inner(state, batch, key)

        @functools.partial(jax.jit, donate_argnums=(0,))
        def multi(state, batch, key):
            def body(s, _):
                s, m = inner(s, batch, key)
                return s, m["loss"]
            state, losses = lax.scan(body, state, None,
                                     length=steps_per_call)
            return state, {"loss": losses[-1]}

        step = multi
        if args.warmup == 0 or args.steps < steps_per_call:
            print(f"bench: steps-per-call={steps_per_call} rounds "
                  f"warmup {args.warmup}->{max(1, args.warmup // steps_per_call) * steps_per_call} "
                  f"and steps {args.steps}->{max(1, args.steps // steps_per_call) * steps_per_call} "
                  f"(one priming call always runs; pass --steps-per-call 1 "
                  f"for exact counts)", file=sys.stderr)
        args.steps = max(1, args.steps // steps_per_call)
        args.warmup = max(1, args.warmup // steps_per_call)

    # Barrier = a host fetch of the loss scalar: float() forces the
    # device->host round trip. A fetch every `sync_interval` steps mirrors
    # real training's periodic metric logging (SURVEY.md §2.5: never
    # per-step).
    for _ in range(args.warmup):
        state, metrics = step(state, batch, key)
    if args.warmup:
        float(metrics["loss"])

    t0 = time.perf_counter()
    wt = _WindowTimer()
    win = 0
    for i in range(args.steps):
        state, metrics = step(state, batch, key)
        win += steps_per_call
        if args.sync_interval > 0 and (i + 1) % args.sync_interval == 0:
            float(metrics["loss"])
            wt.mark(win)
            win = 0
    float(metrics["loss"])
    wt.mark(win)
    dt = time.perf_counter() - t0

    images_per_sec = args.steps * steps_per_call * global_batch / dt
    per_chip = images_per_sec / n_chips
    from distributed_training_tpu.observability import (
        forward_flops,
        train_step_flops,
    )

    # Instance dispatch covers resnet AND vit (None for models without a
    # formula) and reads dims off the architecture actually benched.
    step_flops = train_step_flops(forward_flops(
        model, image_size=args.image_size, batch=global_batch))
    result = {
        "metric": f"{args.model} synthetic-ImageNet train throughput "
                  f"(bf16, batch {args.batch_size}/chip"
                  f"{', zero-' + str(args.zero_stage) if args.zero_stage else ''}"
                  f"{', offload' if args.cpu_offload else ''}"
                  f"{', remat' if args.remat else ''}"
                  f"{', remat:' + args.remat_policy if args.remat_policy else ''}"
                  f"{', params:bf16' if args.param_dtype == 'bf16' else ''}"
                  f"{', in:' + args.input_dtype if args.input_dtype != 'fp32' else ''}"
                  f"{', accum:' + str(args.grad_accum) if args.grad_accum > 1 else ''}"
                  f"{', steps/call:' + str(steps_per_call) if steps_per_call > 1 else ''}"
                  f", {n_chips} {platform} chip(s))",
        "value": round(per_chip, 2),
        "unit": "images/sec/chip",
        "vs_baseline": round(per_chip / BASELINE_IMAGES_PER_SEC_PER_CHIP, 4),
        **observability_fields(step_flops, wt.per_step_ms, n_chips,
                               args.steps * steps_per_call, dt),
    }
    print(json.dumps(result))


if __name__ == "__main__":
    main()
