"""Capture a per-op profile of a train step on the real chip.

Round-2 evidence tooling (VERDICT r1 #1: "capture a per-op profile of the
R50 step into the repo"). Runs the same jitted step bench.py measures under
``jax.profiler.trace`` and parses the xplane protobuf DIRECTLY
(``tensorflow.tsl...xplane_pb2`` — the tensorboard-plugin-profile converter
is broken in this image) into a compact committed JSON artifact:

- per-HLO-category totals: self time, FLOPs, bytes accessed → achieved
  TFLOP/s and GB/s against the device's own advertised peaks;
- top-N individual fusions by total device time.

Usage (needs a TPU; one process per chip):
    PROTOCOL_BUFFERS_PYTHON_IMPLEMENTATION=python \
    python tools/profile_step.py --model resnet50 --batch-size 256 \
        --out profiles/r50_b256
    python tools/profile_step.py --lm --seq-len 1024 --out profiles/gpt_t1024
    python tools/profile_step.py --summarize profiles/r50_b256.json
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# The pure-python protobuf fallback is required for the prebuilt tsl protos.
os.environ.setdefault("PROTOCOL_BUFFERS_PYTHON_IMPLEMENTATION", "python")


def parse_xplane(path: str, top: int) -> dict:
    """Aggregate the TPU plane of one xplane.pb into category/op tables."""
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    xs = xplane_pb2.XSpace()
    with open(path, "rb") as fh:
        xs.ParseFromString(fh.read())
    tpu = next((p for p in xs.planes if p.name.startswith("/device:TPU")),
               None)
    if tpu is None:
        return {"error": f"no TPU plane in {path}"}
    stat_names = {k: v.name for k, v in tpu.stat_metadata.items()}

    def stats_of(msg):
        out = {}
        for st in msg.stats:
            name = stat_names.get(st.metadata_id, str(st.metadata_id))
            out[name] = (st.double_value or st.uint64_value or st.int64_value
                         or st.str_value)
        return out

    device = stats_of(tpu)

    steps_line = next((l for l in tpu.lines if l.name == "Steps"), None)
    num_steps = len(steps_line.events) if steps_line else 0
    step_ps = (sum(e.duration_ps for e in steps_line.events)
               if steps_line else 0)

    ops_line = next((l for l in tpu.lines if l.name == "XLA Ops"), None)
    cats: dict[str, dict] = {}
    ops: dict[str, dict] = {}
    total_ps = 0
    for ev in ops_line.events if ops_line else ():
        md = tpu.event_metadata[ev.metadata_id]
        ms = stats_of(md)
        cat = ms.get("hlo_category", "?")
        dur = ev.duration_ps
        total_ps += dur
        flops = int(ms.get("flops", 0) or 0)
        bytes_acc = int(ms.get("bytes_accessed", 0) or 0)
        c = cats.setdefault(cat, {"time_ps": 0, "flops": 0, "bytes": 0,
                                  "occurrences": 0})
        c["time_ps"] += dur
        c["flops"] += flops
        c["bytes"] += bytes_acc
        c["occurrences"] += 1
        o = ops.setdefault(md.display_name, {
            "category": cat, "time_ps": 0, "flops": 0, "bytes": 0,
            "occurrences": 0, "source_op": ms.get("tf_op", "")})
        o["time_ps"] += dur
        o["flops"] += flops
        o["bytes"] += bytes_acc
        o["occurrences"] += 1

    top_ops = sorted(ops.items(), key=lambda kv: -kv[1]["time_ps"])[:top]
    return {
        "device": {
            "type": device.get("device_type_string"),
            "peak_tflops": device.get("peak_teraflops_per_second"),
            "peak_hbm_gbps": device.get("peak_hbm_bw_gigabytes_per_second"),
        },
        "num_steps": num_steps,
        "step_time_ms": step_ps / num_steps / 1e9 if num_steps else None,
        "op_time_ms_per_step": (total_ps / num_steps / 1e9
                                if num_steps else None),
        "categories": dict(sorted(cats.items(),
                                  key=lambda kv: -kv[1]["time_ps"])),
        "top_ops": [{"name": k, **v} for k, v in top_ops],
    }


def capture(args) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    import bench
    from distributed_training_tpu.runtime.backend import (
        enable_compile_cache,
        require_tpu,
    )

    enable_compile_cache()
    device = require_tpu("profile_step")
    print(f"[profile] platform={device['platform']} "
          f"device_kind={device['kind']!r}", file=sys.stderr)

    if args.lm:
        import optax

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

        mesh = create_mesh(MeshConfig(data=-1))
        model = get_model(
            "transformer_lm", num_classes=50304, dtype=jnp.bfloat16,
            num_layers=12, num_heads=12, hidden_dim=768,
            max_len=args.seq_len, attn_impl=args.attn_impl,
            logits_dtype=parse_logits_dtype(args.logits_dtype),
            head_bias=args.head_bias)
        tx = optax.adamw(3e-4)
        state = init_train_state(
            model, jax.random.PRNGKey(0), (1, 8), tx,
            loss_scale=LossScaleState.create(PrecisionConfig(dtype="bf16")),
            input_dtype=jnp.int32)
        step = make_tp_lm_train_step(
            mesh, model=model, donate=True,
            ce_chunk=args.ce_chunk,
            accuracy_metric=not args.no_accuracy)
        tokens = np.random.RandomState(0).randint(
            0, 50304, (args.batch_size, args.seq_len + 1)).astype(np.int32)
        batch = jax.device_put(
            {k: jnp.asarray(v) for k, v in make_lm_batch(tokens).items()},
            step.batch_shardings)
        label = f"gpt2s_T{args.seq_len}_B{args.batch_size}_{args.attn_impl}"
    else:
        mesh, state, step, _ = bench.build(
            args.model, args.batch_size, args.image_size, args.num_classes,
            zero_stage=args.zero_stage, remat=args.remat,
            remat_policy=args.remat_policy, param_dtype=args.param_dtype)
        rng = np.random.RandomState(0)
        batch = {
            "image": jnp.asarray(
                rng.rand(args.batch_size, args.image_size, args.image_size,
                         3), jnp.float32),
            "label": jnp.asarray(
                rng.randint(0, args.num_classes, args.batch_size), jnp.int32),
        }
        label = f"{args.model}_b{args.batch_size}"

    key = jax.random.PRNGKey(0)
    for _ in range(args.warmup):
        state, metrics = step(state, batch, key)
    float(metrics["loss"])  # barrier: host fetch of the last step's loss

    trace_dir = args.out + "_trace"
    with jax.profiler.trace(trace_dir):
        for _ in range(args.trace_steps):
            state, metrics = step(state, batch, key)
        float(metrics["loss"])

    artifact = {"label": label, "trace_steps": args.trace_steps}
    xplanes = sorted(glob.glob(
        os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    if xplanes:
        artifact.update(parse_xplane(xplanes[-1], args.top))
    else:
        artifact["error"] = f"no xplane.pb under {trace_dir}"

    os.makedirs(os.path.dirname(os.path.abspath(args.out)) or ".",
                exist_ok=True)
    with open(args.out + ".json", "w") as fh:
        json.dump(artifact, fh, indent=1)
    print(f"[profile] wrote {args.out}.json "
          f"(trace in {trace_dir})", file=sys.stderr)
    summarize(args.out + ".json", args.top)


def summarize(path: str, top: int) -> None:
    """Print category roofline + top-op tables from a saved artifact."""
    with open(path) as fh:
        a = json.load(fh)
    if "categories" not in a:
        print(f"no parsed profile in {path}: {a.get('error')}")
        return
    n = a["num_steps"] or 1
    step_ms = a.get("step_time_ms")
    busy_ms = a.get("op_time_ms_per_step")
    fmt = lambda v: f"{v:.2f} ms" if v is not None else "n/a"
    print(f"\n{a['label']}: {a['num_steps']} steps traced, "
          f"step {fmt(step_ms)} "
          f"(XLA-op busy {fmt(busy_ms)}); device "
          f"{a['device']['type']} peaks {a['device']['peak_tflops']} TFLOP/s"
          f" / {a['device']['peak_hbm_gbps']} GB/s HBM")
    print("\n| category | ms/step | % | TFLOP/s | GB/s (bytes-accessed) |")
    print("|---|---|---|---|---|")
    total = sum(c["time_ps"] for c in a["categories"].values())
    for cat, c in a["categories"].items():
        secs = max(c["time_ps"], 1) / 1e12
        ms = c["time_ps"] / n / 1e9
        print(f"| {cat} | {ms:.2f} | {100 * c['time_ps'] / total:.1f} "
              f"| {c['flops'] / secs / 1e12:.1f} "
              f"| {c['bytes'] / secs / 1e9:.0f} |")
    print(f"\ntop {top} fusions by device time:")
    print("| fusion | category | ms/step | TFLOP/s | GB/s | n |")
    print("|---|---|---|---|---|---|")
    for o in a["top_ops"][:top]:
        secs = max(o["time_ps"], 1) / 1e12
        print(f"| {o['name'][:46]} | {o['category']} "
              f"| {o['time_ps'] / n / 1e9:.2f} "
              f"| {o['flops'] / secs / 1e12:.1f} "
              f"| {o['bytes'] / secs / 1e9:.0f} | {o['occurrences']} |")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="resnet50")
    ap.add_argument("--batch-size", type=int, default=256)
    ap.add_argument("--image-size", type=int, default=224)
    ap.add_argument("--num-classes", type=int, default=1000)
    ap.add_argument("--zero-stage", type=int, default=0)
    ap.add_argument("--remat", action="store_true", default=False)
    ap.add_argument("--remat-policy", default=None, choices=[None, "conv"])
    ap.add_argument("--param-dtype", default="fp32",
                    choices=["fp32", "bf16"])
    ap.add_argument("--lm", action="store_true", default=False,
                    help="profile the GPT-2-small LM step instead")
    ap.add_argument("--seq-len", type=int, default=1024)
    ap.add_argument("--attn-impl", default="flash")
    ap.add_argument("--ce-chunk", type=int, default=None)
    ap.add_argument("--no-accuracy", action="store_true", default=False)
    ap.add_argument("--head-bias", action=argparse.BooleanOptionalAction,
                    default=False,
                    help="lm_head bias (default off, matching the round-5 "
                         "bench/CLI default)")
    ap.add_argument("--logits-dtype", default="bf16",
                    choices=["fp32", "bf16"])
    ap.add_argument("--warmup", type=int, default=4)
    ap.add_argument("--trace-steps", type=int, default=3)
    ap.add_argument("--top", type=int, default=15)
    ap.add_argument("--out", default=None,
                    help="artifact prefix (writes <out>.json + <out>_trace/); "
                         "required unless --summarize")
    ap.add_argument("--summarize", default=None,
                    help="just print the tables from an existing artifact")
    args = ap.parse_args()
    if args.summarize:
        summarize(args.summarize, args.top)
        return
    if not args.out:
        raise SystemExit("--out is required to capture a profile")
    capture(args)


if __name__ == "__main__":
    main()
