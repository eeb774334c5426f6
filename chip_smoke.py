"""Chip smoke: train -> checkpoint -> serve -> image, once, on a real TPU.

The quickest proof that the system still starts on the chip. Drives the
main path through the entry points a user would call, at the full width of
GPT-2-small (12 layers, 12 heads, 768 wide, vocab 50304, context 1024 — the
one model the trainer, the checkpoint format and the engine share), with
random weights made from a seed. Three phases, each its own process, one
after another (a chip belongs to one process at a time; this parent never
imports jax):

- train: ``gpt/jax_tpu/train.py`` bf16, Pallas flash attention, B16 T1024,
  a handful of steps, one orbax save; loss finite and falling, checkpoint
  committed and verified (``resilience/verify.py``), flash kernels compiled.
- serve: ``gpt/jax_tpu/serve.py`` restores that checkpoint and answers a few
  byte-level prompts through ``Engine``; every request finishes
  ``length``/``eos``, the page pool drains balanced (serve.py raises
  otherwise), the restored epoch is the one just written, and two identical
  greedy prompts get identical completions.
- image: ``resnet/jax_tpu/train.py`` ResNet-50 on synthetic ImageNet at
  224 px, bf16, a handful of steps; loss finite.

Every wall time printed is set-up evidence, not a speed. There is no CPU
mode: without a TPU (or under ``JAX_PLATFORMS=cpu``) it exits non-zero in
seconds and prints no result. On success the last stdout line is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}
"""

from __future__ import annotations

import json
import math
import os
import re
import shutil
import signal
import subprocess
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
TRAIN_CLI = os.path.join("gpt", "jax_tpu", "train.py")
SERVE_CLI = os.path.join("gpt", "jax_tpu", "serve.py")
IMAGE_CLI = os.path.join("resnet", "jax_tpu", "train.py")

# GPT-2-small at full width; serve.py mirrors the training flags.
GPT2_SMALL = ["--vocab-size", "50304", "--num-layers", "12",
              "--num-heads", "12", "--hidden-dim", "768"]
TRAIN_STEPS = 24
IMAGE_STEPS = 6
MAX_NEW_TOKENS = 24
# Byte-level prompts; the first two are identical on purpose (greedy
# decode is lane-independent, so their completions must match bitwise).
PROMPTS = ["The quick brown fox", "The quick brown fox", "Once upon a time",
           "In the beginning", "0123456789", "chip smoke"]
TOTAL_BUDGET_S = 1150  # the contract: exit within 1200 s, cold compiles in


def phase_argv(phase: str, work: str) -> tuple[str, list[str]]:
    """(CLI script, argv) for one phase — the commands a user would type."""
    ckpt = os.path.join(work, "gpt_ckpt")
    if phase == "train":
        return TRAIN_CLI, [
            *GPT2_SMALL, "--max-len", "1024", "--seq-len", "1024",
            "-b", "16", "--dtype", "bf16", "--attn-impl", "flash",
            "-e", "1", "--steps-per-epoch", str(TRAIN_STEPS),
            "-i", "1", "-c", ckpt, "--log-interval", "1",
            "--metrics-jsonl", os.path.join(work, "train_metrics.jsonl")]
    if phase == "serve":
        return SERVE_CLI, [
            *GPT2_SMALL, "--model-max-len", "1024", "--dtype", "bf16",
            "-c", ckpt, "--max-batch", "4",
            "--max-new-tokens", str(MAX_NEW_TOKENS), "--json"]
    if phase == "image":
        return IMAGE_CLI, [
            "--model", "resnet50", "--dataset", "synthetic_imagenet",
            "--dtype", "bf16", "-b", "128", "-e", "1",
            "--steps-per-epoch", str(IMAGE_STEPS), "-i", "0",
            "-c", os.path.join(work, "image_ckpt"), "--log-interval", "1",
            "--metrics-jsonl", os.path.join(work, "image_metrics.jsonl")]
    raise ValueError(f"unknown phase {phase!r}")


# -- child: one phase, holding the chip --------------------------------------

def run_phase(phase: str, work: str) -> int:
    """Child process: say which device this is, refuse anything but a known
    TPU, then run the phase's CLI as ``__main__`` in this process."""
    import importlib.metadata
    import runpy

    import jax
    import jaxlib
    from jax import monitoring
    from jax.experimental import pallas as pl

    from distributed_training_tpu.observability.flops import (
        device_peak_flops,
    )
    from distributed_training_tpu.runtime.backend import (
        device_summary,
        enable_compile_cache,
        require_tpu,
    )

    cache_dir = enable_compile_cache()
    device = device_summary()
    peak = device_peak_flops()
    print(json.dumps({
        "phase": phase, "platform": device["platform"],
        "device_kind": device["kind"], "device_count": device["count"],
        "jax": jax.__version__, "jaxlib": jaxlib.__version__,
        "libtpu": importlib.metadata.version("libtpu"),
        "peak_bf16_flops": peak, "compile_cache": cache_dir}), flush=True)
    require_tpu(f"chip_smoke[{phase}]")
    if peak is None:
        raise SystemExit(
            f"chip_smoke[{phase}]: device_kind {device['kind']!r} has no "
            f"peak in observability/flops.py (an unknown kind is an error, "
            f"never a default)")

    # Set-up accounting: backend-compile seconds (cache retrievals
    # included) and persistent-cache hits / entries written.
    stats = {"compile_s": 0.0, "cache_hits": 0, "cache_writes": 0,
             "pallas_calls": 0}

    def on_duration(event: str, seconds: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            stats["compile_s"] += seconds

    def on_event(event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            stats["cache_hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            stats["cache_writes"] += 1

    monitoring.register_event_duration_secs_listener(on_duration)
    monitoring.register_event_listener(on_event)

    # Every Pallas call traced in this process: counted, and refused if
    # it asks for the interpreter (utils/compat.py refuses it too; this
    # also catches a call that bypasses that helper).
    real_pallas_call = pl.pallas_call

    def counting_pallas_call(*args, **kwargs):
        if kwargs.get("interpret"):
            raise RuntimeError("pallas_call(interpret=True) on the chip")
        stats["pallas_calls"] += 1
        return real_pallas_call(*args, **kwargs)

    pl.pallas_call = counting_pallas_call

    script, argv = phase_argv(phase, work)
    sys.argv = [script, *argv]
    t0 = time.monotonic()
    try:
        runpy.run_path(os.path.join(ROOT, script), run_name="__main__")
    except SystemExit as e:
        if e.code not in (None, 0):
            raise  # the CLI's own failure is this child's failure
    if phase == "train":
        from distributed_training_tpu.resilience import verify

        epoch_dir = os.path.join(work, "gpt_ckpt", "epoch_0")
        if not verify.is_committed(epoch_dir):
            raise SystemExit(f"chip_smoke[train]: {epoch_dir} not COMMITTED")
        verify.verify_checkpoint(epoch_dir)  # raises CheckpointCorruptError
        if stats["pallas_calls"] == 0:
            raise SystemExit("chip_smoke[train]: no Pallas kernel was "
                             "traced — flash attention did not run")
    print(json.dumps({
        "phase": phase, "wall_s": round(time.monotonic() - t0, 1),
        "compile_s": round(stats["compile_s"], 1),
        "cache_hits": stats["cache_hits"],
        "cache_writes": stats["cache_writes"],
        "pallas_calls": stats["pallas_calls"]}), flush=True)
    return 0


# -- parent: never touches jax -----------------------------------------------

class SmokeFailure(Exception):
    pass


def spawn_phase(phase: str, work: str, env: dict, stdin_text: str,
                timeout_s: float) -> tuple[list, str]:
    """Run one phase child to its end; returns (stdout lines, stderr). The
    child leads its own process group so a timeout stops everything it
    started."""
    print(f"[chip_smoke] phase {phase}: starting", file=sys.stderr,
          flush=True)
    t0 = time.monotonic()
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--phase", phase, work],
        cwd=ROOT, env=env, stdin=subprocess.PIPE, stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, text=True, start_new_session=True)
    try:
        out, err = proc.communicate(stdin_text, timeout=timeout_s)
    except subprocess.TimeoutExpired:
        os.killpg(proc.pid, signal.SIGKILL)
        out, err = proc.communicate()
        sys.stderr.write(err[-8000:])
        raise SmokeFailure(
            f"phase {phase} still running after {timeout_s:.0f} s (what "
            f"was left of the {TOTAL_BUDGET_S} s budget)")
    sys.stderr.write(err[-8000:])
    sys.stderr.flush()
    print(f"[chip_smoke] phase {phase}: exit {proc.returncode} after "
          f"{time.monotonic() - t0:.1f} s (process wall, set-up included)",
          file=sys.stderr, flush=True)
    if proc.returncode != 0:
        sys.stderr.write(out[-4000:])
        raise SmokeFailure(f"phase {phase} exited {proc.returncode}")
    return out.splitlines(), err


def json_lines(lines: list) -> list:
    rows = []
    for ln in lines:
        if ln.startswith("{"):
            try:
                rows.append(json.loads(ln))
            except json.JSONDecodeError:
                pass
    return rows


def losses_from(path: str) -> list:
    with open(path) as fh:
        rows = [json.loads(ln) for ln in fh if ln.strip()]
    return [r["loss"] for r in rows if r.get("prefix") == "train"]


def check_losses(phase: str, losses: list, want: int, falling: bool) -> None:
    if len(losses) < want:
        raise SmokeFailure(
            f"{phase}: {len(losses)} logged steps, expected {want}")
    if not all(math.isfinite(x) for x in losses):
        raise SmokeFailure(f"{phase}: non-finite loss in {losses}")
    # Single steps are noisy this early; compare quarter means.
    q = max(len(losses) // 4, 1)
    if falling and not sum(losses[-q:]) / q < sum(losses[:q]) / q:
        raise SmokeFailure(f"{phase}: loss did not fall: {losses}")
    print(f"[chip_smoke] {phase}: loss {losses[0]:.4f} -> {losses[-1]:.4f} "
          f"over {len(losses)} steps", file=sys.stderr)


def check_serve(rows: list, lines: list, err: str, work: str) -> None:
    ckpt = os.path.join(work, "gpt_ckpt")
    if f"restored epoch 0 from {ckpt}" not in err:
        raise SmokeFailure("serve: did not restore the epoch just written")
    done = [ln for ln in lines if ln.startswith("[serve] #")]
    if len(done) != len(PROMPTS):
        raise SmokeFailure(
            f"serve: {len(done)} completions for {len(PROMPTS)} prompts")
    bad = [ln for ln in done
           if not ln.split("(", 1)[1].startswith(("length,", "eos,"))]
    if bad:
        raise SmokeFailure(f"serve: unfinished request(s): {bad}")
    sla = [r for r in rows if "requests_finished" in r]
    if not sla or sla[-1]["requests_finished"] != len(PROMPTS):
        raise SmokeFailure(f"serve: SLA row missing or short: {sla}")
    if sla[-1]["tokens_emitted"] <= 0:
        raise SmokeFailure("serve: no tokens emitted")
    first, second = (ln.split(" -> ", 1)[1] for ln in done[:2])
    if first != second:
        raise SmokeFailure(
            f"serve: identical greedy prompts diverged: {first} vs {second}")
    print(f"[chip_smoke] serve: {len(done)} requests finished, "
          f"{sla[-1]['tokens_emitted']} tokens, pool balanced, epoch 0 "
          f"restored", file=sys.stderr)


def main() -> int:
    requested = os.environ.get("JAX_PLATFORMS", "")
    if requested and requested.split(",")[0].strip().lower() != "tpu":
        print(f"chip_smoke: JAX_PLATFORMS={requested!r} — this check runs "
              f"on a TPU only (no CPU mode); nothing started",
              file=sys.stderr)
        return 2
    missing = [p for p in (TRAIN_CLI, SERVE_CLI, IMAGE_CLI,
                           "distributed_training_tpu")
               if not os.path.exists(os.path.join(ROOT, p))]
    if missing:
        print(f"chip_smoke: not a checkout of the repo (missing "
              f"{', '.join(missing)}); nothing started", file=sys.stderr)
        return 2

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "tpu"  # JAX may not fall back to the CPU
    env.pop("OBS_PEAK_FLOPS", None)  # the peaks table must know the chip
    # The GPT-2-small checkpoint (params + Adam state, ~1.5 GB) lives
    # outside the checkout — the chip tool re-copies the tree on every
    # call — and is removed at the end.
    work = tempfile.mkdtemp(prefix="chip_smoke_")
    deadline = time.monotonic() + TOTAL_BUDGET_S
    devices = set()
    try:
        for phase in ("train", "serve", "image"):
            stdin_text = "\n".join(PROMPTS) + "\n" if phase == "serve" else ""
            lines, err = spawn_phase(phase, work, env, stdin_text,
                                     max(deadline - time.monotonic(), 1.0))
            rows = json_lines(lines)
            head = next((r for r in rows if "device_kind" in r), None)
            tail = next((r for r in reversed(rows) if "compile_s" in r),
                        None)
            if head is None or tail is None:
                raise SmokeFailure(f"{phase}: device/set-up lines missing")
            print(json.dumps(head))
            print(json.dumps(tail))
            devices.add((head["platform"], head["device_kind"],
                         head["device_count"]))
            if phase == "train":
                check_losses("train", losses_from(
                    os.path.join(work, "train_metrics.jsonl")),
                    TRAIN_STEPS, falling=True)
            elif phase == "serve":
                check_serve(rows, lines, err, work)
            else:
                check_losses("image", losses_from(
                    os.path.join(work, "image_metrics.jsonl")),
                    IMAGE_STEPS, falling=False)
                # Which augment path fed the step (ops/native/native.py
                # says it once; it may share a line with a progress bar).
                native = re.search(r"\[native\] augment: [^\r\n]*", err)
                if native is None:
                    raise SmokeFailure("image: the augment path in use was "
                                       "not reported")
                print(f"[chip_smoke] image: {native.group(0)}",
                      file=sys.stderr)
        if len(devices) != 1:
            raise SmokeFailure(f"phases saw different devices: {devices}")
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED — {e}", file=sys.stderr)
        return 1
    finally:
        shutil.rmtree(work, ignore_errors=True)
    platform, kind, count = devices.pop()
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": kind, "count": count}}))
    return 0


if __name__ == "__main__":
    if len(sys.argv) == 4 and sys.argv[1] == "--phase":
        sys.exit(run_phase(sys.argv[2], sys.argv[3]))
    sys.exit(main())
