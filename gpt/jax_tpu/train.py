"""Transformer-LM trainer CLI (JAX/TPU backend, sibling-directory layout).

The reference's plugin boundary is a directory per backend under the
workload dir (``resnet/{pytorch_ddp,deepspeed,colossal}``, SURVEY.md §1 L1);
this directory extends the same layout to the framework's long-context LM
workload — a model family the reference does not have (SURVEY.md §5
"Long-context": absent).

The parallel strategy is the mesh: ``--sp 4`` rings the sequence over 4
devices, ``--tp 4`` megatron-shards the layers, ``--pp 4`` pipelines them;
the rest of the devices form the data axis. ZeRO stages compose with TP/DP
via ``--stage``.
"""

from __future__ import annotations

import argparse
import dataclasses
import os
import sys

# Script-style backend dir (like serve.py): make the package importable
# when run from anywhere, not just with PYTHONPATH set.
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def add_argument() -> argparse.Namespace:
    parser = argparse.ArgumentParser(description="TransformerLM on TPU")
    parser.add_argument("-b", "--batch_size", type=int, default=32,
                        help="per-data-shard batch size")
    parser.add_argument("-e", "--epochs", type=int, default=5)
    parser.add_argument("--gradient-accumulation-steps", type=int, default=1,
                        help="microbatches per optimizer update (tensor/dp "
                             "strategy; effective batch scales by this)")
    parser.add_argument("--remat", action="store_true", default=False,
                        help="activation-checkpoint each decoder block")
    parser.add_argument("--ema-decay", type=float, default=None,
                        help="parameter EMA decay; eval uses the average")
    parser.add_argument("--seq-len", type=int, default=128)
    parser.add_argument("--vocab-size", type=int, default=256)
    parser.add_argument("--num-layers", type=int, default=4)
    parser.add_argument("--num-heads", type=int, default=4)
    parser.add_argument("--hidden-dim", type=int, default=256)
    parser.add_argument("--max-len", type=int, default=2048)
    parser.add_argument("--corpus", type=str, default=None,
                        help="byte-level text file; default synthetic tokens")
    parser.add_argument("--attn-impl", type=str, default="exact",
                        choices=["exact", "flash"],
                        help="flash = Pallas blockwise kernel; under --sp it "
                             "becomes the per-hop ring compute")
    parser.add_argument("--ce-chunk-size", type=int, default=None,
                        help="chunked cross-entropy: tokens per lm_head+CE "
                             "chunk (never materializes [B,T,vocab] logits; "
                             "for long-context × large-vocab runs)")
    parser.add_argument("--logits-dtype", type=str, default="bf16",
                        choices=["fp32", "bf16"],
                        help="head/logits compute dtype. Default bf16 "
                             "(round 5): halves the [B,T,vocab] HBM "
                             "traffic, CE still reduces in fp32, and 3- "
                             "and 8-epoch chip A/Bs track fp32 step-for-"
                             "step (final ppl 1.0784 vs 1.0785); fp32 "
                             "remains selectable")
    parser.add_argument("--ce-save-probs", action="store_true", default=False,
                        help="CE backward from saved bf16 softmax probs: "
                             "+2%% tok/s under --logits-dtype fp32 (its "
                             "niche); refused with --ce-chunk-size, warns "
                             "under bf16 logits (measured slower there)")
    parser.add_argument("--head-bias", action=argparse.BooleanOptionalAction,
                        default=False,
                        help="lm_head bias. Default off (round 5): GPT-2's "
                             "real head has none, and its gradient costs a "
                             "full HBM pass over the [B,T,vocab] logits")
    # MoE surface (DeepSpeed flag names, resnet/deepspeed parity) — here
    # they swap alternating decoder FFNs for expert-parallel MoE layers.
    parser.add_argument("--moe", action="store_true", default=False)
    parser.add_argument("--ep-world-size", type=int, default=1,
                        help="expert mesh axis size")
    parser.add_argument("--num-experts", type=int, nargs="+", default=[8])
    parser.add_argument("--moe-every", type=int, default=2,
                        help="swap every Nth decoder FFN for MoE (GShard "
                             "alternating at 2); 1 = every layer — the "
                             "homogeneous layout the pipeline strategy "
                             "(--pp) can carry")
    parser.add_argument("--top-k", type=int, default=1)
    parser.add_argument("--min-capacity", type=int, default=0)
    parser.add_argument("--noisy-gate-policy", type=str, default=None,
                        choices=[None, "RSample", "Jitter"])
    parser.add_argument("--mlp-type", type=str, default="standard",
                        choices=["standard", "residual"])
    parser.add_argument("--dtype", type=str, default="fp32",
                        choices=["bf16", "fp16", "fp32"])
    parser.add_argument("--stage", type=int, default=0, choices=[0, 1, 2, 3],
                        help="ZeRO stage (composes with --tp / pure DP)")
    parser.add_argument("--tp", type=int, default=1,
                        help="tensor-parallel (model axis) size")
    parser.add_argument("--tp-overlap", action="store_true", default=False,
                        help="ring-overlapped tensor parallelism: decompose "
                             "the megatron layer collectives into ppermute "
                             "rings fused with the partial matmuls "
                             "(latency-hiding collective matmul; needs "
                             "--tp > 1 to do anything, and seq_len/--sp "
                             "divisible by --tp)")
    parser.add_argument("--pp", type=int, default=1,
                        help="pipeline-parallel (pipe axis) size")
    parser.add_argument("--sp", type=int, default=1,
                        help="sequence-parallel (ring) size")
    parser.add_argument("--virtual-stages", type=int, default=1,
                        help="interleaved/circular pipeline: layer chunks "
                             "per pipe device (1 = GPipe); cuts the bubble "
                             "to (S-1)/(v*M+S-1)")
    parser.add_argument("--microbatches", type=int, default=2,
                        help="GPipe microbatches (only with --pp)")
    parser.add_argument("-c", "--checkpoint", type=str, default="./checkpoint")
    parser.add_argument("-i", "--interval", type=int, default=5)
    parser.add_argument("-r", "--resume", type=int, default=-1)
    parser.add_argument("--log-interval", type=int, default=50)
    parser.add_argument("--steps-per-epoch", type=int, default=None)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--wall-clock-breakdown", action="store_true")
    parser.add_argument("--profile-dir", type=str, default=None)
    parser.add_argument("--auto-resume", action="store_true", default=False,
                        help="resume from the newest checkpoint if present")
    parser.add_argument("--tensorboard-dir", type=str, default=None)
    parser.add_argument("--metrics-jsonl", type=str, default=None)
    # Observability (flight instruments; docs/OBSERVABILITY.md).
    parser.add_argument("--flight-recorder",
                        action=argparse.BooleanOptionalAction, default=True,
                        help="ring buffer of per-step timestamps + flushed "
                             "metrics; step-time p50/p95 + goodput, dumped "
                             "to JSON on anomaly/crash (read it with "
                             "tools/flight_report.py)")
    parser.add_argument("--flight-dir", type=str, default=None,
                        help="where anomaly/crash forensics land (flight "
                             "JSON, offending batch, HLO, profiler trace)")
    parser.add_argument("--trace", action=argparse.BooleanOptionalAction,
                        default=False,
                        help="span-level Perfetto trace: step/eval/ckpt "
                             "phases, the async checkpoint writer's own "
                             "track, chaos injections — written at run "
                             "end (open in ui.perfetto.dev, or summarize "
                             "with tools/trace_report.py)")
    parser.add_argument("--trace-dir", type=str, default=None,
                        help="trace output directory (default: "
                             "<flight dir>/trace)")
    parser.add_argument("--metrics-port", type=int, default=None,
                        help="live telemetry plane: serve /metrics "
                             "(Prometheus text), /healthz and /vars from "
                             "a background thread on this port while the "
                             "run is alive (loopback; 0 = ephemeral; "
                             "master process only). Scrapes read cached "
                             "host-side summaries — never a device value")
    parser.add_argument("--grad-norm-metric", action="store_true",
                        default=False,
                        help="global L2 grad norm as an on-device step "
                             "metric (no extra host syncs; also arms the "
                             "anomaly detector's spike rule)")
    parser.add_argument("--anomaly-detection", action="store_true",
                        default=False,
                        help="NaN/Inf-loss + grad-norm-spike detection at "
                             "meter flushes; on trigger: flight dump + "
                             "batch/HLO save + N-step profiler trace, then "
                             "--anomaly-action")
    parser.add_argument("--anomaly-action", default="raise",
                        choices=["raise", "skip"])
    parser.add_argument("--anomaly-trace-steps", type=int, default=3,
                        help="profiler-trace steps captured after an "
                             "anomaly trigger (0 = no trace)")
    add_chaos_arguments(parser)
    return parser.parse_args()


def add_chaos_arguments(parser: argparse.ArgumentParser) -> None:
    """Deterministic fault injection (resilience/chaos.py;
    docs/RESILIENCE.md). All defaults inert. resnet/jax_tpu/train.py
    mirrors this flag group inline (the backend dirs are deliberately
    self-contained scripts, like the observability flags) — keep the
    two in sync when adding knobs."""
    parser.add_argument("--chaos-seed", type=int, default=0)
    parser.add_argument("--chaos-kill-at-step", type=int, default=None,
                        help="deliver --chaos-kill-signal from inside the "
                             "step loop at this global step (simulated "
                             "TPU eviction)")
    parser.add_argument("--chaos-kill-signal", type=str, default="sigterm",
                        choices=["sigterm", "kill"],
                        help="sigterm = graceful grace-window eviction "
                             "(preemption save); kill = SIGKILL, hard "
                             "death with no save")
    parser.add_argument("--chaos-torn-ckpt-epoch", type=int, default=None,
                        help="after this epoch's checkpoint save lands, "
                             "truncate it and drop its COMMITTED marker "
                             "(torn write; auto-resume must fall back)")
    parser.add_argument("--chaos-torn-bytes", type=int, default=64,
                        help="bytes to leave in the torn file")
    parser.add_argument("--chaos-corrupt-ckpt-epoch", type=int,
                        default=None,
                        help="tear-AFTER-commit: corrupt this epoch's "
                             "save payload while keeping its COMMITTED "
                             "marker (checksum-level bit rot; the "
                             "hot-swap watcher's verify stage must "
                             "quarantine it)")
    parser.add_argument("--chaos-data-error-rate", type=float, default=0.0,
                        help="seeded per-key probability of a one-shot "
                             "transient data-read error (the retry "
                             "policy must absorb it)")
    parser.add_argument("--chaos-slow-step-every", type=int, default=None,
                        help="inject a host stall every N steps "
                             "(straggler simulation)")
    parser.add_argument("--chaos-slow-step-ms", type=float, default=50.0)
    parser.add_argument("--chaos-slow-step-host", type=int, default=None,
                        help="restrict the slow-step injection to this "
                             "process index (multihost straggler drill: "
                             "one slow host for the flight aggregation "
                             "to attribute); default: every host")


def chaos_config_from_flags(args: argparse.Namespace):
    from distributed_training_tpu.config import ChaosConfig

    return ChaosConfig(
        seed=args.chaos_seed,
        kill_at_step=args.chaos_kill_at_step,
        kill_signal=args.chaos_kill_signal,
        torn_ckpt_epoch=args.chaos_torn_ckpt_epoch,
        torn_truncate_bytes=args.chaos_torn_bytes,
        corrupt_ckpt_epoch=args.chaos_corrupt_ckpt_epoch,
        data_error_rate=args.chaos_data_error_rate,
        slow_step_every=args.chaos_slow_step_every,
        slow_step_ms=args.chaos_slow_step_ms,
        slow_step_host=args.chaos_slow_step_host,
    )


def build_config(args: argparse.Namespace):
    from distributed_training_tpu.config import (
        CheckpointConfig,
        DataConfig,
        LMConfig,
        MeshSpec,
        MoEConfig,
        ObservabilityConfig,
        TraceConfig,
        TrainConfig,
        ZeroConfig,
    )

    cfg = TrainConfig(model="transformer_lm")
    if args.ema_decay is not None:
        cfg = cfg.replace(
            optimizer=dataclasses.replace(
                cfg.optimizer, ema_decay=args.ema_decay))
    return cfg.replace(
        moe=MoEConfig(
            enabled=args.moe,
            ep_world_size=args.ep_world_size,
            num_experts=tuple(args.num_experts),
            every=args.moe_every,
            top_k=args.top_k,
            min_capacity=args.min_capacity,
            noisy_gate_policy=args.noisy_gate_policy,
            mlp_type=args.mlp_type,
        ),
        num_epochs=args.epochs,
        gradient_accumulation_steps=args.gradient_accumulation_steps,
        remat=args.remat,
        tp_overlap=args.tp_overlap,
        seed=args.seed,
        log_interval=args.log_interval,
        wall_clock_breakdown=args.wall_clock_breakdown,
        profile_dir=args.profile_dir,
        tensorboard_dir=args.tensorboard_dir,
        metrics_jsonl=args.metrics_jsonl,
        observability=ObservabilityConfig(
            flight_recorder=args.flight_recorder,
            dump_dir=args.flight_dir,
            metrics_port=args.metrics_port,
            grad_norm=args.grad_norm_metric or args.anomaly_detection,
            anomaly_detection=args.anomaly_detection,
            anomaly_action=args.anomaly_action,
            anomaly_trace_steps=args.anomaly_trace_steps,
            trace=TraceConfig(enabled=args.trace, dir=args.trace_dir),
        ),
        chaos=chaos_config_from_flags(args),
        precision=dataclasses.replace(cfg.precision, dtype=args.dtype),
        zero=ZeroConfig(stage=args.stage),
        # expert gated on --moe: a dense run must keep the full data axis
        # (an expert axis under a dense model would just replicate compute).
        mesh=MeshSpec(data=-1, model=args.tp, pipe=args.pp, sequence=args.sp,
                      expert=args.ep_world_size if args.moe else 1),
        checkpoint=CheckpointConfig(
            directory=args.checkpoint,
            interval=args.interval,
            resume=args.resume,
            auto_resume=args.auto_resume,
        ),
        data=DataConfig(
            batch_size=args.batch_size,
            max_steps_per_epoch=args.steps_per_epoch,
        ),
        lm=LMConfig(
            seq_len=args.seq_len,
            vocab_size=args.vocab_size,
            num_layers=args.num_layers,
            num_heads=args.num_heads,
            hidden_dim=args.hidden_dim,
            max_len=args.max_len,
            num_microbatches=args.microbatches,
            virtual_stages=args.virtual_stages,
            attn_impl=args.attn_impl,
            ce_chunk_size=args.ce_chunk_size,
            ce_save_probs=args.ce_save_probs,
            logits_dtype=args.logits_dtype,
            head_bias=args.head_bias,
            corpus_path=args.corpus,
        ),
    )


def main() -> int:
    args = add_argument()

    from distributed_training_tpu.runtime.backend import enable_compile_cache
    from distributed_training_tpu.runtime.distributed import (
        initialize_distributed,
    )
    from distributed_training_tpu.train.lm_trainer import LMTrainer

    enable_compile_cache()
    initialize_distributed()
    cfg = build_config(args)
    trainer = LMTrainer(cfg)
    result = trainer.fit()
    trainer.coord.print(f"[done] {result}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
