"""JAX/TPU backend trainer — sibling of the reference's per-backend dirs.

The per-backend-directory layout IS the plugin boundary
(``resnet/{pytorch_ddp,deepspeed,colossal}/`` in the reference;
BASELINE.json north star: "a JAX/TPU backend added as a sibling"). This CLI
subsumes the union of all three reference trainers' surfaces:

- DDP style (``resnet/pytorch_ddp/ddp_train.py:107-114``): defaults —
  5 epochs, batch 100/device, Adam lr 1e-3 × world_size.
- DeepSpeed style (``resnet/deepspeed/deepspeed_train.py:27-129``):
  ``--dtype``, ``--stage``, the full MoE flag set, ``--log-interval``,
  ``--deepspeed``/``--deepspeed_config`` passthrough, and the in-code
  ds_config dict (``:172-220``) ingested via ``from_ds_config``.
- ColossalAI style (``resnet/colossal/colossal_train.py:30-50``):
  ``-p/--plugin``, ``-r/--resume``, ``-c/--checkpoint``, ``-i/--interval``,
  ``--target_acc`` — all functional here (the reference parses but never
  wires resume/checkpoint/target_acc; SURVEY.md §2.5).

Unlike the reference there is no per-rank process fan-out (``mp.spawn``) —
JAX is one process per host; multi-host runs call
``initialize_distributed()`` from the launcher env (RANK/WORLD_SIZE/
MASTER_ADDR), and all device parallelism lives in the compiled mesh program.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys

# Script-style backend dir (like serve.py): make the package importable
# when run from anywhere, not just with PYTHONPATH set.
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def add_argument() -> argparse.Namespace:
    parser = argparse.ArgumentParser(description="CIFAR on TPU (JAX backend)")

    # -- model / plugin (Colossal style) ------------------------------------
    parser.add_argument("-p", "--plugin", type=str, default="torch_ddp",
                        choices=["torch_ddp", "torch_ddp_fp16",
                                 "low_level_zero", "gemini", "deepspeed"],
                        help="parallelism plugin to use")
    parser.add_argument("--model", type=str, default="resnet18",
                        help="model name from the registry")
    parser.add_argument("-r", "--resume", type=int, default=-1,
                        help="resume from the epoch's checkpoint")
    parser.add_argument("-c", "--checkpoint", type=str, default="./checkpoint",
                        help="checkpoint directory")
    parser.add_argument("-i", "--interval", type=int, default=5,
                        help="interval of saving checkpoint (epochs)")
    parser.add_argument("--precise-bn-batches", type=int, default=0,
                        help="refresh BatchNorm running stats with N "
                             "train-mode forwards before each eval (the EMA "
                             "stats lag fast-moving params; 0 = raw stats)")
    parser.add_argument("--target_acc", type=float, default=None,
                        help="target accuracy; raise if not reached")
    parser.add_argument("--local-rank", "--local_rank", type=int, default=-1,
                        help="accepted for launcher compat; unused (JAX is "
                             "one process per host)")

    # -- train (DeepSpeed style) --------------------------------------------
    parser.add_argument("-b", "--batch_size", type=int, default=100,
                        help="per-device mini-batch size")
    parser.add_argument("-e", "--epochs", type=int, default=5,
                        help="number of total epochs")
    parser.add_argument("--gradient-accumulation-steps", type=int, default=1,
                        help="microbatches accumulated per optimizer update "
                             "(effective batch = batch_size × world × this)")
    parser.add_argument("--label-smoothing", type=float, default=0.0,
                        help="uniform label smoothing for the train CE")
    parser.add_argument("--remat", action="store_true", default=False,
                        help="activation checkpointing per block (fit "
                             "bigger batches; ~30%% extra backward FLOPs)")

    # -- optimizer overrides (None = keep the plugin preset) ----------------
    parser.add_argument("--optimizer", type=str, default=None,
                        choices=["adam", "adamw", "sgd", "lamb",
                                 "hybrid_adam"])
    parser.add_argument("--lr", type=float, default=None)
    parser.add_argument("--momentum", type=float, default=None,
                        help="SGD momentum (sgd only)")
    parser.add_argument("--nesterov", action="store_true", default=False)
    parser.add_argument("--weight-decay", type=float, default=None)
    parser.add_argument("--weight-decay-mask", type=str, default=None,
                        choices=["all", "no_1d"],
                        help="no_1d = don't decay biases/norm params "
                             "(ImageNet recipe)")
    parser.add_argument("--ema-decay", type=float, default=None,
                        help="parameter EMA decay (e.g. 0.9999); eval uses "
                             "the averaged params")
    parser.add_argument("--log-interval", type=int, default=100,
                        help="steps between metric fetches/logs")
    parser.add_argument("--dtype", type=str, default="fp32",
                        choices=["bf16", "fp16", "fp32"],
                        help="compute datatype")
    parser.add_argument("--stage", type=int, default=0, choices=[0, 1, 2, 3],
                        help="ZeRO optimization stage (deepspeed plugin)")
    parser.add_argument("--deepspeed", action="store_true", default=False,
                        help="accepted for launcher compat (config comes "
                             "from --deepspeed_config / built-in defaults)")
    parser.add_argument("--deepspeed_config", type=str, default=None,
                        help="path to a DeepSpeed-style JSON config to ingest")

    # -- MoE (DeepSpeed style, deepspeed_train.py:61-106) -------------------
    parser.add_argument("--moe", action="store_true", default=False,
                        help="use mixture of experts")
    parser.add_argument("--ep-world-size", type=int, default=1,
                        help="(moe) expert parallel world size")
    parser.add_argument("--num-experts", type=int, nargs="+", default=[1],
                        help="number of experts list, MoE related.")
    parser.add_argument("--mlp-type", type=str, default="standard",
                        help="only applicable when num-experts > 1; "
                             "accepts [standard, residual]")
    parser.add_argument("--top-k", type=int, default=1,
                        help="(moe) gating top 1 and 2 supported")
    parser.add_argument("--min-capacity", type=int, default=0,
                        help="(moe) minimum expert capacity")
    parser.add_argument("--noisy-gate-policy", type=str, default=None,
                        help="(moe) None, RSample, or Jitter")
    parser.add_argument("--moe-param-group", action="store_true",
                        default=False,
                        help="(moe) separate moe param groups for ZeRO")

    # -- data / misc --------------------------------------------------------
    parser.add_argument("--dataset", type=str, default="cifar10",
                        choices=["cifar10", "synthetic_cifar",
                                 "synthetic_cifar_hard",
                                 "synthetic_imagenet", "imagefolder"])
    parser.add_argument("--data-path", type=str, default=None,
                        help="dataset root (default: $DATA or ../data); "
                             "imagefolder expects <root>/train and "
                             "<root>/val class-directory trees")
    parser.add_argument("--decoded-cache", action="store_true", default=False,
                        help="(imagefolder) decode the tree once into a "
                             "uint8 memmap cache under <root>/.decoded_cache "
                             "and serve epochs from it — decode-bound hosts "
                             "become augment-bound (DALI-cache analogue)")
    parser.add_argument("--image-size", type=int, default=None,
                        help="square input size (default: 224 for "
                             "imagenet-style datasets, 32 for CIFAR)")
    parser.add_argument("--num-classes", type=int, default=None,
                        help="label count (default by dataset)")
    parser.add_argument("--steps-per-epoch", type=int, default=None,
                        help="cap train steps per epoch (smoke runs)")
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--wall-clock-breakdown", action="store_true",
                        default=False)
    parser.add_argument("--profile-dir", type=str, default=None,
                        help="jax.profiler trace output directory")
    parser.add_argument("--auto-resume", action="store_true", default=False,
                        help="resume from the newest checkpoint if present "
                             "(pairs with SIGTERM preemption saves)")
    parser.add_argument("--tensorboard-dir", type=str, default=None,
                        help="TensorBoard scalar log directory")
    parser.add_argument("--metrics-jsonl", type=str, default=None,
                        help="append metric flushes to this JSONL file")
    # Observability (flight instruments; docs/OBSERVABILITY.md). Same
    # surface as gpt/jax_tpu/train.py.
    parser.add_argument("--flight-recorder",
                        action=argparse.BooleanOptionalAction, default=True,
                        help="ring buffer of per-step timestamps + flushed "
                             "metrics (step-time percentiles, goodput; "
                             "dumped on anomaly/crash)")
    parser.add_argument("--flight-dir", type=str, default=None,
                        help="anomaly/crash forensics directory")
    parser.add_argument("--trace", action=argparse.BooleanOptionalAction,
                        default=False,
                        help="span-level Perfetto trace (step/eval/ckpt "
                             "phases, ckpt-writer track, chaos marks); "
                             "summarize with tools/trace_report.py")
    parser.add_argument("--trace-dir", type=str, default=None,
                        help="trace output directory (default: "
                             "<flight dir>/trace)")
    parser.add_argument("--metrics-port", type=int, default=None,
                        help="live telemetry plane: /metrics (Prometheus "
                             "text), /healthz and /vars served from a "
                             "background thread on this port while the "
                             "run is alive (loopback; 0 = ephemeral; "
                             "master process only)")
    parser.add_argument("--grad-norm-metric", action="store_true",
                        default=False,
                        help="global L2 grad norm as an on-device metric")
    parser.add_argument("--anomaly-detection", action="store_true",
                        default=False,
                        help="NaN/Inf-loss + grad-norm-spike detection at "
                             "meter flushes (flight dump + batch/HLO + "
                             "profiler trace on trigger)")
    parser.add_argument("--anomaly-action", default="raise",
                        choices=["raise", "skip"])
    parser.add_argument("--anomaly-trace-steps", type=int, default=3)

    # Chaos harness (resilience/chaos.py; docs/RESILIENCE.md) — mirrors
    # gpt/jax_tpu/train.py::add_chaos_arguments (backend dirs are
    # self-contained scripts; keep in sync). All defaults inert.
    parser.add_argument("--chaos-seed", type=int, default=0)
    parser.add_argument("--chaos-kill-at-step", type=int, default=None,
                        help="deliver --chaos-kill-signal at this global "
                             "step (simulated TPU eviction)")
    parser.add_argument("--chaos-kill-signal", type=str, default="sigterm",
                        choices=["sigterm", "kill"])
    parser.add_argument("--chaos-torn-ckpt-epoch", type=int, default=None,
                        help="tear this epoch's save after it lands "
                             "(truncate + drop COMMITTED; auto-resume "
                             "must fall back)")
    parser.add_argument("--chaos-torn-bytes", type=int, default=64)
    parser.add_argument("--chaos-corrupt-ckpt-epoch", type=int,
                        default=None,
                        help="tear-AFTER-commit: corrupt this epoch's "
                             "save payload, COMMITTED marker intact "
                             "(checksum pass must catch it)")
    parser.add_argument("--chaos-data-error-rate", type=float, default=0.0,
                        help="seeded one-shot transient data-read faults "
                             "(the retry policy must absorb them)")
    parser.add_argument("--chaos-slow-step-every", type=int, default=None)
    parser.add_argument("--chaos-slow-step-ms", type=float, default=50.0)
    parser.add_argument("--chaos-slow-step-host", type=int, default=None,
                        help="restrict slow-step injection to this "
                             "process index (straggler drill)")

    return parser.parse_args()


# The DeepSpeed trainer's in-code engine config
# (resnet/deepspeed/deepspeed_train.py:172-220), reproduced as the default
# ds_config for the 'deepspeed' plugin; --dtype/--stage patch it exactly the
# way the reference's args do.
def default_ds_config(dtype: str, stage: int, batch_size: int) -> dict:
    return {
        "train_batch_size": batch_size,
        "steps_per_print": 2000,
        "optimizer": {
            "type": "Adam",
            "params": {
                "lr": 0.001,
                "betas": [0.8, 0.999],
                "eps": 1e-8,
                "weight_decay": 3e-7,
            },
        },
        "scheduler": {
            "type": "WarmupLR",
            "params": {
                "warmup_min_lr": 0,
                "warmup_max_lr": 0.001,
                "warmup_num_steps": 1000,
            },
        },
        "gradient_clipping": 1.0,
        "prescale_gradients": False,
        "bf16": {"enabled": dtype == "bf16"},
        "fp16": {
            "enabled": dtype == "fp16",
            "fp16_master_weights_and_grads": False,
            "loss_scale": 0,
            "loss_scale_window": 500,
            "hysteresis": 2,
            "min_loss_scale": 1,
            "initial_scale_power": 15,
        },
        "wall_clock_breakdown": False,
        "zero_optimization": {
            "stage": stage,
            "allgather_partitions": True,
            "reduce_scatter": True,
            "allgather_bucket_size": 50000000,
            "reduce_bucket_size": 50000000,
            "overlap_comm": True,
            "contiguous_gradients": True,
            "cpu_offload": False,
        },
    }


def build_config(args: argparse.Namespace):
    from distributed_training_tpu.config import (
        ChaosConfig,
        CheckpointConfig,
        DataConfig,
        MoEConfig,
        ObservabilityConfig,
        TraceConfig,
        TrainConfig,
        from_ds_config,
    )

    cfg = TrainConfig.from_plugin(args.plugin)

    if args.moe and not args.model.startswith("moe"):
        # The reference parses --moe but trains a dense ResNet regardless
        # (deepspeed_train.py:223); here the flag selects the MoE model.
        print(f"[moe] switching model {args.model!r} -> 'moe_mlp'")
        args.model = "moe_mlp"

    if args.plugin == "deepspeed":
        if args.deepspeed_config:
            with open(args.deepspeed_config) as fh:
                ds = json.load(fh)
        else:
            ds = default_ds_config(args.dtype, args.stage, args.batch_size)
        cfg = from_ds_config(ds, base=cfg)
    else:
        cfg = cfg.replace(
            precision=dataclasses.replace(cfg.precision, dtype=args.dtype)
            if args.dtype != "fp32" else cfg.precision)

    imagenet_style = args.dataset in ("synthetic_imagenet", "imagefolder")
    num_classes = args.num_classes or (1000 if imagenet_style else 10)
    image_size = args.image_size or (224 if imagenet_style else 32)
    augment = ("normalize_only" if args.plugin == "deepspeed"
               else "pad_crop_flip")  # DS normalizes; DDP/Colossal crop+flip

    cfg = cfg.replace(
        model=args.model,
        num_epochs=args.epochs,
        gradient_accumulation_steps=args.gradient_accumulation_steps,
        label_smoothing=args.label_smoothing,
        # --remat opts in; never clobber a remat=True the ds_config set.
        remat=args.remat or cfg.remat,
        seed=args.seed,
        log_interval=args.log_interval,
        target_acc=args.target_acc,
        eval_precise_bn_batches=args.precise_bn_batches,
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
        chaos=ChaosConfig(
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
        ),
        checkpoint=CheckpointConfig(
            directory=args.checkpoint,
            interval=args.interval,
            resume=args.resume,
            auto_resume=args.auto_resume,
        ),
        data=DataConfig(
            dataset=args.dataset,
            data_path=args.data_path,
            batch_size=args.batch_size,
            augment=augment,
            image_size=image_size,
            num_classes=num_classes,
            max_steps_per_epoch=args.steps_per_epoch,
            decoded_cache=args.decoded_cache,
        ),
        moe=MoEConfig(
            enabled=args.moe,
            ep_world_size=args.ep_world_size,
            num_experts=tuple(args.num_experts),
            mlp_type=args.mlp_type,
            top_k=args.top_k,
            min_capacity=args.min_capacity,
            noisy_gate_policy=args.noisy_gate_policy,
            moe_param_group=args.moe_param_group,
        ),
        # The Trainer engages expert sharding from the mesh, not MoEConfig
        # (train/trainer.py decides expert_axis from the realized mesh shape),
        # so --ep-world-size must size the expert axis here — matching the
        # gpt CLI's wiring. DeepSpeed's flag (deepspeed_train.py:64-66) has
        # the same contract: ep_world_size is the expert-parallel degree.
        # Gated on --moe: a dense run must keep the full data axis (an
        # expert axis under a dense model would just replicate compute).
        mesh=dataclasses.replace(
            cfg.mesh, expert=args.ep_world_size if args.moe else 1),
    )

    # Optimizer overrides on top of the plugin preset (None = keep preset).
    opt_overrides = {
        k: v for k, v in (
            ("name", args.optimizer),
            ("lr", args.lr),
            ("momentum", args.momentum),
            ("weight_decay", args.weight_decay),
            ("weight_decay_mask", args.weight_decay_mask),
            ("ema_decay", args.ema_decay),
        ) if v is not None
    }
    if args.nesterov:
        opt_overrides["nesterov"] = True
    if opt_overrides:
        cfg = cfg.replace(
            optimizer=dataclasses.replace(cfg.optimizer, **opt_overrides))
    return cfg


def main() -> int:
    args = add_argument()

    from distributed_training_tpu.runtime.backend import enable_compile_cache
    from distributed_training_tpu.runtime.distributed import (
        initialize_distributed,
    )
    from distributed_training_tpu.train.trainer import Trainer

    enable_compile_cache()
    initialize_distributed()  # no-op single-process; env-driven multi-host
    cfg = build_config(args)
    trainer = Trainer(cfg)
    result = trainer.fit()
    trainer.coord.print(f"[done] {result}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
