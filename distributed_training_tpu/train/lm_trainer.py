"""LMTrainer: end-to-end transformer-LM training over any mesh strategy.

The LM counterpart of :class:`~distributed_training_tpu.train.trainer.Trainer`
(the reference has no token workload at all — SURVEY.md §5 "Long-context";
this engine drives the framework's long-context extension as a first-class
product surface, not just library steps).

The parallel strategy follows from the mesh, not from a flag:

- ``pipe > 1``      → GPipe pipeline parallelism
  (``make_pp_lm_train_step``: stacked blocks sharded over ``pipe``; with
  ``sequence > 1`` too, ring attention runs INSIDE each tick — SP×PP,
  round 5);
- ``sequence > 1``  → ring-attention sequence parallelism
  (``make_lm_train_step``: shard_map, K/V blocks hop the ICI ring);
- otherwise         → the GSPMD step (``make_tp_lm_train_step``), which is
  megatron TP when ``model > 1`` and plain DP when ``model == 1``, with
  ZeRO stages composing on the free dims.

``model > 1`` composes with EITHER explicit strategy (TP×SP, PP×TP), and
``expert > 1`` with tensor/dp, sequence, and (homogeneous MoE) pipeline:
the explicit shard_maps are partial-manual — their own axes are manual
while ``model``/``expert`` stay automatic, so megatron/expert shardings
propagate inside the shards and GSPMD inserts the collectives there.
"""

from __future__ import annotations

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

from distributed_training_tpu import checkpoint as ckpt_lib
from distributed_training_tpu.config import TrainConfig, effective_batch_sizes
from distributed_training_tpu.data.lm_text import (
    TokenLoader,
    byte_corpus,
    synthetic_tokens,
)
from distributed_training_tpu.models import get_model
from distributed_training_tpu.parallel.sharding import place_state
from distributed_training_tpu.runtime.backend import device_banner
from distributed_training_tpu.runtime.coordinator import Coordinator
from distributed_training_tpu.runtime.mesh import (
    AXIS_MODEL,
    AXIS_PIPE,
    AXIS_SEQUENCE,
    MeshConfig,
    create_mesh,
    data_axis_size,
)
from distributed_training_tpu.train.lm_step import (
    make_lm_batch,
    model_logits_dtype,
    parse_logits_dtype,
    make_lm_train_step,
    make_pp_lm_train_step,
    make_tp_lm_train_step,
)
from distributed_training_tpu.train.optim import make_optimizer
from distributed_training_tpu.train.precision import LossScaleState, Policy
from distributed_training_tpu.train.train_state import (
    TrainState,
    init_train_state,
    param_count,
)
from distributed_training_tpu.utils.compat import on_tpu
from distributed_training_tpu.observability import (
    AnomalyError,
    TrainObservability,
    forward_flops,
    train_step_flops,
)
from distributed_training_tpu.observability import trace as trace_lib
from distributed_training_tpu.resilience import retry as retry_lib
from distributed_training_tpu.resilience import chaos as chaos_lib
from distributed_training_tpu.resilience.async_ckpt import (
    AsyncCheckpointWriter,
)
from distributed_training_tpu.resilience.chaos import ChaosMonkey
from distributed_training_tpu.runtime.preemption import PreemptionGuard
from distributed_training_tpu.utils.logging import EpochBar, MetricMeter
from distributed_training_tpu.utils.metrics_io import MetricsWriter
from distributed_training_tpu.utils.profiling import WallClock, trace


def restore_lm_checkpoint(directory: str, epoch: int, state, layout=None):
    """``checkpoint.restore_checkpoint`` with actionable LM diagnostics.

    The most common pytree-structure mismatch after round 5 is the
    head-bias default flip: pre-round-5 checkpoints carry an ``lm_head``
    bias the new bias-less template lacks, and orbax surfaces that as a raw
    tree-structure error. Name the flag (mirroring
    ``gpt/jax_tpu/generate.py``'s handler) instead of leaving the user to
    decode the pytree diff.
    """
    try:
        return ckpt_lib.restore_checkpoint(
            directory, epoch, state, layout=layout)
    except FileNotFoundError:
        raise  # missing checkpoint: not a model-tree problem
    except ckpt_lib.CheckpointCorruptError:
        raise  # typed corruption verdict already names dir + remedy
    except Exception as e:
        if isinstance(e, ValueError) and "PERMUTED" in str(e):
            raise  # the layout guard's own refusal is already actionable
        raise ValueError(
            f"checkpoint restore failed — if the original error below is a "
            f"tree-structure mismatch, the configured model must mirror "
            f"the training run's. Most likely: this build defaults to NO "
            f"lm_head bias (round 5); set lm.head_bias=True (--head-bias "
            f"on the CLI) to resume checkpoints trained before that, or "
            f"check num_layers/hidden_dim/vocab/MoE flags. (An I/O or "
            f"deserialization error instead means the checkpoint itself is "
            f"damaged.) Original error: {e}") from e


class LMTrainer:
    """Epoch-loop engine for :class:`TransformerLM` on a device mesh."""

    @trace_lib.span("setup.trainer_init")
    def __init__(self, cfg: TrainConfig, mesh=None):
        self.cfg = cfg
        self.coord = Coordinator()
        self.mesh = mesh if mesh is not None else create_mesh(
            MeshConfig(**dataclasses.asdict(cfg.mesh)))
        shape = dict(zip(self.mesh.axis_names, self.mesh.devices.shape))
        seq = shape.get(AXIS_SEQUENCE, 1)
        pipe = shape.get(AXIS_PIPE, 1)
        model_par = shape.get(AXIS_MODEL, 1)
        # pipe>1 selects the pipeline engine; a sequence axis composes
        # WITH it since round 5 (each pipeline tick runs ring attention
        # over the manual sequence axis inside the stage), so seq>1 alone
        # selects the plain ring strategy and seq×pipe goes through the
        # pipeline with a seq_axis model.
        self.strategy = ("pipeline" if pipe > 1 else
                         "sequence" if seq > 1 else
                         "tensor/dp")
        # model_par composes with EITHER explicit strategy: the sequence and
        # pipeline shard_maps are partial-manual (their own axes manual,
        # ``model`` automatic), so megatron TP shardings propagate inside
        # the shards and GSPMD inserts the row-parallel psums there.
        self.tp_size = model_par
        if (cfg.lm.attn_impl == "flash" and self.strategy == "tensor/dp"
                and self.mesh.size > 1 and on_tpu()):
            # Seen on a real 2x2 v5e host (PR 21): under plain jit XLA
            # cannot split a Mosaic kernel over the data/model axes
            # ("Mosaic kernels cannot be automatically partitioned"), and
            # libtpu 0.0.34 has no emitter for custom_partitioning either.
            # The CPU interpreter hides it, so say it here, before the
            # first compile. The shard_map strategies run flash on chips.
            raise NotImplementedError(
                "attn_impl='flash' does not run under the tensor/dp "
                "strategy on more than one TPU chip (XLA cannot partition "
                "a Mosaic kernel under plain jit); use --sp N (ring+flash), "
                "--pp N, or --attn-impl exact")
        if cfg.tp_overlap and self.strategy == "pipeline":
            raise NotImplementedError(
                "tp_overlap does not compose with the pipeline strategy "
                "(the stacked-stage scan keeps `model` automatic for "
                "GSPMD); use the tensor/dp or sequence strategy")
        if cfg.tp_overlap and cfg.moe.enabled:
            raise NotImplementedError(
                "tp_overlap does not compose with MoE (expert dispatch "
                "relies on GSPMD's expert axis, which the full-manual "
                "overlap region unbinds)")
        if self.strategy == "pipeline" and cfg.zero.stage >= 3:
            # Stages 1/2 compose since round 4 (make_pp_lm_train_step
            # shards the optimizer state over data on dims the pipe/TP
            # specs leave free); stage 3 would all-gather every stage's
            # params each pipeline tick — DeepSpeed's pipeline engine
            # refuses ZeRO-3 for the same reason.
            raise NotImplementedError(
                f"zero stage {cfg.zero.stage} does not compose with the "
                "pipeline strategy (params sharded over data would be "
                "all-gathered every tick); use stage 1/2 or another "
                "strategy")
        from distributed_training_tpu.parallel.sharding import (
            check_cpu_offload,
        )

        # Validate the ds_config offload knob once, strategy-independent
        # (the step builders re-check where they place opt state).
        check_cpu_offload(cfg.zero.cpu_offload, cfg.zero.stage)
        expert = shape.get("expert", 1)
        if cfg.moe.enabled and expert > 1 and cfg.zero.stage >= 1 \
                and not cfg.moe.moe_param_group:
            # DeepSpeed's --moe-param-group splits expert params into their
            # own groups so ZeRO partitions their optimizer state per
            # expert-parallel group instead of over the whole DP world
            # (resnet/deepspeed/deepspeed_train.py:103-106) — without it,
            # ZeRO×EP is wrong there. This framework's rule table always
            # keeps expert moments expert-sharded (tensor_parallel.py
            # LM_TP_RULES), i.e. the flag's semantics are the only
            # implemented behavior; requiring it under ZeRO×EP keeps the
            # CLI contract explicit rather than silently implying it.
            raise ValueError(
                "zero stage >= 1 with expert parallelism requires "
                "--moe-param-group (expert optimizer state is partitioned "
                "per expert group, DeepSpeed's split_params_into_"
                "different_moe_groups_for_optimizer semantics)")
        # Gated on moe.enabled (not the expert axis): an expert axis with
        # MoE off has its own accurate diagnosis below ("enable --moe or
        # drop the expert axis") — steering that user to --moe-every 1
        # would not fix anything.
        if cfg.moe.enabled and self.strategy == "pipeline":
            homogeneous = (cfg.moe.every == 1
                           and len(set(cfg.moe.num_experts)) == 1)
            if not homogeneous:
                raise NotImplementedError(
                    "the pipeline engine carries MoE only in the "
                    "HOMOGENEOUS layout (--moe-every 1, one expert count: "
                    "the stacked-stage scan requires congruent per-layer "
                    "param trees, which the alternating/per-layer layouts "
                    "break). That already exceeds the parity bar — "
                    "DeepSpeed's PipelineModule cannot carry MoE layers at "
                    "all (deepspeed.moe routes through the non-pipeline "
                    "engine only; the reference's MoE surface, "
                    "resnet/deepspeed/deepspeed_train.py:61-106, drives "
                    "plain DP training). Use tensor/dp or sequence for "
                    "alternating/per-layer MoE")
        if expert > 1 and not cfg.moe.enabled:
            raise ValueError(
                f"expert mesh axis sized {expert} with MoE disabled would "
                "replicate the dense model over it (idle chips); enable "
                "--moe or drop the expert axis")
        lm = cfg.lm
        if seq > 1 and lm.seq_len % seq:
            raise ValueError(
                f"sequence-parallel size {seq} must divide seq_len "
                f"(= {lm.seq_len})")
        if lm.ce_chunk_size is not None:
            if lm.ce_chunk_size < 1:
                raise ValueError(
                    f"ce_chunk_size must be >= 1, got {lm.ce_chunk_size}")
            # Token datasets yield seq_len+1 tokens so the shifted loss
            # length is exactly seq_len — seq_len/sp per shard for the
            # ring strategy's shard-local chunked CE, but the FULL seq_len
            # for the pipeline path (its chunked CE runs under GSPMD over
            # the global time axis, even with a sequence mesh axis).
            # tp_overlap additionally time-shards the loss over the model
            # axis (both the ring and tensor/dp strategies route through
            # the full-manual overlap body).
            t_loss = (lm.seq_len // seq
                      if self.strategy == "sequence" else lm.seq_len)
            if cfg.tp_overlap and self.strategy != "pipeline":
                t_loss //= model_par
            if t_loss % lm.ce_chunk_size:
                raise ValueError(
                    f"ce_chunk_size {lm.ce_chunk_size} must divide the "
                    f"per-shard loss sequence length (= {t_loss})")
        if pipe > 1:
            if lm.num_layers % pipe:
                raise ValueError(
                    f"pipeline size {pipe} must divide num_layers "
                    f"(= {lm.num_layers})")
            if cfg.data.batch_size % lm.num_microbatches:
                raise ValueError(
                    f"num_microbatches {lm.num_microbatches} must divide "
                    f"the per-shard batch_size (= {cfg.data.batch_size})")
        if cfg.moe.enabled and expert > 1:
            # Per-layer lists (DeepSpeed --num-experts nargs surface) are
            # honored since round 4; EVERY layer's expert set shards over
            # the expert axis, so each count must divide it.
            for ne in cfg.moe.num_experts:
                if int(ne) % expert:
                    raise ValueError(
                        f"expert-parallel size {expert} must divide every "
                        f"per-layer num_experts "
                        f"(= {tuple(cfg.moe.num_experts)})")
        if model_par > 1:
            # The megatron rule table shards heads / mlp columns / vocab over
            # the model axis; device_put fails opaquely on non-divisible
            # dims, so check here where the message can name the knob.
            # tp_overlap keeps vocab params replicated (no vocab constraint)
            # but time-shards activations over `model` instead.
            checks = [("num_heads", lm.num_heads),
                      ("mlp dim", lm.hidden_dim * lm.mlp_ratio)]
            if not cfg.tp_overlap:
                checks.append(("vocab_size", lm.vocab_size))
            for what, n in checks:
                if n % model_par:
                    raise ValueError(
                        f"tensor parallelism size {model_par} must divide "
                        f"{what} (= {n})")
            if cfg.tp_overlap and (lm.seq_len // seq) % model_par:
                raise ValueError(
                    f"tp_overlap time-shards activations over the model "
                    f"axis: the per-sequence-shard length "
                    f"(= {lm.seq_len // seq}) must divide by the "
                    f"tensor-parallel size {model_par}")
        policy = Policy.from_config(cfg.precision)
        moe_kwargs = {}
        if cfg.moe.enabled:
            moe_kwargs = dict(
                moe_num_experts=tuple(int(n) for n in cfg.moe.num_experts),
                moe_every=cfg.moe.every,
                moe_top_k=cfg.moe.top_k,
                moe_capacity_factor=cfg.moe.capacity_factor,
                moe_min_capacity=cfg.moe.min_capacity,
                moe_noisy_gate_policy=cfg.moe.noisy_gate_policy,
                moe_mlp_type=cfg.moe.mlp_type,
                moe_expert_axis="expert" if expert > 1 else None,
            )
        with trace_lib.span("setup.model_init"):
            self.model = get_model(
                "transformer_lm",
                num_classes=lm.vocab_size,
                dtype=policy.compute_dtype,
                remat=cfg.remat,
                seq_axis=AXIS_SEQUENCE if seq > 1 else None,
                num_layers=lm.num_layers,
                num_heads=lm.num_heads,
                hidden_dim=lm.hidden_dim,
                mlp_ratio=lm.mlp_ratio,
                max_len=lm.max_len,
                attn_impl=lm.attn_impl,
                logits_dtype=parse_logits_dtype(lm.logits_dtype),
                head_bias=lm.head_bias,
                **moe_kwargs,
            )
        self.world_size = data_axis_size(self.mesh)
        self.train_gbs, self.eval_gbs, self.grad_accum = effective_batch_sizes(
            cfg, self.world_size)
        # DeepSpeed's pipeline engine EQUATES gradient accumulation with
        # microbatching (`gradient_accumulation_steps` is its microbatch
        # count; the ds_config surface at
        # resnet/deepspeed/deepspeed_train.py:172-173 feeds both knobs from
        # the same batch triple): accum multiplies the microbatch count,
        # each microbatch keeps its shape (batch_size/num_microbatches),
        # and the schedule drains accum× more ticks before the single
        # optimizer update — same effective batch, better bubble fraction.
        self._pp_microbatches = cfg.lm.num_microbatches * (
            self.grad_accum if self.strategy == "pipeline" else 1)
        if (self.strategy == "pipeline"
                and cfg.data.batch_size % self._pp_microbatches):
            # The shared PipelinedLM apply_fn serves BOTH the train step
            # (which sees batch_size × accum rows and drains num_micro ×
            # accum microbatches) and eval (micro-sized batches through the
            # same schedule): batch_size itself must divide by the scaled
            # count, or eval's spmd_pipeline would crash after a full
            # training epoch.
            raise ValueError(
                f"with the pipeline strategy, gradient_accumulation_steps "
                f"multiplies the microbatch count (DeepSpeed pipeline "
                f"semantics): num_microbatches × accum = "
                f"{self._pp_microbatches} must divide the per-shard "
                f"batch_size (= {cfg.data.batch_size})")
        self.tx = make_optimizer(cfg.optimizer, cfg.scheduler, self.world_size)
        loss_scale = LossScaleState.create(cfg.precision)

        self.rng, init_rng = jax.random.split(jax.random.PRNGKey(cfg.seed))
        with trace_lib.span("setup.step_build"):
            if self.strategy == "pipeline":
                self.train_step = make_pp_lm_train_step(
                    self.mesh, model=self.model,
                    num_microbatches=self._pp_microbatches,
                    ce_chunk=lm.ce_chunk_size,
                    accuracy_metric=lm.metrics_accuracy,
                    zero_stage=cfg.zero.stage,
                    virtual_stages=lm.virtual_stages,
                    cpu_offload=cfg.zero.cpu_offload,
                    ce_save_probs=lm.ce_save_probs,
                    grad_norm_metric=cfg.observability.grad_norm)
            else:
                # The sequence strategy's partial-manual shard_map leaves
                # `model` automatic, so both builders take the same
                # arguments; over a model axis of size 1 every TP spec of
                # the rule table is a no-op shard.
                make_step = (make_lm_train_step
                             if self.strategy == "sequence"
                             else make_tp_lm_train_step)
                self.train_step = make_step(
                    self.mesh, model=self.model, ce_chunk=lm.ce_chunk_size,
                    grad_accum_steps=self.grad_accum,
                    zero_stage=cfg.zero.stage,
                    accuracy_metric=lm.metrics_accuracy,
                    cpu_offload=cfg.zero.cpu_offload,
                    ce_save_probs=lm.ce_save_probs,
                    tp_overlap=cfg.tp_overlap and model_par > 1,
                    grad_norm_metric=cfg.observability.grad_norm)
        with trace_lib.span("setup.state_init"):
            if self.strategy == "pipeline":
                plm = self.train_step.pipelined
                state = TrainState.create(
                    apply_fn=plm.apply_fn,
                    params=plm.init_params(init_rng),
                    tx=self.tx, loss_scale=loss_scale)
            else:
                state = init_train_state(
                    self.model, init_rng, (1, 8), self.tx,
                    loss_scale=loss_scale, input_dtype=jnp.int32)
            # TP rule table (+ ZeRO recruitment over the data axes).
            self.shardings = self.train_step.state_shardings(state)
            self.state = place_state(state, self.shardings)

        self.batch_shardings = self.train_step.batch_shardings

        # Eval forward. The sequence strategy evaluates through the SHARDED
        # ring forward (make_lm_eval_fn): the ring model only applies
        # inside shard_map, and a context that only *fits* sharded (the
        # T16384 flagship) must be evaluable at its trained length —
        # tests/test_lm_sequence_parallel.py pins sharded eval == the
        # unsharded oracle.
        if self.strategy == "sequence":
            from distributed_training_tpu.train.lm_step import make_lm_eval_fn

            self._eval_fn = make_lm_eval_fn(
                self.mesh, model=self.model, ce_chunk=lm.ce_chunk_size,
                tp_overlap=cfg.tp_overlap and self.tp_size > 1)
        else:
            eval_apply = self.state.apply_fn

            if lm.ce_chunk_size:
                from distributed_training_tpu.train.lm_step import (
                    chunked_ce_and_accuracy,
                )

                def eval_loss(params, batch):
                    hidden = eval_apply({"params": params}, batch["tokens"],
                                        train=False, return_hidden=True)
                    ce, _ = chunked_ce_and_accuracy(
                        hidden, params["lm_head"], batch["targets"],
                        lm.ce_chunk_size,
                        logits_dtype=model_logits_dtype(self.model))
                    return ce
            else:
                from distributed_training_tpu.train.lm_step import (
                    _fused_softmax_ce,
                )

                def eval_loss(params, batch):
                    # Same fusion-friendly CE as training: fp32 reduction
                    # over stored-dtype logits with no materialized
                    # log-prob tensor (see lm_step._fused_ce_rows).
                    logits = eval_apply({"params": params}, batch["tokens"],
                                        train=False)
                    return _fused_softmax_ce(logits, batch["targets"])

            self._eval_fn = jax.jit(eval_loss)

        self.meter = MetricMeter(cfg.log_interval)
        # Forensics default next to the run's durable artifacts.
        obs_dump_dir = cfg.observability.dump_dir or os.path.join(
            cfg.checkpoint.directory, "flight")
        # Span tracing (off by default → trace is None and every
        # integration point below stays span-free; observability/trace.py).
        self.trace, trace_path = trace_lib.session_for_run(
            cfg.observability.trace, default_dir=obs_dump_dir)
        # Always-on when the flight recorder (or the span trace) is
        # (goodput attribution); the per-epoch report print stays gated
        # on wall_clock_breakdown.
        self.clock = WallClock(
            cfg.wall_clock_breakdown or cfg.observability.flight_recorder
            or self.trace is not None, trace=self.trace)
        self.metrics_writer = MetricsWriter(
            cfg.tensorboard_dir, cfg.metrics_jsonl,
            enabled=self.coord.is_master())
        # Flight instruments. Step FLOPs cover the EFFECTIVE batch's
        # tokens (micro × accum × world × seq_len) — one optimizer step's
        # model FLOPs, accumulation-aware by construction; MoE models
        # report no MFU (routed FLOPs are runtime-dependent).
        self.obs = TrainObservability(
            cfg.observability,
            step_flops=train_step_flops(forward_flops(
                self.model, seq_len=lm.seq_len, batch=self.train_gbs)),
            n_devices=int(self.mesh.devices.size),
            clock=self.clock, is_master=self.coord.is_master(),
            printer=self.coord.print,
            dump_dir=obs_dump_dir,
            extra_provider=self._resilience_snapshot,
            trace=self.trace, trace_path=trace_path,
            num_processes=jax.process_count())
        # Resilience: fault injection + background checkpoint writer
        # (single-process only; multihost saves stay synchronous — see
        # trainer.py for the rationale).
        self.chaos = (ChaosMonkey(cfg.chaos,
                                  process_index=jax.process_index(),
                                  trace=self.trace)
                      if cfg.chaos.active else None)
        self._ckpt_writer = None
        if cfg.checkpoint.async_save and jax.process_count() == 1:
            self._ckpt_writer = AsyncCheckpointWriter(
                post_save=(self.chaos.after_checkpoint_save
                           if self.chaos else None),
                printer=self.coord.print, trace=self.trace)
        self._sync_saves = 0
        self._guard: PreemptionGuard | None = None
        self._global_step = 0
        self._epoch_step = 0
        strategy_label = self.strategy + (
            "×tp" if self.tp_size > 1 and self.strategy != "tensor/dp" else ""
        ) + ("(tp-overlap)" if cfg.tp_overlap and self.tp_size > 1 else "")
        self.coord.print(
            f"[lm_trainer] params={param_count(state.params):,} "
            f"mesh={shape} strategy={strategy_label} "
            f"zero_stage={cfg.zero.stage} dtype={cfg.precision.dtype} "
            f"seq_len={lm.seq_len}"
            + (f" grad_accum={self.grad_accum}" if self.grad_accum > 1 else "")
            + f" {device_banner()}", file=sys.stderr)

    # -- resilience ---------------------------------------------------------
    def _save_ckpt(self, epoch: int, *, sync: bool = False, **kw) -> None:
        """One save through the configured path (async writer or sync
        orbax); ``sync=True`` = the preemption durability contract."""
        d = self.cfg.checkpoint.directory
        kw.setdefault("layout", self._ckpt_layout())
        if self._ckpt_writer is not None:
            self._ckpt_writer.save(d, epoch, self.state, sync=sync, **kw)
            return
        path = ckpt_lib.save_checkpoint(d, epoch, self.state, **kw)
        self._sync_saves += 1
        if self.chaos is not None:
            self.chaos.after_checkpoint_save(path, epoch)

    def _prune_ckpts(self) -> None:
        d, keep = self.cfg.checkpoint.directory, self.cfg.checkpoint.keep
        if self._ckpt_writer is not None:
            self._ckpt_writer.prune(d, keep)
        else:
            ckpt_lib.prune_checkpoints(d, keep)

    def _resilience_snapshot(self) -> dict:
        """Flight-dump resilience section (tools/flight_report.py)."""
        c = {"io_retries": retry_lib.total_retries(),
             "saves_committed": self._sync_saves, "saves_failed": 0}
        if self._ckpt_writer is not None:
            c["saves_committed"] += \
                self._ckpt_writer.counters["saves_committed"]
            c["saves_failed"] = self._ckpt_writer.counters["saves_failed"]
        if self.chaos is not None:
            c["chaos_faults"] = dict(self.chaos.counters)
        return {"resilience": c}

    # -- data ---------------------------------------------------------------
    def make_loaders(self) -> tuple[TokenLoader, TokenLoader]:
        lm = self.cfg.lm
        if lm.corpus_path:
            # Disjoint byte spans: eval windows never overlap training text.
            train = byte_corpus(
                lm.corpus_path, lm.train_sequences, lm.seq_len,
                seed=self.cfg.seed, span=(0.0, 0.9))
            evals = byte_corpus(
                lm.corpus_path, lm.eval_sequences, lm.seq_len,
                seed=self.cfg.seed + 1, span=(0.9, 1.0))
        else:
            train = synthetic_tokens(
                lm.train_sequences, lm.seq_len, lm.vocab_size,
                seed=self.cfg.seed)
            evals = synthetic_tokens(
                lm.eval_sequences, lm.seq_len, lm.vocab_size,
                seed=self.cfg.seed + 1)
        def mk(toks, train_mode):
            # Train consumes effective batches; eval stays micro-sized.
            return TokenLoader(
                toks,
                global_batch_size=(self.train_gbs if train_mode
                                   else self.eval_gbs),
                shuffle=train_mode,
                seed=self.cfg.seed,
                max_steps=(self.cfg.data.max_steps_per_epoch
                           if train_mode else None))
        return mk(train, True), mk(evals, False)

    def _place(self, host_batch: dict) -> dict:
        # Shift on the host numpy array, then one device_put straight onto
        # the mesh placement — no staging copy through the default device.
        batch = make_lm_batch(host_batch["tokens"])
        return jax.device_put(batch, self.batch_shardings)

    def _batches(self, loader: TokenLoader):
        """Device-resident batches, prefetched ``cfg.data.prefetch`` ahead;
        the synchronous path keeps per-batch 'data' wall-clock attribution."""
        from distributed_training_tpu.data.prefetch import DevicePrefetcher

        if self.cfg.data.prefetch < 1:
            def sync_gen():
                for b in loader:
                    with self.clock.phase("data"):
                        gb = self._place(b)
                    yield gb
            return sync_gen()
        return DevicePrefetcher(loader, self._place,
                                depth=self.cfg.data.prefetch)

    # -- train --------------------------------------------------------------
    def train_epoch(self, epoch: int, loader: TokenLoader,
                    skip_steps: int = 0) -> dict:
        loader.set_epoch(epoch)
        if skip_steps:
            # Step-accurate preemption resume: skip the already-trained
            # prefix of the epoch's deterministic shuffle (see trainer.py).
            from distributed_training_tpu.data.pipeline import SkipBatches

            self.coord.print(
                f"[lm_trainer] resuming epoch {epoch} at step {skip_steps}")
            loader = SkipBatches(loader, skip_steps)
        self._epoch_step = skip_steps
        self.obs.on_epoch()  # boundary pause ≠ a straggler step
        bar = EpochBar(len(loader), epoch, self.cfg.num_epochs,
                       self.coord.is_master())
        gbatch = None
        # Spans of one optimizer step share its number as their key: the
        # main thread's wait on the prefetcher, the dispatch, the fetch.
        for gbatch in trace_lib.spanned(
                self._batches(loader), "train.batch_wait",
                key=lambda: self._global_step + 1):
            with self.clock.phase("step"), trace_lib.span(
                    "train.dispatch", key=self._global_step + 1):
                self.rng, step_rng = jax.random.split(self.rng)
                self.state, metrics = self.train_step(
                    self.state, gbatch, step_rng)
            with self.clock.phase("log"):
                self._global_step += 1
                self._epoch_step += 1
                fetched = self.meter.push(self._global_step, metrics)
                # Chaos BEFORE the recorder's timestamp: an injected
                # slow-step stall then lands in THIS step's wall delta
                # (like a real straggler's would), so the cross-host
                # aggregation attributes the injected step itself.
                if self.chaos is not None:
                    self.chaos.on_step(self._global_step)
                self.obs.on_step(self._global_step)
                bar.update()
                if fetched:
                    extras = self.obs.on_flush(
                        self.meter.last, batch=gbatch, state=self.state,
                        step_fn=self.train_step, rng=self.rng)
                    bar.set_postfix(self.meter.last)
                    self.metrics_writer.write(
                        self.meter.last["step"],
                        {**self.meter.last, **extras})
            if self._guard is not None and self._guard.should_stop(
                    at_sync_point=fetched):
                break
        # Flush the epoch tail only if steps are actually pending — an
        # unconditional write would duplicate the last interval's point.
        if self.meter.pending:
            flushed = self.meter.flush()
            extras = self.obs.on_flush(
                flushed, batch=gbatch, state=self.state,
                step_fn=self.train_step, rng=self.rng)
            self.metrics_writer.write(flushed["step"], {**flushed, **extras})
        bar.set_postfix(self.meter.last)
        bar.close()
        if self.cfg.wall_clock_breakdown:
            self.coord.print(f"[wall_clock] {self.clock.report()}")
        return self.meter.last

    # -- eval ---------------------------------------------------------------
    def _eval_params(self):
        """Params evaluation sees: the EMA tree when configured."""
        if (self.cfg.optimizer.ema_decay is not None
                and self.cfg.eval_with_ema):
            from distributed_training_tpu.train.optim import ema_params

            return ema_params(self.state.opt_state)
        return self.state.params

    def evaluate(self, loader: TokenLoader) -> float:
        """Mean held-out perplexity (exp of the mean token CE)."""
        params = self._eval_params()
        losses = []
        for gbatch in self._batches(loader):
            losses.append(float(self._eval_fn(params, gbatch)))
        if not losses:
            raise ValueError(
                "eval loader yielded no batches (eval_sequences "
                f"{self.cfg.lm.eval_sequences} < global batch "
                f"{loader.global_batch_size}? drop_last discards partials)")
        ppl = float(np.exp(np.mean(losses)))
        self.metrics_writer.write(
            self._global_step, {"perplexity": ppl}, prefix="eval")
        return ppl

    # -- full run -----------------------------------------------------------
    def fit(self) -> dict:
        if self.chaos is not None:
            chaos_lib.install(self.chaos)  # data loaders poll it
        try:
            result = self._fit()
            # Surfaces a deferred anomaly raise whose trace window the
            # run's end cut short (forensics were dumped at trigger time).
            self.obs.close()
            return result
        except AnomalyError:
            raise
        except BaseException:
            self.obs.on_crash()  # flight record before the exception flies
            raise
        finally:
            if self.chaos is not None:
                chaos_lib.uninstall()
            if self._ckpt_writer is not None:
                self._ckpt_writer.close(raise_on_error=False)
            self.obs.close(raise_pending=False)  # idempotent trace teardown
            self.metrics_writer.close()

    def _ckpt_layout(self) -> dict:
        """Storage-layout metadata for save/restore validation: the
        pipeline strategy stacks blocks in a (pipe_size × virtual_stages)-
        dependent permutation (parallel/pipeline.circular_layer_order);
        shape-identical checkpoints across different layouts would load
        silently permuted (see checkpoint.restore_checkpoint)."""
        if self.strategy != "pipeline":
            return {}
        plm = self.train_step.pipelined
        if plm.virtual_stages == 1:
            # GPipe stacking is the identity for ANY pipe size — only the
            # circular permutation makes the layout pipe-size-dependent.
            return {"virtual_stages": 1}
        return {"pipe_size": plm.pipe_size,
                "virtual_stages": plm.virtual_stages}

    def _fit(self) -> dict:
        cfg = self.cfg
        train_loader, eval_loader = self.make_loaders()

        start_epoch = 0
        start_step = 0
        resume = ckpt_lib.resolve_resume(cfg.checkpoint)
        if resume >= 0:
            self.state, start_epoch, start_step = restore_lm_checkpoint(
                cfg.checkpoint.directory, resume, self.state,
                layout=self._ckpt_layout())
            self.state = place_state(self.state, self.shardings)
            # Metric sinks continue the restored step axis (see trainer.py).
            self._global_step = int(jax.device_get(self.state.step))
            self.coord.print(f"[lm_trainer] resumed at epoch {start_epoch}")

        ppl = None
        preempted = False
        with trace(cfg.profile_dir), PreemptionGuard() as guard:
            self._guard = guard
            for epoch in range(start_epoch, cfg.num_epochs):
                self.train_epoch(
                    epoch, train_loader,
                    skip_steps=start_step if epoch == start_epoch else 0)
                if guard.should_stop():
                    preempted = True
                    if cfg.checkpoint.save_on_preemption:
                        # Completed-epoch preemption rolls over (trainer.py).
                        done = self._epoch_step >= len(train_loader)
                        next_ep = epoch + 1 if done else epoch
                        estep = 0 if done else self._epoch_step
                        with self.clock.phase("ckpt"):
                            # sync: durable before the grace window ends.
                            self._save_ckpt(epoch, sync=True,
                                            next_epoch=next_ep,
                                            epoch_step=estep)
                        self.coord.print(
                            f"[lm_trainer] SIGTERM: saved preemption "
                            f"checkpoint (resumes at epoch {next_ep} "
                            f"step {estep})")
                    break
                if cfg.eval_every and (epoch + 1) % cfg.eval_every == 0:
                    with self.clock.phase("eval"):
                        ppl = self.evaluate(eval_loader)
                    self.coord.print(
                        f"[eval] epoch {epoch + 1}: perplexity {ppl:.4f}")
                if cfg.checkpoint.interval and (
                        epoch + 1) % cfg.checkpoint.interval == 0:
                    with self.clock.phase("ckpt"):
                        self._save_ckpt(epoch)
                        self._prune_ckpts()
        self._guard = None
        if self._ckpt_writer is not None:
            # Durable before fit() reports done (failures counted, not
            # thrown over a successful run — see trainer.py).
            self._ckpt_writer.wait(raise_on_error=False)
        return {"final_perplexity": ppl, "preempted": preempted,
                "last_metrics": self.meter.last,
                "steps": int(jax.device_get(self.state.step))}
