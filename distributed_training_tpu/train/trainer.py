"""The Trainer: epoch loop, eval, checkpointing, logging.

The framework-level replacement for the reference's three per-backend
``__main__`` blocks + ``train_epoch`` functions (SURVEY.md §1 L2): one
engine parameterized by :class:`TrainConfig`, with every dangling surface of
the reference wired for real — the eval loop the reference never runs
(``test_dataloader`` built and dropped, ``resnet/pytorch_ddp/ddp_train.py:96``),
the ``--target_acc`` assertion (``resnet/colossal/colossal_train.py:43-46``),
and checkpoint save/resume (``:40-42``).
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
from distributed_training_tpu.data.pipeline import (
    SkipBatches,
    build_dataloaders,
    to_global_batch,
)
from distributed_training_tpu.data.prefetch import DevicePrefetcher
from distributed_training_tpu.models import get_model
from distributed_training_tpu.parallel.sharding import (
    batch_sharding,
    place_state,
    state_shardings,
)
from distributed_training_tpu.runtime.backend import device_banner
from distributed_training_tpu.runtime.coordinator import Coordinator
from distributed_training_tpu.runtime.mesh import MeshConfig, create_mesh, data_axis_size
from distributed_training_tpu.train.optim import make_optimizer
from distributed_training_tpu.train.precision import LossScaleState, Policy
from distributed_training_tpu.train.step import (
    make_eval_step,
    make_shard_map_train_step,
    make_train_step,
)
from distributed_training_tpu.train.train_state import init_train_state, param_count
from distributed_training_tpu.observability import (
    AnomalyError,
    TrainObservability,
    forward_flops,
    train_step_flops,
)
from distributed_training_tpu.observability import trace as trace_lib
from distributed_training_tpu.resilience import retry as retry_lib
from distributed_training_tpu.resilience.async_ckpt import (
    AsyncCheckpointWriter,
)
from distributed_training_tpu.resilience.chaos import ChaosMonkey
from distributed_training_tpu.resilience import chaos as chaos_lib
from distributed_training_tpu.runtime.preemption import PreemptionGuard
from distributed_training_tpu.utils.logging import EpochBar, MetricMeter
from distributed_training_tpu.utils.metrics_io import MetricsWriter
from distributed_training_tpu.utils.profiling import WallClock, trace


class Trainer:
    """End-to-end training engine over a device mesh."""

    def __init__(self, cfg: TrainConfig, mesh=None):
        self.cfg = cfg
        self.coord = Coordinator()
        # Field-by-name conversion so every MeshSpec axis (incl. additions
        # like `pipe`) reaches the mesh — a hand-copied subset here would
        # silently reassign those devices to the inferred data axis.
        self.mesh = mesh if mesh is not None else create_mesh(
            MeshConfig(**dataclasses.asdict(cfg.mesh)))
        self.world_size = data_axis_size(self.mesh)

        if cfg.moe.enabled and not cfg.model.startswith("moe"):
            raise NotImplementedError(
                f"MoE is only wired into the moe_* models (models/moe.py); "
                f"model {cfg.model!r} would silently train dense")
        if cfg.model == "transformer_lm":
            raise NotImplementedError(
                "transformer_lm is a token model; this Trainer drives image "
                "classification. Use train.lm_step.make_lm_train_step with "
                "a (data × sequence) mesh (see tests/test_lm_sequence_parallel.py)")

        policy = Policy.from_config(cfg.precision)
        model_kwargs = {}
        if cfg.remat:
            # Only set when asked: models without a remat attr (moe_mlp)
            # then raise loudly instead of silently not checkpointing.
            model_kwargs["remat"] = True
        if cfg.model.startswith("moe"):
            mesh_shape = dict(zip(self.mesh.axis_names, self.mesh.devices.shape))
            model_kwargs |= dict(
                num_experts=tuple(cfg.moe.num_experts),
                top_k=cfg.moe.top_k,
                capacity_factor=cfg.moe.capacity_factor,
                min_capacity=cfg.moe.min_capacity,
                noisy_gate_policy=cfg.moe.noisy_gate_policy,
                mlp_type=cfg.moe.mlp_type,
                expert_axis="expert" if mesh_shape.get("expert", 1) > 1 else None,
            )
        # GSPMD path: BN statistics reduce over the global (sharded) batch
        # automatically — SyncBN for free, no axis name needed. Local BN
        # (sync_batchnorm=False, the torch-DDP-default semantics) instead
        # uses the explicit shard_map step where each shard computes its own
        # statistics (model axis_name stays None there too: BN only syncs
        # when the model is given the mesh axis).
        self.model = get_model(
            cfg.model,
            num_classes=cfg.data.num_classes,
            dtype=policy.compute_dtype,
            axis_name=None,
            **model_kwargs,
        )
        self.tx = make_optimizer(cfg.optimizer, cfg.scheduler, self.world_size)

        rng = jax.random.PRNGKey(cfg.seed)
        self.rng, init_rng = jax.random.split(rng)
        input_shape = (
            max(1, cfg.data.batch_size),
            cfg.data.image_size, cfg.data.image_size, 3)
        state = init_train_state(
            self.model, init_rng, input_shape, self.tx,
            loss_scale=LossScaleState.create(cfg.precision))
        mesh_shape = dict(zip(self.mesh.axis_names, self.mesh.devices.shape))
        self.tp_size = mesh_shape.get("model", 1)
        if self.tp_size > 1:
            # Megatron TP for image transformers (round 4: the rule table
            # covers ViT blocks). A model without matching rules would
            # silently replicate its weights over the model axis — idle
            # chips wearing a TP banner.
            if not cfg.model.startswith("vit"):
                raise NotImplementedError(
                    f"a model mesh axis of {self.tp_size} is only wired for "
                    f"the vit_* models (parallel/tensor_parallel.py rule "
                    f"table); {cfg.model!r} would replicate over it")
            # device_put fails opaquely on non-divisible dims; check here
            # where the message can name the knob (mirrors lm_trainer).
            # tp_overlap keeps the class head replicated (no num_classes
            # constraint) but ring-scatters the row-parallel outputs over
            # the hidden dim, which must divide instead.
            checks = [("num_heads", self.model.num_heads),
                      ("mlp_dim", self.model.mlp_dim)]
            checks.append(("hidden_size", self.model.hidden_size)
                          if cfg.tp_overlap
                          else ("num_classes", cfg.data.num_classes))
            for what, n in checks:
                if n % self.tp_size:
                    raise ValueError(
                        f"tensor parallelism size {self.tp_size} must "
                        f"divide {what} (= {n})")
            import functools

            from distributed_training_tpu.parallel.tensor_parallel import (
                tp_state_shardings,
            )

            shardings_fn = functools.partial(tp_state_shardings,
                                             overlap=cfg.tp_overlap)
        else:
            shardings_fn = state_shardings
        self.shardings = shardings_fn(state, self.mesh, cfg.zero.stage,
                                      cpu_offload=cfg.zero.cpu_offload)
        self.state = place_state(state, self.shardings)

        # Local-vs-sync BN only differs for models that actually carry
        # BatchNorm state; BN-free models (ViT, MoE-MLP) always take the
        # GSPMD path, where ZeRO placement composes.
        has_bn = bool(jax.tree.leaves(state.batch_stats))
        uses_gspmd_step = cfg.sync_batchnorm or not has_bn
        # Resolve DeepSpeed batch-triple semantics once, where world size is
        # known (accum may be derived from global_batch_size; both the
        # GSPMD and the shard_map local-BN steps accumulate).
        # batch_size is per *chip* (DDP parity: per-GPU mini-batch ×
        # world), so scale by every mesh device — under a data×expert mesh
        # the data axis is smaller than the chip count, but each chip still
        # contributes batch_size examples of work.
        self.train_gbs, self.eval_gbs, self.grad_accum = effective_batch_sizes(
            cfg, int(self.mesh.devices.size), allow_derive=True)
        # uint8 batches (decoded-cache loader) defer ToTensor/Normalize to
        # the device, fused into the first conv; the affine encodes the
        # augment mode's normalization. Float batches ignore it. Kept on
        # self so the precise-BN refresh normalizes identically.
        input_affine = self._input_affine = (
            (2.0 / 255.0, -1.0) if cfg.data.augment == "normalize_only"
            else (1.0 / 255.0, 0.0))
        if uses_gspmd_step:
            self.train_step = make_train_step(
                self.mesh, zero_stage=cfg.zero.stage,
                grad_accum_steps=self.grad_accum,
                label_smoothing=cfg.label_smoothing,
                input_affine=input_affine,
                cpu_offload=cfg.zero.cpu_offload,
                tensor_parallel=self.tp_size > 1,
                tp_overlap=cfg.tp_overlap and self.tp_size > 1,
                grad_norm_metric=cfg.observability.grad_norm)
        else:
            if cfg.zero.stage != 0:
                raise NotImplementedError(
                    "sync_batchnorm=False uses the explicit shard_map DP "
                    "step, which has no ZeRO sharding; use zero stage 0 "
                    "with local BN")
            if cfg.zero.cpu_offload:
                raise NotImplementedError(
                    "cpu_offload rides the ZeRO opt-state sharding of the "
                    "GSPMD step; the local-BN shard_map step has neither")
            self.train_step = make_shard_map_train_step(
                self.mesh, label_smoothing=cfg.label_smoothing,
                input_affine=input_affine,
                grad_accum_steps=self.grad_accum,
                grad_norm_metric=cfg.observability.grad_norm)
        self.eval_step = make_eval_step(self.mesh, input_affine=input_affine)
        self.meter = MetricMeter(cfg.log_interval)
        # Forensics default next to the run's durable artifacts.
        obs_dump_dir = cfg.observability.dump_dir or os.path.join(
            cfg.checkpoint.directory, "flight")
        # Span tracing (off by default → trace is None and every
        # integration point below stays span-free; observability/trace.py).
        self.trace, trace_path = trace_lib.session_for_run(
            cfg.observability.trace, default_dir=obs_dump_dir)
        # The clock always runs when the flight recorder (or the span
        # trace) does: goodput attribution costs two perf_counter reads
        # per phase, and the per-epoch report print stays gated on
        # wall_clock_breakdown.
        self.clock = WallClock(
            cfg.wall_clock_breakdown or cfg.observability.flight_recorder
            or self.trace is not None, trace=self.trace)
        self.metrics_writer = MetricsWriter(
            cfg.tensorboard_dir, cfg.metrics_jsonl,
            enabled=self.coord.is_master())
        # Flight instruments: analytic step FLOPs (effective batch — MFU is
        # accumulation-aware by construction) + the flush-boundary hooks.
        self.obs = TrainObservability(
            cfg.observability,
            step_flops=train_step_flops(forward_flops(
                self.model, image_size=cfg.data.image_size,
                batch=self.train_gbs)),
            n_devices=int(self.mesh.devices.size),
            clock=self.clock, is_master=self.coord.is_master(),
            printer=self.coord.print,
            dump_dir=obs_dump_dir,
            extra_provider=self._resilience_snapshot,
            trace=self.trace, trace_path=trace_path,
            num_processes=jax.process_count())
        # Resilience: deterministic fault injection + the background
        # checkpoint writer (single-process only — multihost snapshots
        # need orbax's own per-host gathers, so those save synchronously).
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
        self._stats_refresh = None
        self._global_step = 0
        self._epoch_step = 0
        self.coord.print(
            f"[trainer] model={cfg.model} params={param_count(state.params):,} "
            f"mesh={dict(zip(self.mesh.axis_names, self.mesh.devices.shape))} "
            f"plugin={cfg.plugin} zero_stage={cfg.zero.stage} "
            f"dtype={cfg.precision.dtype}"
            + (f" grad_accum={self.grad_accum}" if self.grad_accum > 1 else "")
            + f" {device_banner()}", file=sys.stderr)

    # -- resilience ---------------------------------------------------------
    def _save_ckpt(self, epoch: int, *, sync: bool = False, **kw) -> None:
        """One checkpoint save through the configured path: async writer
        (snapshot now, persist in background) or synchronous orbax.
        ``sync=True`` is the preemption contract — durable before return."""
        d = self.cfg.checkpoint.directory
        if self._ckpt_writer is not None:
            self._ckpt_writer.save(d, epoch, self.state, sync=sync, **kw)
            return
        path = ckpt_lib.save_checkpoint(d, epoch, self.state, **kw)
        self._sync_saves += 1
        if self.chaos is not None:
            self.chaos.after_checkpoint_save(path, epoch)

    def _prune_ckpts(self) -> None:
        """Retention sweep, ordered after any in-flight async save."""
        d, keep = self.cfg.checkpoint.directory, self.cfg.checkpoint.keep
        if self._ckpt_writer is not None:
            self._ckpt_writer.prune(d, keep)
        else:
            ckpt_lib.prune_checkpoints(d, keep)

    def _resilience_snapshot(self) -> dict:
        """Extra flight-dump section: checkpoint durability + I/O retry
        counters (rendered by tools/flight_report.py)."""
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
    def make_loaders(self):
        # Train consumes effective batches (micro × accum × world); eval
        # stays micro-sized — accumulation exists because effective-batch
        # forwards don't fit.
        return build_dataloaders(
            self.cfg, self.coord, seed=self.cfg.seed,
            global_batch_size=self.train_gbs,
            eval_global_batch_size=self.eval_gbs)

    def _batch_shardings(self, batch):
        return {k: batch_sharding(self.mesh, v.ndim) for k, v in batch.items()}

    def _batches(self, loader):
        """Device-resident batches, prefetched ``cfg.data.prefetch`` ahead
        (host augment + DMA overlap the previous step's compute; the 'data'
        wall-clock phase then reads ~0 by construction). The synchronous
        prefetch=0 path keeps per-batch 'data' attribution."""
        place = lambda b: to_global_batch(  # noqa: E731
            b, self.mesh, self._batch_shardings(b))
        if self.cfg.data.prefetch < 1:
            def sync_gen():
                for b in loader:
                    with self.clock.phase("data"):
                        gb = place(b)
                    yield gb
            return sync_gen()
        return DevicePrefetcher(loader, place, depth=self.cfg.data.prefetch)

    # -- train --------------------------------------------------------------
    def train_epoch(self, epoch: int, loader, skip_steps: int = 0) -> dict:
        """One epoch; ``skip_steps`` drops that many leading batches of the
        epoch's deterministic shuffle (step-accurate preemption resume —
        the pre-preemption prefix must not train twice)."""
        loader.set_epoch(epoch)
        if skip_steps:
            self.coord.print(
                f"[trainer] resuming epoch {epoch} at step {skip_steps}")
            loader = SkipBatches(loader, skip_steps)
        self._epoch_step = skip_steps
        self.obs.on_epoch()  # boundary pause ≠ a straggler step
        bar = EpochBar(len(loader), epoch, self.cfg.num_epochs,
                       self.coord.is_master())
        gbatch = None
        for gbatch in self._batches(loader):
            with self.clock.phase("step"):
                self.rng, step_rng = jax.random.split(self.rng)
                self.state, metrics = self.train_step(
                    self.state, gbatch, step_rng)
            with self.clock.phase("log"):
                # Host-side counter: metrics stay device-resident until the
                # meter's interval flush — no per-step loss.item() sync.
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
    def _eval_state(self):
        """The state evaluation sees: EMA params (and EMA BatchNorm stats —
        averaged weights need matching normalization statistics) when
        configured."""
        if (self.cfg.optimizer.ema_decay is not None
                and self.cfg.eval_with_ema):
            from distributed_training_tpu.train.optim import (
                ema_batch_stats,
                ema_params,
            )

            state = self.state.replace(
                params=ema_params(self.state.opt_state))
            ema_bs = ema_batch_stats(self.state.opt_state)
            if jax.tree.leaves(ema_bs):
                state = state.replace(batch_stats=ema_bs)
            return state
        return self.state

    def _refresh_batch_stats(self, train_loader, num_batches: int) -> None:
        """Precise-BN: re-estimate running stats with the CURRENT params
        (train-mode forwards, no optimizer) so eval normalizes with
        statistics that match the weights it is evaluating — the EMA lags
        by design and goes stale whenever params move fast.

        This is a TRUE average over the ``num_batches`` per-batch moments,
        not an EMA tick from the stale stats (which would leave a
        ``momentum**N`` stale residue — ~59% at N=5). A train-mode forward
        never *reads* the running stats (it normalizes by batch
        statistics), so ticking from a zero baseline returns exactly
        ``(1 - momentum) * batch_stat``; dividing recovers the raw moment,
        which is then averaged across batches."""
        import itertools

        if self._stats_refresh is None:
            from distributed_training_tpu.train.step import _input_images

            from distributed_training_tpu.models.resnet import BN_MOMENTUM

            affine = self._input_affine  # the step's input normalization
            # The zoo-wide BN momentum — needed to undo the single EMA tick
            # and recover the raw batch statistic.
            momentum = BN_MOMENTUM

            def batch_stat(state, batch, idx):
                rngs = {
                    "dropout": jax.random.fold_in(jax.random.PRNGKey(0), idx),
                    "gate": jax.random.fold_in(jax.random.PRNGKey(1), idx),
                }
                zeros = jax.tree.map(jnp.zeros_like, state.batch_stats)
                _, mut = state.apply_fn(
                    {"params": state.params, "batch_stats": zeros},
                    _input_images(batch, affine), train=True,
                    mutable=["batch_stats", "aux_loss"], rngs=rngs)
                ticked = dict(mut).get("batch_stats", zeros)
                return jax.tree.map(lambda s: s / (1.0 - momentum), ticked)

            self._stats_refresh = jax.jit(batch_stat)

        head = itertools.islice(iter(train_loader), num_batches)
        acc, n = None, 0
        for gbatch in self._batches(head):
            b = self._stats_refresh(self.state, gbatch, n)
            acc = b if acc is None else jax.tree.map(jnp.add, acc, b)
            n += 1
        if n:
            self.state = self.state.replace(
                batch_stats=jax.tree.map(lambda a: a / n, acc))

    def evaluate(self, loader, train_loader=None) -> float:
        """Top-1 accuracy (the ``target_acc`` metric); top-5 is kept on
        ``self.last_eval`` and written to the metric sinks."""
        k = self.cfg.eval_precise_bn_batches
        uses_ema_stats = (
            self.cfg.optimizer.ema_decay is not None
            and self.cfg.eval_with_ema)
        # Refresh only when eval will actually read self.state.batch_stats:
        # BN-free models have nothing to refresh, and the EMA-eval path
        # replaces the stats with the EMA copy (refreshing raw stats there
        # would be paid-for compute that eval never sees).
        if (k and train_loader is not None and not uses_ema_stats
                and jax.tree.leaves(self.state.batch_stats)):
            self._refresh_batch_stats(train_loader, k)
        eval_state = self._eval_state()
        correct = correct5 = total = 0.0
        for gbatch in self._batches(loader):
            c, c5, t = self.eval_step(eval_state, gbatch)
            correct += float(c)
            correct5 += float(c5)
            total += float(t)
        self.last_eval = {"top1": correct / max(total, 1.0),
                          "top5": correct5 / max(total, 1.0)}
        self.metrics_writer.write(
            self._global_step, self.last_eval, prefix="eval")
        return self.last_eval["top1"]

    # -- full run -----------------------------------------------------------
    def fit(self) -> dict:
        if self.chaos is not None:
            # Data loaders poll the process-global chaos registration for
            # transient-I/O injection; scoped to this fit only.
            chaos_lib.install(self.chaos)
        try:
            result = self._fit()
            # Surfaces a deferred anomaly raise whose trace window the
            # run's end cut short (forensics were dumped at trigger time).
            self.obs.close()
            return result
        except AnomalyError:
            raise
        except BaseException:
            # Crash forensics: the flight recorder's last ring of steps,
            # flushed metrics, and goodput — written before the exception
            # propagates (the process may be about to die).
            self.obs.on_crash()
            raise
        finally:
            if self.chaos is not None:
                chaos_lib.uninstall()
            if self._ckpt_writer is not None:
                # Drain + stop the writer thread; a background save
                # failure was already counted/printed — it must not mask
                # this run's real outcome or exception.
                self._ckpt_writer.close(raise_on_error=False)
            self.obs.close(raise_pending=False)  # idempotent trace teardown
            # Both exits (incl. preemption — the process is about to die in
            # its SIGTERM grace window — and the target_acc raise) must
            # flush buffered TensorBoard events.
            self.metrics_writer.close()

    def _fit(self) -> dict:
        cfg = self.cfg
        train_loader, eval_loader = self.make_loaders()

        start_epoch = 0
        start_step = 0
        resume = ckpt_lib.resolve_resume(cfg.checkpoint)
        if resume >= 0:
            self.state, start_epoch, start_step = ckpt_lib.restore_checkpoint(
                cfg.checkpoint.directory, resume, self.state)
            self.state = place_state(self.state, self.shardings)
            # Metric sinks must continue the restored step axis, not restart
            # at 1 and double back over the pre-preemption history.
            self._global_step = int(jax.device_get(self.state.step))
            self.coord.print(f"[trainer] resumed at epoch {start_epoch}")

        final_acc = None
        last_eval_epoch = -1
        preempted = False
        with trace(cfg.profile_dir), PreemptionGuard() as guard:
            self._guard = guard
            for epoch in range(start_epoch, cfg.num_epochs):
                self.train_epoch(
                    epoch, train_loader,
                    skip_steps=start_step if epoch == start_epoch else 0)
                if guard.should_stop():
                    # Preempted mid-epoch: next_epoch points back at this
                    # (partial) epoch, and epoch_step records how far into
                    # its deterministic shuffle training got — the resume
                    # skips exactly that prefix (no batch trains twice). A
                    # SIGTERM landing in the final log interval lets the
                    # epoch COMPLETE first; that save must roll over to the
                    # next epoch, or the resume would refuse a skip ==
                    # len(loader).
                    preempted = True
                    if cfg.checkpoint.save_on_preemption:
                        done = self._epoch_step >= len(train_loader)
                        next_ep = epoch + 1 if done else epoch
                        estep = 0 if done else self._epoch_step
                        with self.clock.phase("ckpt"):
                            # sync: the process dies in its grace window
                            # right after this — the save must be durable
                            # (and verified) before returning.
                            self._save_ckpt(epoch, sync=True,
                                            next_epoch=next_ep,
                                            epoch_step=estep)
                        self.coord.print(
                            f"[trainer] SIGTERM: saved preemption checkpoint "
                            f"(resumes at epoch {next_ep} step {estep})")
                    break
                if cfg.eval_every and (epoch + 1) % cfg.eval_every == 0:
                    with self.clock.phase("eval"):
                        final_acc = self.evaluate(eval_loader, train_loader)
                    last_eval_epoch = epoch + 1
                    self.coord.print(
                        f"[eval] epoch {epoch + 1}: top-1 {final_acc:.4f}")
                if cfg.checkpoint.interval and (
                        epoch + 1) % cfg.checkpoint.interval == 0:
                    with self.clock.phase("ckpt"):
                        self._save_ckpt(epoch)
                        self._prune_ckpts()
        self._guard = None
        if self._ckpt_writer is not None:
            # The run's saves must be durable before fit() reports done;
            # a background failure is surfaced as counters + a print, not
            # as a crash of the (successful) training run.
            self._ckpt_writer.wait(raise_on_error=False)
        if preempted:
            return {"final_acc": None, "preempted": True,
                    "last_metrics": self.meter.last,
                    "steps": int(jax.device_get(self.state.step))}

        # --target_acc gate, parsed-but-never-used in the reference
        # (colossal_train.py:43-46) — functional here. Re-evaluate if the
        # last eval predates the final epoch (eval_every ∤ num_epochs), so
        # the gate judges the *final* model, not a stale accuracy.
        if cfg.target_acc is not None:
            if final_acc is None or last_eval_epoch != cfg.num_epochs:
                final_acc = self.evaluate(eval_loader, train_loader)
            if final_acc < cfg.target_acc:
                raise RuntimeError(
                    f"target accuracy {cfg.target_acc} not reached "
                    f"(got {final_acc:.4f})")
        return {"final_acc": final_acc, "preempted": False,
                "last_metrics": self.meter.last,
                "steps": int(jax.device_get(self.state.step))}
