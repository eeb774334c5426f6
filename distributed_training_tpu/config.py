"""Configuration system.

Subsumes the three config styles of the reference
(SURVEY.md §5 "Config / flag system"):

1. hardcoded constants        — ``resnet/pytorch_ddp/ddp_train.py:108-111``
2. argparse + ds_config dict  — ``resnet/deepspeed/deepspeed_train.py:27-129,172-220``
3. argparse plugin selection  — ``resnet/colossal/colossal_train.py:30-50,128-136``

into one dataclass tree with (a) a ``plugin`` strategy enum mirroring the
ColossalAI choice names and (b) :func:`from_ds_config` ingesting the
DeepSpeed-style JSON dict.
"""

from __future__ import annotations

import argparse
import dataclasses
import warnings
from typing import Any, Mapping, Sequence

# Plugin names mirror resnet/colossal/colossal_train.py:38 choices plus the
# unreachable 'gemini' (constructed at :133-134 but not selectable) and a
# 'deepspeed' entry parameterized by --stage (deepspeed_train.py:115-122).
PLUGINS = (
    "torch_ddp",        # pure DP, fp32              (ddp_train.py)
    "torch_ddp_fp16",   # DP + fp16 loss scaling     (colossal_train.py:129-130)
    "low_level_zero",   # ZeRO-1/2 class             (colossal_train.py:135-136)
    "gemini",           # ZeRO-3 class               (colossal_train.py:133-134)
    "deepspeed",        # stage-selected ZeRO        (deepspeed_train.py:210-219)
)


@dataclasses.dataclass(frozen=True)
class OptimizerConfig:
    """Adam hyperparameters.

    Defaults follow the DeepSpeed trainer's ds_config optimizer block
    (``resnet/deepspeed/deepspeed_train.py:175-186``). The DDP/Colossal
    trainers use torch defaults (betas 0.9/0.999, wd 0) with linear LR
    scaling ``lr = 1e-3 * world_size`` (``ddp_train.py:110``,
    ``colossal_train.py:116-122``) — expressed here via ``scale_lr_by_world``.
    """

    # adam | adamw | sgd | lamb | hybrid_adam (Pallas fused)
    name: str = "adam"
    lr: float = 1e-3
    betas: tuple = (0.9, 0.999)
    eps: float = 1e-8
    weight_decay: float = 0.0
    # The ImageNet-recipe convention: don't decay biases/BN/LayerNorm
    # params. "all" decays everything (torch default); "no_1d" masks out
    # rank-<2 params (biases, norm scales/offsets).
    weight_decay_mask: str = "all"  # all | no_1d
    # SGD-family knobs (ignored by the Adam family).
    momentum: float = 0.9
    nesterov: bool = False
    # Parameter EMA (e.g. 0.9999): the optimizer state carries a moving
    # average of the post-update params; evaluation can use it via
    # train/optim.py::ema_params (Trainer does when eval_with_ema).
    ema_decay: float | None = None
    scale_lr_by_world: bool = False
    # Gradient clipping: ds_config "gradient_clipping": 1.0
    # (deepspeed_train.py:195). None disables.
    grad_clip_norm: float | None = None


@dataclasses.dataclass(frozen=True)
class SchedulerConfig:
    """WarmupLR parity (``resnet/deepspeed/deepspeed_train.py:187-194``)."""

    name: str = "constant"  # constant | warmup_lr | cosine
    warmup_min_lr: float = 0.0
    warmup_max_lr: float = 1e-3
    warmup_num_steps: int = 1000
    total_steps: int | None = None  # for cosine decay


@dataclasses.dataclass(frozen=True)
class PrecisionConfig:
    """Mixed-precision policy + dynamic loss scaling.

    ``dtype`` mirrors ``--dtype {bf16,fp16,fp32}``
    (``resnet/deepspeed/deepspeed_train.py:107-114``). The fp16 loss-scaler
    defaults replicate the ds_config fp16 block
    (``deepspeed_train.py:203-207``): dynamic scale (initial 2**15), window
    500, hysteresis 2, min scale 1. ColossalAI's plugins use
    ``initial_scale=2**5`` (``colossal_train.py:134,136``) — selected by the
    plugin presets in :func:`TrainConfig.from_plugin`.
    """

    dtype: str = "fp32"  # bf16 | fp16 | fp32  (compute dtype)
    # fp16 dynamic loss scaling (ignored unless dtype == fp16):
    initial_scale_power: int = 15
    loss_scale_window: int = 500
    hysteresis: int = 2
    min_loss_scale: float = 1.0
    # A fixed (non-dynamic) scale; None means dynamic ("loss_scale": 0 in ds).
    static_loss_scale: float | None = None

    @property
    def initial_scale(self) -> float:
        return float(2 ** self.initial_scale_power)


@dataclasses.dataclass(frozen=True)
class ZeroConfig:
    """ZeRO optimizer/gradient/parameter sharding.

    ``stage`` mirrors ``--stage {0,1,2,3}``
    (``resnet/deepspeed/deepspeed_train.py:115-122``) and the
    ``zero_optimization`` block (``:210-219``). The bucketing/overlap knobs
    (``allgather_bucket_size``, ``reduce_bucket_size``, ``overlap_comm``,
    ``contiguous_gradients``) are accepted for config parity but are
    deliberate no-ops on TPU: XLA's latency-hiding scheduler buckets and
    overlaps collectives itself, so there is nothing to tune by hand. They
    are recorded so ds_config round-trips losslessly.
    """

    stage: int = 0
    # Parity-accepted, XLA-scheduled (documented no-ops):
    allgather_partitions: bool = True
    reduce_scatter: bool = True
    allgather_bucket_size: int = 50_000_000
    reduce_bucket_size: int = 50_000_000
    overlap_comm: bool = True
    contiguous_gradients: bool = True
    # ZeRO-Offload (functional, round 4): the sharded optimizer state lives
    # in pinned HOST memory; the step fetches the shard on-device for the
    # update and streams it back (``parallel/sharding.py``,
    # ``train/step.py::fetch_offloaded_opt_state``). Requires stage >= 1
    # (validated); trades step time for ~12 bytes/param of HBM.
    cpu_offload: bool = False


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    """Mixture-of-experts surface.

    Mirrors the DeepSpeed trainer's MoE CLI flags
    (``resnet/deepspeed/deepspeed_train.py:61-106``). The reference parses
    these but never wires them into its (plain ResNet) model. Here they
    configure the expert-parallel MLP in ``models/moe.py``; Trainer refuses
    ``enabled=True`` with a non-MoE model rather than silently training
    dense the way the reference does.
    """

    enabled: bool = False
    ep_world_size: int = 1
    # Swap every ``every``-th decoder FFN for MoE (GShard's alternating
    # convention at the default 2). ``every=1`` makes EVERY layer MoE —
    # the homogeneous layout the pipeline strategy can stack (round 5).
    every: int = 2
    # One count for every MoE layer, or a per-layer list (DeepSpeed's
    # `--num-experts 64 64 128` nargs surface, deepspeed_train.py:71-75);
    # list length must be 1 or the number of MoE layers
    # (models/gpt.py::moe_layer_experts).
    num_experts: Sequence[int] = (1,)
    mlp_type: str = "standard"  # standard | residual
    top_k: int = 1
    min_capacity: int = 0
    capacity_factor: float = 1.25
    noisy_gate_policy: str | None = None  # None | RSample | Jitter
    # DeepSpeed ``--moe-param-group``: split expert params into their own
    # optimizer groups so ZeRO partitions their state per expert-parallel
    # group (deepspeed_train.py:103-106). Here the rule table always keeps
    # expert moments expert-sharded (that IS the flag's semantics), so the
    # flag is a contract marker: ZeRO×EP *requires* it (LMTrainer raises
    # otherwise) instead of silently implying it.
    moe_param_group: bool = False


@dataclasses.dataclass(frozen=True)
class CheckpointConfig:
    """Checkpoint/resume surface (``resnet/colossal/colossal_train.py:40-42``).

    The reference parses ``--resume/--checkpoint/--interval`` but never wires
    them (``start_epoch = 0`` hardcoded, no save call — SURVEY.md §2.5); here
    they are functional (orbax; see ``checkpoint.py``).
    """

    directory: str = "./checkpoint"
    interval: int = 5          # epochs between saves
    resume: int = -1           # epoch to resume from; -1 = fresh
    keep: int = 3              # retained checkpoints
    # Preemption safety (the failure-handling subsystem the reference lacks,
    # SURVEY.md §5): resume from the newest VERIFIED checkpoint in
    # `directory` when present (torn/uncommitted saves are skipped and
    # quarantined — checkpoint.latest_valid_epoch), and save one on
    # SIGTERM before returning.
    auto_resume: bool = False
    save_on_preemption: bool = True
    # Verified async checkpointing (resilience/async_ckpt.py): the step
    # loop blocks only for the host-side state snapshot; orbax write,
    # checksum manifest, and the atomic COMMITTED marker run on a
    # background writer thread. Single-process runs only — multihost
    # falls back to synchronous saves (orbax coordinates the per-host
    # gathers itself there). Preemption saves always complete before the
    # process returns, async or not.
    async_save: bool = True


@dataclasses.dataclass(frozen=True)
class DataConfig:
    # cifar10 | synthetic_cifar | synthetic_imagenet | imagefolder
    # (imagefolder = lazy <data_path>/{train,val}/<class>/<img> trees)
    dataset: str = "cifar10"
    data_path: str | None = None  # None → $DATA or ../data (ddp_train.py:34)
    batch_size: int = 100      # per-device (ddp_train.py:111)
    global_batch_size: int | None = None  # ds-style; overrides batch_size
    augment: str = "pad_crop_flip"  # pad_crop_flip | normalize_only | none
    num_workers: int = 4
    image_size: int = 32
    num_classes: int = 10
    drop_last: bool = True
    synthetic_ok: bool = True  # fall back to synthetic data if not on disk
    max_steps_per_epoch: int | None = None  # cap train steps (smoke/bench runs)
    # Batches staged ahead of the step (host augment + device DMA overlap
    # with compute; data/prefetch.py). 0 disables.
    prefetch: int = 2
    # imagefolder only: decode the tree ONCE into a uint8 memmap cache and
    # serve epochs from it (data/decoded_cache.py). Turns a decode-bound
    # host (~150 img/s/core) into an augment-bound one (~47k img/s/core).
    decoded_cache: bool = False


@dataclasses.dataclass(frozen=True)
class TraceConfig:
    """Span-level event tracing (``observability/trace.py``).

    Off by default: every integration point holds ``trace=None`` when
    disabled, so no span body executes and the hot loop is byte-identical
    to the untraced code (the transfer-guard test pins that). Enabled, a
    run exports a Chrome/Perfetto ``trace_event`` JSON timeline with one
    track per component (train phases, the async checkpoint writer, chaos
    injections, one track per serving decode slot);
    ``tools/trace_report.py`` summarizes it headlessly.
    """

    enabled: bool = False
    # Where the trace JSON lands. None — the default — resolves next to
    # the flight forensics (``<dump_dir>/trace``) in the trainers; the
    # serving CLIs default it to ``./trace``.
    dir: str | None = None
    # Event-buffer bound: past it, events are dropped and counted in the
    # exported metadata (a forensic trace must never OOM its host).
    max_events: int = 500_000

    def __post_init__(self):
        if self.max_events < 1:
            raise ValueError(
                f"max_events must be >= 1, got {self.max_events}")


@dataclasses.dataclass(frozen=True)
class ObservabilityConfig:
    """Flight instruments (``observability/``): MFU accounting, the
    flight recorder, device-memory telemetry, anomaly-triggered forensics.

    Everything here respects the hot-loop contract of
    ``utils/logging.py``: per-step cost is one host timestamp; every
    other input is read at meter-flush boundaries from values the meter
    already fetched. The reference has none of this surface (its only
    observability is a per-step tqdm loss postfix, SURVEY.md §5).
    """

    # Ring buffer of per-step host timestamps + flushed metrics; dumps
    # step-time p50/p95/max and goodput to JSON on demand / anomaly /
    # crash (``tools/flight_report.py`` renders it).
    flight_recorder: bool = True
    ring_size: int = 1024
    # Where anomaly/crash forensics land (flight JSON, offending batch
    # npz, step HLO, profiler trace). None — the default — resolves to
    # ``<checkpoint.directory>/flight`` in the trainers: forensics
    # belong next to the run's durable artifacts, not in whatever cwd
    # the process crashed from.
    dump_dir: str | None = None
    # Analytic model-FLOPs → ``mfu`` + ``model_flops_per_sec`` at every
    # meter flush (models with a formula: ResNet/ViT/GPT; MoE reports
    # none — routed FLOPs are runtime-dependent).
    mfu: bool = True
    # Override the per-chip peak FLOPs the MFU divides by (None → the
    # device_kind table in observability/flops.py; unknown kinds, e.g.
    # CPU, then omit mfu while keeping model_flops_per_sec).
    peak_flops: float | None = None
    # ``device.memory_stats()`` bytes-in-use / peak at flush boundaries
    # (allocator counters — no device sync; absent on CPU).
    memory_telemetry: bool = True
    # Global L2 grad-norm as an on-device step metric (one extra fused
    # reduction over the already-materialized grads; also what arms the
    # anomaly detector's spike rule).
    grad_norm: bool = False
    # NaN/Inf-loss + grad-norm-spike detection over flushed metrics. On
    # trigger (once per run): dump flight recorder, save batch + HLO,
    # capture an ``anomaly_trace_steps``-step profiler trace, then skip
    # or raise per ``anomaly_action``. A raise is deferred to the end of
    # the trace window and fires on every host at the same step
    # (detector inputs are replicated), so it cannot strand a multihost
    # barrier.
    anomaly_detection: bool = False
    anomaly_action: str = "raise"  # raise | skip
    anomaly_trace_steps: int = 3
    grad_norm_spike_factor: float = 10.0
    # Cross-host step-time skew + straggler attribution at meter-flush
    # boundaries (observability/aggregate.py): per-host payloads are
    # all-gathered (replicated — no stranded barrier; every host flushes
    # at the same deterministic step) and the worst (host, step) cell is
    # named in flight dumps. Single-process runs fall back to a
    # within-host baseline (which step stalled). Requires the flight
    # recorder.
    straggler_attribution: bool = True
    # Recent steps each host contributes to the skew window (fixed shape
    # is what makes the payload all-gatherable).
    straggler_window: int = 256
    # Span-level Perfetto tracing (off by default; see TraceConfig).
    trace: TraceConfig = dataclasses.field(default_factory=TraceConfig)
    # Live telemetry plane (observability/exporter.py): serve /metrics
    # (Prometheus text), /healthz (liveness + run phase) and /vars
    # (strict-JSON flight snapshot) from a background thread while the
    # run is alive. None — the default — binds nothing; 0 binds an
    # ephemeral port (tests). Master process only on multihost. The
    # scrape handler reads the same cached host-side summaries the
    # flight dump reads — never a device value, never a collective.
    metrics_port: int | None = None
    # Exporter bind address. Loopback by default: exposing telemetry
    # beyond the host is an explicit operator decision ("0.0.0.0").
    metrics_host: str = "127.0.0.1"

    def __post_init__(self):
        if self.metrics_port is not None and not (
                0 <= self.metrics_port <= 65535):
            raise ValueError(
                f"metrics_port must be in [0, 65535], got "
                f"{self.metrics_port}")
        if self.anomaly_action not in ("raise", "skip"):
            raise ValueError(
                f"anomaly_action must be 'raise' or 'skip', got "
                f"{self.anomaly_action!r}")
        if self.anomaly_trace_steps < 0:
            raise ValueError(
                f"anomaly_trace_steps must be >= 0, got "
                f"{self.anomaly_trace_steps}")
        if self.straggler_window < 2:
            raise ValueError(
                f"straggler_window must be >= 2, got "
                f"{self.straggler_window}")


@dataclasses.dataclass(frozen=True)
class ChaosConfig:
    """Deterministic fault injection (``resilience/chaos.py``).

    Every fault is step- or epoch-addressed and seeded — a pure function
    of this config, no wall-clock randomness — so chaos runs replay
    bit-identically and recovery paths (preemption save, auto-resume
    fallback, transient-I/O retry) are exercised by tier-1 tests rather
    than only by real TPU evictions. All defaults are inert; the
    trainers build a :class:`~distributed_training_tpu.resilience.chaos.
    ChaosMonkey` only when :attr:`active`.
    """

    seed: int = 0
    # Deliver a termination signal from inside the step loop at this
    # global step: "sigterm" = graceful cloud-TPU eviction (the
    # PreemptionGuard path: finish the step, save, return); "kill" =
    # SIGKILL, hard death with no save (the resume must fall back to the
    # last committed interval save).
    kill_at_step: int | None = None
    kill_signal: str = "sigterm"  # sigterm | kill
    # After this epoch's checkpoint save completes, truncate its largest
    # file and drop the COMMITTED marker — byte-for-byte what a crash
    # mid-write leaves, which latest_valid_epoch must skip.
    torn_ckpt_epoch: int | None = None
    torn_truncate_bytes: int = 64
    # Tear-AFTER-commit: corrupt this epoch's save payload while
    # keeping its COMMITTED marker and manifest — invisible to the
    # marker scan, caught only by the checksum pass. The hot-swap
    # watcher (serving/hotswap.py) must quarantine it at the verify
    # stage instead of deploying it.
    corrupt_ckpt_epoch: int | None = None
    # Probability (per distinct read key, seeded) that a data read
    # raises a ONE-SHOT transient ChaosIOError — the RetryPolicy on the
    # loaders must absorb it.
    data_error_rate: float = 0.0
    # Same, for the hot-swap staging read: the swap attempt must be
    # rejected with a typed SwapError (engine keeps its weights) and
    # the next watcher poll must succeed.
    swap_error_rate: float = 0.0
    # Inject a host-side stall of slow_step_ms every slow_step_every-th
    # step (straggler simulation; shows up as flight-recorder p95).
    slow_step_every: int | None = None
    slow_step_ms: float = 50.0
    # Restrict the slow-step injection to ONE host (process index) of a
    # multihost run — the straggler-attribution drill needs exactly one
    # slow host to pin (observability/aggregate.py). None = every host.
    slow_step_host: int | None = None

    @property
    def active(self) -> bool:
        return (self.kill_at_step is not None
                or self.torn_ckpt_epoch is not None
                or self.corrupt_ckpt_epoch is not None
                or self.data_error_rate > 0
                or self.swap_error_rate > 0
                or self.slow_step_every is not None)

    def __post_init__(self):
        if self.kill_signal not in ("sigterm", "kill"):
            raise ValueError(
                f"kill_signal must be 'sigterm' or 'kill', got "
                f"{self.kill_signal!r}")
        if not 0.0 <= self.data_error_rate <= 1.0:
            raise ValueError(
                f"data_error_rate must be in [0, 1], got "
                f"{self.data_error_rate}")
        if not 0.0 <= self.swap_error_rate <= 1.0:
            raise ValueError(
                f"swap_error_rate must be in [0, 1], got "
                f"{self.swap_error_rate}")
        if self.slow_step_every is not None and self.slow_step_every < 1:
            raise ValueError(
                f"slow_step_every must be >= 1, got {self.slow_step_every}")
        if self.slow_step_host is not None and self.slow_step_host < 0:
            raise ValueError(
                f"slow_step_host must be >= 0, got {self.slow_step_host}")
        if self.torn_truncate_bytes < 0:
            raise ValueError(
                f"torn_truncate_bytes must be >= 0, got "
                f"{self.torn_truncate_bytes}")


def kv_page_size_arg(text: str) -> int:
    """argparse ``type`` of the serving CLIs' ``--kv-page-size``: a value
    :class:`ServeConfig` refuses is a parser error in its words."""
    try:
        return ServeConfig(kv_page_size=int(text)).kv_page_size
    except ValueError as e:
        raise argparse.ArgumentTypeError(str(e)) from None


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Continuous-batching inference engine knobs (``serving/``).

    Everything here is static shape-wise: the engine compiles ONE decode
    step for ``max_batch`` slots × ``max_len`` cache positions and a small
    bucketed family of prefill programs, then serves any request mix
    without retracing (finished sequences leave via per-slot active masks,
    not shape changes).
    """

    # Decode slots: sequences decoded together per iteration. Freed slots
    # refill from the queue at iteration boundaries (Orca-style
    # iteration-level scheduling).
    max_batch: int = 8
    # Per-slot KV-cache positions (prompt + generated). None → the model's
    # max_len; smaller caps shrink the slot cache and tighten admission
    # (inference/sampler.py::cache_budget).
    max_len: int | None = None
    # Default completion budget per request (requests may ask for less).
    max_new_tokens: int = 128
    # Sampling transforms (sampler.py semantics; 0 temperature = greedy).
    temperature: float = 0.0
    top_k: int | None = None
    top_p: float | None = None
    eos_id: int | None = None
    pad_id: int = 0
    # Paged KV cache (vLLM-style block tables; docs/SERVING.md "Paged KV
    # cache"). KV memory is a fixed pool of kv_page_size-token pages and
    # each slot holds a static-shape page table; pages allocate on
    # demand as the write head advances, so a request only ever holds
    # ceil(written/kv_page_size) pages instead of the full max_len
    # budget. Trade-off: smaller pages
    # track the write head tighter (reserved/written → 1) but mean more
    # table entries and a finer-grained gather; larger pages amortize
    # both at the cost of tail-page waste ~ page_size/2 per sequence.
    kv_page_size: int = 8
    # Pool size in pages. None → max_batch × ceil(budget/kv_page_size)
    # (every slot's full budget, no oversubscription); smaller values
    # oversubscribe — admission then gates on committed pages, so a
    # burst of long requests queues instead of overflowing.
    kv_pages: int | None = None
    # Chunked prefill (Sarathi-style): prompts prefill
    # in fixed-size chunks that ride along with decode iterations in ONE
    # fused compiled step, so admission never serializes ahead of
    # decode. One chunk (oldest prefilling request first) per iteration.
    prefill_chunk: int = 64
    # SLA telemetry: flight-recorder ring size (one entry per decode
    # iteration) and iterations between metric flushes into it.
    ring_size: int = 4096
    flush_every: int = 32
    seed: int = 0
    # Graceful degradation (resilience round). Bounded queue depth: a
    # submit that would exceed it is SHED with the typed QueueFullError
    # instead of growing the queue (and every queued request's TTFT)
    # without bound. None = unbounded (the pre-round behavior).
    max_queue_depth: int | None = None
    # Per-request deadlines. A request still queued past its TTFT
    # deadline, or still decoding past its total deadline, is evicted
    # with finish reason "timeout" (partial tokens returned) — overload
    # degrades into bounded per-request latency, not collapse. None
    # disables.
    ttft_deadline_ms: float | None = None
    deadline_ms: float | None = None
    # Speculative decoding (docs/SERVING.md "Speculative decoding"):
    # per decode iteration each slot's drafter proposes up to spec_k
    # tokens and the target model verifies all spec_k+1 positions in ONE
    # dispatch (a fixed-width verify window — the decode step
    # generalized from [max_batch, 1] to [max_batch, spec_k+1]).
    # Acceptance is lossless: every emitted token is the target's own
    # sample under the sequential fold_in(rng, position) stream, so
    # greedy output stays bitwise token-identical to the sequential
    # Generator and sampled output bitwise equal to the non-speculative
    # engine — drafts only decide how many tokens one dispatch lands.
    # 0 = off (the verify window degenerates to the plain decode step).
    # Trade-off: larger k lands more tokens per dispatch when the
    # drafter is right, but pays k+1 positions of target compute per
    # iteration regardless; past the drafter's typical run length the
    # extra width is pure waste.
    spec_k: int = 0
    # Drafter backend: "ngram" = self-contained prompt-lookup drafter
    # (zero extra params, no extra compiled program — the default);
    # "gpt" = a GPT draft model proposing greedily over a fixed
    # spec_draft_window token window (adds ONE compiled 'draft' program;
    # defaults to self-drafting with the serving model's own weights,
    # kept fresh across hot-swaps — a separate small draft model plugs
    # in via Engine(..., drafter=GPTDrafter(model, params))).
    spec_drafter: str = "ngram"
    # Longest context suffix the n-gram drafter matches (it backs off
    # max..1 and proposes the continuation of the most recent match).
    spec_ngram: int = 3
    # GPT drafter: context tokens re-run per draft step (right-aligned,
    # pad-filled); must fit the draft model's positional table.
    spec_draft_window: int = 16
    # SLO tiers + multi-tenant fairness (docs/SERVING.md "Tiered
    # scheduling & preemption"). Requests carry priority 0 (highest,
    # interactive) .. num_tiers-1 (best-effort); admission is strictly
    # tier-ordered, FIFO within a (tier, tenant) lane, and weighted-fair
    # across tenants within a tier. 1 = the old single-FIFO behavior.
    num_tiers: int = 1
    # Max concurrently SEATED sequences per tenant (None = uncapped). A
    # quota-saturated tier falls through to the next tier so slots never
    # idle on a fairness cap.
    tenant_quota: int | None = None
    # tenant -> weighted-fair share (missing tenants weigh 1.0): each
    # seat charges its worst-case token footprint / weight, and the
    # least-charged eligible tenant seats next.
    tenant_weights: dict | None = None
    # Overload headroom reserved for tier 0: requests of priority > 0
    # only seat while MORE than tier_reserved_slots slots are free, and
    # only while committing them would leave at least
    # tier_reserved_pages pool pages uncommitted — so a high-tier
    # arrival finds capacity without even needing a preemption. Tier 0
    # ignores both reserves.
    tier_reserved_slots: int = 0
    tier_reserved_pages: int = 0
    # Lossless preempt-and-requeue (only meaningful with num_tiers > 1):
    # when a higher-tier request cannot seat (slots or pages), evict the
    # worst strictly-lower-tier ACTIVE sequence — its pages are freed
    # and it requeues carrying its emitted tokens; the re-seat
    # re-prefills prompt+emitted and continues the same
    # fold_in(rng, position) stream, so the final output is bitwise
    # identical to an uninterrupted run (pinned by
    # tests/test_preemption.py). False = tiers only order the queue.
    preempt: bool = True
    # Crash-durable serving (serving/journal.py; docs/RESILIENCE.md
    # "Crash-durable serving"). journal_dir enables the write-ahead
    # request journal: admissions are durably recorded before submit()
    # returns, emitted-token batches/preemptions/finishes ride a
    # background writer thread, and Engine.recover() replays the log on
    # restart — finished requests re-deliver exactly once (client
    # cursor), unfinished ones re-seat through the preemption resume
    # path and complete BITWISE equal to an uninterrupted run. None =
    # off (no thread, no I/O).
    journal_dir: str | None = None
    # fsync policy: "none" (OS page cache only — survives kill -9, not
    # power loss), "batch" (one fsync per writer flush — the default
    # durability/latency trade), "always" (fsync per record).
    journal_fsync: str = "batch"
    # Segment rotation threshold: past this many bytes the journal
    # compacts its live state into a fresh segment and deletes the old
    # ones, so the on-disk footprint tracks in-flight work, not run
    # history.
    journal_segment_bytes: int = 1 << 20
    # Radix-tree prefix cache (serving/prefix_cache.py; docs/SERVING.md
    # "Prefix caching"): cross-request KV reuse over the paged pool.
    # Finished sequences' full written pages stay indexed in a
    # content-addressed trie (refcounted, LRU-evicted under pressure,
    # flushed at every hot-swap barrier); a new request whose prompt
    # starts with a resident page-aligned chain aliases those pages
    # into its block table, commits only the non-resident tail, and
    # prefills only that tail — shared system prompts and few-shot
    # preambles prefill ONCE. Bitwise-neutral by construction: a hit
    # changes prefill work, never a token (pinned by
    # tests/test_prefix_cache.py).
    prefix_cache: bool = False
    # Cap on pages the trie may hold (None = bounded only by the pool;
    # LRU leaves evict past the cap). Smaller caps bound the resident
    # working set when the pool is shared with deep decode traffic.
    prefix_cache_pages: int | None = None
    # Quantized execution (serving/quantize.py; docs/SERVING.md
    # "Quantized execution"). quantize_weights=True quantizes the
    # transformer's matmul weights (embedding/attention/MLP kernels) to
    # symmetric per-channel int8 ONCE — at engine construction and at
    # hot-swap arm time on the watcher thread, never inside
    # Engine.step. Layernorms, biases, the positional table and the
    # logits head stay high-precision. Deterministic round-to-nearest:
    # the quantized engine is bitwise-reproducible across runs and
    # batch-composition-independent, quality-bounded rather than
    # bit-equal to fp32 (CI pins greedy exact-match >= 0.98 on the
    # smoke corpus).
    quantize_weights: bool = False
    # KV cache storage dtype for the paged pool: None = model dtype
    # (fp32 pools today), "int8" = pages stored int8 with per-row
    # per-head fp32 scales alongside, quantize-on-scatter /
    # dequantize-in-gather inside the same two compiled programs
    # (inventory grows by zero — sanitizer-pinned). Roughly quarters
    # KV bytes/token vs fp32, so the same kv_pages HBM holds ~4x the
    # tokens; prefix-cache/preemption/journal/speculation operate on
    # quantized pages unchanged (content addressing is host-token-
    # keyed).
    kv_dtype: str | None = None
    # Serving control room (serving/timeseries.py + serving/alerts.py;
    # docs/OBSERVABILITY.md "Serving SLO alerting & incident capture").
    # The engine appends one flat sample of its host-side counters and
    # gauges to a bounded time-series ring every sample_every
    # ITERATIONS (never wall time — the cadence is a pure function of
    # the virtual-dt schedule, so alert decisions over deterministic
    # counters are bitwise-reproducible). The ring holds
    # timeseries_capacity samples (~100 floats each; < 1 MB at the
    # defaults) regardless of run length.
    sample_every: int = 16
    timeseries_capacity: int = 1024
    # Declarative SLO burn-rate rules evaluated at sample cadence:
    # "default" = the shipped set (p95 TTFT/TPOT, shed/timeout rate,
    # pool pressure, zero-tolerance ledger-conservation and
    # journal-write-error watchers), or a ';'-separated clause list —
    # name:metric[/den]>objective[@fast,slow][xBURN][~CLEAR]
    # (serving/alerts.py::parse_slo_rules). None = no alerting (the
    # ring still samples; alert counters report 0).
    slo_rules: str | None = None
    # Incident capture: a firing alert enqueues ONE bundled snapshot
    # (flight dump + ledger_top + the last time-series window + the
    # firing event) for a background writer thread to write atomically
    # under this directory (tools/incident_report.py renders it). None
    # = alerts log/count but write no bundles.
    incident_dir: str | None = None

    def __post_init__(self):
        if self.max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {self.max_batch}")
        if self.max_queue_depth is not None and self.max_queue_depth < 1:
            raise ValueError(
                f"max_queue_depth must be >= 1, got {self.max_queue_depth}")
        for name in ("ttft_deadline_ms", "deadline_ms"):
            v = getattr(self, name)
            if v is not None and v <= 0:
                raise ValueError(f"{name} must be > 0, got {v}")
        if self.max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1, got {self.max_new_tokens}")
        if not isinstance(self.kv_page_size, int) or self.kv_page_size < 1:
            raise ValueError(
                f"kv_page_size must be an integer >= 1, got "
                f"{self.kv_page_size!r}: the contiguous-slot engine "
                f"(kv_page_size=None, --kv-page-size 0) was removed in "
                f"PR 29")
        if self.kv_pages is not None and self.kv_pages < 1:
            raise ValueError(
                f"kv_pages must be >= 1, got {self.kv_pages}")
        if self.prefill_chunk < 1:
            raise ValueError(
                f"prefill_chunk must be >= 1, got {self.prefill_chunk}")
        if self.prefix_cache_pages is not None \
                and self.prefix_cache_pages < 1:
            raise ValueError(
                f"prefix_cache_pages must be >= 1 (or None), "
                f"got {self.prefix_cache_pages}")
        if self.flush_every < 1:
            raise ValueError(
                f"flush_every must be >= 1, got {self.flush_every}")
        if self.max_len is not None and self.max_len < 2:
            raise ValueError(
                f"max_len must be >= 2 (one prompt token + one generated), "
                f"got {self.max_len}")
        if self.spec_k < 0:
            raise ValueError(
                f"spec_k must be >= 0 (0 = speculation off), "
                f"got {self.spec_k}")
        if self.spec_drafter not in ("ngram", "gpt"):
            raise ValueError(
                f"spec_drafter must be 'ngram' or 'gpt', "
                f"got {self.spec_drafter!r}")
        if self.spec_ngram < 1:
            raise ValueError(
                f"spec_ngram must be >= 1, got {self.spec_ngram}")
        if self.spec_draft_window < 1:
            raise ValueError(
                f"spec_draft_window must be >= 1, "
                f"got {self.spec_draft_window}")
        if self.num_tiers < 1:
            raise ValueError(
                f"num_tiers must be >= 1, got {self.num_tiers}")
        if self.tenant_quota is not None and self.tenant_quota < 1:
            raise ValueError(
                f"tenant_quota must be >= 1, got {self.tenant_quota}")
        if self.tenant_weights is not None:
            for t, w in self.tenant_weights.items():
                if not w > 0:
                    raise ValueError(
                        f"tenant weight must be > 0, got {t!r}: {w}")
        if not 0 <= self.tier_reserved_slots < self.max_batch:
            raise ValueError(
                f"tier_reserved_slots must be in [0, max_batch-1] (a "
                f"full reserve would starve every non-top tier), got "
                f"{self.tier_reserved_slots} of {self.max_batch} slots")
        if self.tier_reserved_pages < 0:
            raise ValueError(
                f"tier_reserved_pages must be >= 0, "
                f"got {self.tier_reserved_pages}")
        if self.journal_fsync not in ("none", "batch", "always"):
            raise ValueError(
                f"journal_fsync must be 'none', 'batch' or 'always', "
                f"got {self.journal_fsync!r}")
        if self.journal_segment_bytes < 4096:
            raise ValueError(
                f"journal_segment_bytes must be >= 4096 (a segment "
                f"must hold more than one compaction header), got "
                f"{self.journal_segment_bytes}")
        if self.kv_dtype not in (None, "int8"):
            raise ValueError(
                f"kv_dtype must be None (model dtype) or 'int8', "
                f"got {self.kv_dtype!r}")
        if self.sample_every < 1:
            raise ValueError(
                f"sample_every must be >= 1, got {self.sample_every}")
        if self.timeseries_capacity < 2:
            raise ValueError(
                f"timeseries_capacity must be >= 2, "
                f"got {self.timeseries_capacity}")
        if self.incident_dir is not None and self.slo_rules is None:
            raise ValueError(
                "incident_dir without slo_rules captures nothing: an "
                "incident bundle is written when a rule fires")


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """Logical mesh axis sizes; -1 infers from device count."""

    data: int = -1
    fsdp: int = 1
    model: int = 1
    expert: int = 1
    sequence: int = 1
    pipe: int = 1


@dataclasses.dataclass(frozen=True)
class LMConfig:
    """Transformer-LM model + token-data surface (the long-context workload
    the reference lacks; see ``models/gpt.py`` / ``train/lm_trainer.py``).

    The parallel strategy is NOT chosen here — it follows from the mesh:
    ``sequence>1`` → ring attention, ``model>1`` → megatron TP, ``pipe>1`` →
    GPipe. ``num_microbatches`` only applies to the pipe path.
    """

    seq_len: int = 128
    vocab_size: int = 256
    num_layers: int = 4
    num_heads: int = 4
    hidden_dim: int = 256
    mlp_ratio: int = 4
    max_len: int = 2048
    num_microbatches: int = 1
    # Interleaved/circular pipeline: each pipe device holds this many
    # non-contiguous layer chunks and the activation ring wraps that many
    # times — bubble (S-1)/(v·M+S-1) vs GPipe's (S-1)/(M+S-1). 1 = GPipe.
    # Pipeline strategy only; num_layers must divide by pipe × v.
    virtual_stages: int = 1
    attn_impl: str = "exact"  # exact | flash (Pallas kernel; under a
    # sequence axis the kernel computes each ring hop — ring+flash)
    # Chunked cross-entropy: apply the lm_head + CE over time chunks of
    # this many tokens so the [B, T, vocab] logits never materialize
    # (B8·T16k·V50k fp32 = 26 GB — the memory wall for long-context ×
    # large-vocab training). None = whole-sequence logits. Must divide
    # the (per-shard) sequence length; composes with the pipeline
    # executor since round 3 (pinned by
    # test_pipeline_composes_with_chunking).
    ce_chunk_size: int | None = None
    # CE backward from saved bf16 softmax probs instead of re-reading the
    # logits and re-running exp in both lm_head backward matmul fusions.
    # Measured +2.2k tok/s under fp32 logits (117.2k → 119.4k, GPT-2-small
    # B16 T1024), a small LOSS under bf16 logits (the backward reads are
    # already bf16) — use with logits_dtype="fp32" only. Does not compose
    # with ce_chunk_size (train/lm_step.py::_check_ce_options).
    ce_save_probs: bool = False
    # Per-step train token accuracy: a bonus metric over the reference's
    # loss-only logging. Derived from the CE's own row max since round 5
    # (tie-inclusive top-1, no extra HBM pass) so it is nearly free; False
    # drops the metric key for exact loss-only parity with the reference.
    metrics_accuracy: bool = True
    # Head/logits compute dtype: "bf16" (default since round 6, matching
    # the train.py/bench.py/generate.py CLI defaults — ADVICE r5 flagged
    # the divergence) or "fp32". bf16 halves the [B, T, vocab] logits HBM
    # round-trips (measured +7% tok/s on GPT-2-small T1024 in round 4;
    # 8-epoch chip A/B tracks fp32 to the 4th decimal, round 5);
    # the CE still reduces in fp32 (train/lm_step.py::_fused_ce_rows),
    # only the stored logits round to bf16. tests/test_config.py pins
    # config default == CLI default.
    logits_dtype: str = "bf16"
    # lm_head bias. Default OFF since round 5: GPT-2's real head has none,
    # and its gradient is a full extra HBM pass over the [B, T, vocab]
    # logits (profiled 2.3 ms/step at GPT-2-small T1024). True restores
    # the pre-round-5 tree (needed to resume old checkpoints); the
    # gpt/jax_tpu CLIs default to the same value so train → generate
    # round-trips at bare defaults.
    head_bias: bool = False
    corpus_path: str | None = None  # byte-level text file; None → synthetic
    train_sequences: int = 2048     # synthetic dataset size
    eval_sequences: int = 256


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    model: str = "resnet18"
    plugin: str = "torch_ddp"
    num_epochs: int = 5        # all three trainers (ddp_train.py:108)
    # DeepSpeed semantics: effective batch = micro/device × accum × world.
    # The step consumes one effective batch and scans accum microbatches
    # through fwd/bwd before the single optimizer update.
    gradient_accumulation_steps: int = 1
    # Uniform label smoothing for the classification CE (ImageNet recipe);
    # 0 = the reference's plain nn.CrossEntropyLoss.
    label_smoothing: float = 0.0
    # Evaluate with the EMA parameters when optimizer.ema_decay is set.
    eval_with_ema: bool = True
    # Activation checkpointing (jax.checkpoint per block): O(depth)
    # activation memory for ~30% extra backward FLOPs. Unlocks configs
    # that otherwise OOM (e.g. ViT-B/16 batch 512/chip on v5e).
    remat: bool = False
    # Ring-overlapped tensor parallelism (mesh.model > 1 only): decompose
    # the megatron layer collectives into per-shard ppermute rings fused
    # with the partial matmuls, hiding the TP communication behind compute
    # (parallel/collective_matmul.py). Applies to the transformer LM and
    # ViT TP paths; no-op at model == 1. Default off — the declarative
    # GSPMD schedule remains the baseline.
    tp_overlap: bool = False
    seed: int = 0
    log_interval: int = 100    # steps between host-side loss fetches
    target_acc: float | None = None  # colossal_train.py:43-46, wired here
    eval_every: int = 1        # epochs between eval passes
    # Precise-BN: refresh BatchNorm running statistics with N train-mode
    # forwards (current params, no optimizer) right before each eval. The
    # running-stat EMA (momentum 0.9) lags the parameters it normalizes
    # for; when params move fast (high LR, loss-scale skip bursts) the
    # stale stats can cost tens of accuracy points at eval even though
    # train-mode accuracy is fine. 0 = off (raw EMA stats, torch parity).
    eval_precise_bn_batches: int = 0
    sync_batchnorm: bool = True
    optimizer: OptimizerConfig = dataclasses.field(default_factory=OptimizerConfig)
    scheduler: SchedulerConfig = dataclasses.field(default_factory=SchedulerConfig)
    precision: PrecisionConfig = dataclasses.field(default_factory=PrecisionConfig)
    zero: ZeroConfig = dataclasses.field(default_factory=ZeroConfig)
    moe: MoEConfig = dataclasses.field(default_factory=MoEConfig)
    checkpoint: CheckpointConfig = dataclasses.field(default_factory=CheckpointConfig)
    data: DataConfig = dataclasses.field(default_factory=DataConfig)
    lm: LMConfig = dataclasses.field(default_factory=LMConfig)
    mesh: MeshSpec = dataclasses.field(default_factory=MeshSpec)
    # Profiling: ds_config "wall_clock_breakdown" (deepspeed_train.py:209).
    wall_clock_breakdown: bool = False
    profile_dir: str | None = None
    # Durable metric sinks (master-only, written at log_interval flushes).
    tensorboard_dir: str | None = None
    metrics_jsonl: str | None = None
    # Flight instruments: MFU/goodput accounting, device-memory telemetry,
    # anomaly-triggered trace capture (observability/).
    observability: ObservabilityConfig = dataclasses.field(
        default_factory=ObservabilityConfig)
    # Deterministic fault injection (resilience/chaos.py); inert by
    # default — see ChaosConfig.active.
    chaos: ChaosConfig = dataclasses.field(default_factory=ChaosConfig)

    def replace(self, **kw: Any) -> "TrainConfig":
        return dataclasses.replace(self, **kw)

    @staticmethod
    def from_plugin(plugin: str, **overrides: Any) -> "TrainConfig":
        """Build a config from a ColossalAI-style plugin name.

        Presets encode what each reference plugin actually configures:
        - torch_ddp       → DP fp32, Adam(lr·world)   (ddp_train.py:95-110)
        - torch_ddp_fp16  → DP + fp16 booster kwarg   (colossal_train.py:129-130)
        - low_level_zero  → ZeRO-1, initial_scale 2^5 (colossal_train.py:135-136)
        - gemini          → ZeRO-3-like, scale 2^5    (colossal_train.py:133-134)
        - deepspeed       → stage via overrides        (deepspeed_train.py:210-219)
        """
        if plugin not in PLUGINS:
            raise ValueError(f"unknown plugin {plugin!r}; choose from {PLUGINS}")
        opt = OptimizerConfig(scale_lr_by_world=True)
        prec = PrecisionConfig()
        zero = ZeroConfig()
        if plugin == "torch_ddp_fp16":
            prec = PrecisionConfig(dtype="fp16")
        elif plugin == "low_level_zero":
            prec = PrecisionConfig(dtype="fp16", initial_scale_power=5)
            zero = ZeroConfig(stage=1)
        elif plugin == "gemini":
            prec = PrecisionConfig(dtype="fp16", initial_scale_power=5)
            zero = ZeroConfig(stage=3)
        elif plugin == "deepspeed":
            opt = OptimizerConfig(
                betas=(0.8, 0.999), eps=1e-8, weight_decay=3e-7,
                grad_clip_norm=1.0,
            )
        cfg = TrainConfig(plugin=plugin, optimizer=opt, precision=prec, zero=zero)
        return cfg.replace(**overrides) if overrides else cfg


def effective_batch_sizes(cfg: TrainConfig, world: int,
                          allow_derive: bool = True) -> tuple[int, int, int]:
    """Resolve ``(train_global_batch, eval_global_batch, accum_steps)``.

    DeepSpeed's batch triple semantics (train_batch_size = micro × accum ×
    world), resolved at the one place world size is known (the trainers):

    - no ``global_batch_size``: effective = batch_size × world × accum.
    - ``global_batch_size`` set and an exact >1 multiple of batch_size ×
      world while accum was left at 1: accum is *derived* (DeepSpeed:
      ``accum = train_batch_size / (micro × world)``). The image steps
      (GSPMD and shard_map local-BN) and the GSPMD/sequence LM steps scan
      accum microbatches through fwd/bwd; the pipeline LM strategy maps
      accum onto its own schedule instead (DeepSpeed pipeline semantics:
      accumulation IS microbatching — the trainer multiplies
      ``num_microbatches`` by accum and drains them all before the one
      update, see ``LMTrainer._pp_microbatches``).
    - otherwise ``global_batch_size`` wins as the effective batch (the
      reference's ds_config sets only ``train_batch_size: 96``,
      ``deepspeed_train.py:173``) and must divide by accum.

    Eval always runs micro-sized batches: the optimizer never sees an eval
    batch, and accumulation exists precisely because effective-batch
    forwards don't fit.
    """
    accum = cfg.gradient_accumulation_steps
    if accum < 1:
        raise ValueError(f"gradient_accumulation_steps must be >= 1, got {accum}")
    micro_gbs = cfg.data.batch_size * world
    gbs = cfg.data.global_batch_size
    if gbs is None:
        return micro_gbs * accum, micro_gbs, accum
    if allow_derive and accum == 1 and gbs > micro_gbs and gbs % micro_gbs == 0:
        accum = gbs // micro_gbs
    if gbs % accum:
        raise ValueError(
            f"global batch {gbs} not divisible by "
            f"gradient_accumulation_steps={accum}")
    return gbs, gbs // accum, accum


def from_ds_config(ds: Mapping[str, Any], base: TrainConfig | None = None) -> TrainConfig:
    """Ingest a DeepSpeed-style config dict.

    Maps every field of the reference's ds_config
    (``resnet/deepspeed/deepspeed_train.py:172-220``) onto the dataclass
    tree. Unknown keys raise, so silent config drift is impossible.
    """
    cfg = base or TrainConfig.from_plugin("deepspeed")
    known = {
        "train_batch_size", "train_micro_batch_size_per_gpu", "steps_per_print",
        "gradient_accumulation_steps", "activation_checkpointing",
        "optimizer", "scheduler", "gradient_clipping", "prescale_gradients",
        "bf16", "fp16", "wall_clock_breakdown", "zero_optimization",
    }
    unknown = set(ds) - known
    if unknown:
        raise ValueError(f"unknown ds_config keys: {sorted(unknown)}")

    opt = cfg.optimizer
    if "optimizer" in ds:
        p = ds["optimizer"].get("params", {})
        opt_type = ds["optimizer"].get("type", "Adam").lower()
        if opt_type in ("adam", "adamw", "lamb"):
            # One moments-family mapping; 'adamw' selects DECOUPLED weight
            # decay in make_optimizer, plain 'adam' couples it into the
            # moments (torch semantics), 'lamb' adds trust ratios.
            opt = dataclasses.replace(
                opt,
                name=opt_type,
                lr=p.get("lr", opt.lr),
                betas=tuple(p.get("betas", opt.betas)),
                eps=p.get("eps", opt.eps),
                weight_decay=p.get("weight_decay", opt.weight_decay),
            )
        elif opt_type == "sgd":
            opt = dataclasses.replace(
                opt,
                name="sgd",
                lr=p.get("lr", opt.lr),
                momentum=p.get("momentum", opt.momentum),
                nesterov=bool(p.get("nesterov", opt.nesterov)),
                weight_decay=p.get("weight_decay", opt.weight_decay),
            )
        else:
            raise ValueError(
                f"unsupported ds optimizer type {ds['optimizer'].get('type')!r}"
                " (adam | adamw | sgd | lamb)")
    if "gradient_clipping" in ds:
        opt = dataclasses.replace(opt, grad_clip_norm=float(ds["gradient_clipping"]))

    sched = cfg.scheduler
    if "scheduler" in ds:
        if ds["scheduler"].get("type") != "WarmupLR":
            raise ValueError("only WarmupLR scheduler is supported from ds_config")
        p = ds["scheduler"].get("params", {})
        sched = SchedulerConfig(
            name="warmup_lr",
            warmup_min_lr=p.get("warmup_min_lr", 0.0),
            warmup_max_lr=p.get("warmup_max_lr", opt.lr),
            warmup_num_steps=p.get("warmup_num_steps", 1000),
        )

    prec = cfg.precision
    if ds.get("bf16", {}).get("enabled"):
        prec = dataclasses.replace(prec, dtype="bf16")
    fp16 = ds.get("fp16", {})
    if fp16.get("enabled"):
        loss_scale = fp16.get("loss_scale", 0)
        prec = PrecisionConfig(
            dtype="fp16",
            initial_scale_power=fp16.get("initial_scale_power", 15),
            loss_scale_window=fp16.get("loss_scale_window", 500),
            hysteresis=fp16.get("hysteresis", 2),
            min_loss_scale=fp16.get("min_loss_scale", 1.0),
            static_loss_scale=None if loss_scale == 0 else float(loss_scale),
        )

    zero = cfg.zero
    if "zero_optimization" in ds:
        z = dict(ds["zero_optimization"])
        zero = ZeroConfig(
            stage=z.pop("stage", 0),
            allgather_partitions=z.pop("allgather_partitions", True),
            reduce_scatter=z.pop("reduce_scatter", True),
            allgather_bucket_size=z.pop("allgather_bucket_size", 50_000_000),
            reduce_bucket_size=z.pop("reduce_bucket_size", 50_000_000),
            overlap_comm=z.pop("overlap_comm", True),
            contiguous_gradients=z.pop("contiguous_gradients", True),
            cpu_offload=z.pop("cpu_offload", False),
        )
        if z:
            raise ValueError(f"unknown zero_optimization keys: {sorted(z)}")

    data = cfg.data
    if "train_batch_size" in ds:
        data = dataclasses.replace(data, global_batch_size=int(ds["train_batch_size"]))
    if "train_micro_batch_size_per_gpu" in ds:
        data = dataclasses.replace(data, batch_size=int(ds["train_micro_batch_size_per_gpu"]))

    # "prescale_gradients": true divides gradients by world_size BEFORE the
    # all-reduce (a GPU fp16-overflow mitigation). Gradient reduction here
    # is lax.pmean / GSPMD-inserted mean with fp32 accumulation, which
    # applies the 1/world_size scaling inside the one fused collective —
    # either setting yields the averaged gradient, so the knob is accepted
    # as a documented no-op (like the zero_optimization bucketing knobs).
    if not isinstance(ds.get("prescale_gradients", False), bool):
        raise ValueError("prescale_gradients must be a bool")

    # DeepSpeed's activation_checkpointing block maps onto per-block remat.
    # In DeepSpeed the block only CONFIGURES the checkpointing API — nothing
    # is checkpointed unless the model itself calls
    # deepspeed.checkpointing.checkpoint — so inferring remat from the
    # block's mere presence would silently charge ~30% extra backward FLOPs
    # on parity configs. Remat therefore needs an opt-in signal: the
    # dedicated "enabled": true extension key, or any truthy functional
    # sub-knob (partition_activations / cpu_checkpointing /
    # number_checkpoints / contiguous_memory_optimization — a config that
    # sets these describes a model that DOES checkpoint). An all-false
    # block leaves remat off; profile / synchronize_checkpoint_boundary are
    # observability knobs and carry no intent. The sub-knobs themselves are
    # GPU-memory plumbing with no TPU analogue — validated, then no-ops.
    remat = cfg.remat
    if "activation_checkpointing" in ds:
        ac = ds["activation_checkpointing"]
        if isinstance(ac, Mapping):
            functional = {
                "enabled", "partition_activations", "cpu_checkpointing",
                "contiguous_memory_optimization", "number_checkpoints",
            }
            unknown_ac = set(ac) - functional - {
                "synchronize_checkpoint_boundary", "profile",
            }
            if unknown_ac:
                raise ValueError(
                    f"unknown activation_checkpointing keys: "
                    f"{sorted(unknown_ac)}")
            if "enabled" in ac:
                # The dedicated key is authoritative in both directions.
                remat = bool(ac["enabled"])
            elif any(ac.get(k) for k in functional):
                remat = True
            elif not remat:
                # The block is present but carries no opt-in signal — a
                # config written against the old presence-implies-remat
                # inference would silently lose checkpointing (and can OOM
                # with no other symptom), so say what happened once.
                warnings.warn(
                    "activation_checkpointing block present but all "
                    "functional sub-knobs are false — remat stays OFF. "
                    'Set {"activation_checkpointing": {"enabled": true}} '
                    "to opt in.", stacklevel=2)
        else:
            remat = bool(ac)

    return cfg.replace(
        optimizer=opt, scheduler=sched, precision=prec, zero=zero, data=data,
        remat=remat,
        gradient_accumulation_steps=int(
            ds.get("gradient_accumulation_steps",
                   cfg.gradient_accumulation_steps)),
        log_interval=int(ds.get("steps_per_print", cfg.log_interval)),
        wall_clock_breakdown=bool(ds.get("wall_clock_breakdown", cfg.wall_clock_breakdown)),
    )
