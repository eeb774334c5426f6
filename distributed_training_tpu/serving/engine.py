"""Continuous-batching inference engine: admit → prefill → decode → evict.

The training stack's decode loop (``inference/sampler.py``) compiles one
``generate`` program per prompt: great latency for one user, zero
batching across users. This engine turns the same
``RingSelfAttention`` KV cache into a multi-tenant server.

**Paged KV + chunked prefill (docs/SERVING.md "Paged KV cache").** KV
memory is one fixed pool of ``ServeConfig.kv_page_size``-token pages
per layer (PagedAttention's layout); each decode slot holds a
static-shape page table mapping logical pages → physical pages, pages
allocate on demand as the write head advances, and admission commits a
request's worst-case page count instead of the full ``max_len`` budget.
Prefill is chunked (Sarathi-Serve): the prompt splits into fixed-size
``prefill_chunk`` pieces that ride along with decode iterations in ONE
fused compiled step, so admission never serializes ahead of decode.
Compiled-program inventory: a fused prefill-chunk+decode step and a
decode-only step — two programs, one shape each, regardless of prompt
mix (the chunk lane is always ``[1, prefill_chunk]``, padded rows write
the pool's null page).

The discipline — masks, never shapes:

- **Iteration-level scheduling.** At each iteration boundary the
  :class:`SlotScheduler` evicts finished sequences (EOS / length budget
  / deadline) and refills freed slots FIFO from the
  :class:`RequestQueue` (page-aware: the queue head seats only when
  the pool can commit its worst case). Slot membership is
  boolean masks and page-table contents — shapes never change, nothing
  retraces.
- **One device step ahead of the host.** A call to
  ``step()`` delivers the tokens of one device step and, where it may,
  has launched the next one first: nothing the host puts into a step
  depends on a token's value, only on counts, and a slot's incoming
  token is picked on the device from the previous step's own output
  (``_iterate_paged``; docs/SERVING.md "The iteration's order"). What
  changes the world between two steps — a swap barrier, a preemption,
  a cancel or an expired deadline of a seated request, a drafter —
  lands the step in flight first.
- **Lane independence = bitwise determinism.** A slot's row arithmetic
  is identical regardless of which other requests share the batch
  (rows of every position-wise op and of the per-row paged attention —
  kernel or gather — are independent), and sampling RNG is
  ``fold_in(fold_in(seed, uid), position)`` — a pure function of the
  request and position. A
  request's tokens are therefore bitwise independent of batch
  composition AND of the paging/chunking configuration, and greedy
  decode is token-identical to the sequential ``Generator`` (pinned by
  ``tests/test_serving.py``).

**Prefix caching** (``ServeConfig.prefix_cache``;
``serving/prefix_cache.py``, docs/SERVING.md "Prefix caching"): a
content-addressed radix trie indexes finished sequences' committed page
chains at page granularity. A seat whose prompt starts with a resident
page-aligned chain aliases those physical pages into its block table
(refcounted), commits only the non-resident tail, and chunk-prefills
only that tail — shared system prompts and few-shot preambles prefill
ONCE across the fleet of requests. The n-gram drafter composes for
free: it proposes from the host-side token stream, which a hit never
changes — so speculation reads the reused prefix without touching a
page. Bitwise-neutral by construction (a hit changes prefill work,
never a gathered value or sampled token); every hot-swap barrier
flushes the trie so old-weight KV cannot seed a new-epoch request.

**Speculative decoding** (``ServeConfig.spec_k`` > 0;
``serving/speculative.py``, docs/SERVING.md "Speculative decoding"): a
per-slot drafter proposes up to ``spec_k`` tokens each iteration and
the decode lane widens to a fixed ``[max_batch, spec_k + 1]`` verify
window — the target model verifies every position in the one dispatch
it was already paying for. Acceptance is an argmax over a mismatch
mask (static shape) and is lossless by construction: each position's
token is the target's own sample under the sequential
``fold_in(rng, position)`` stream, so emitted tokens are bitwise
identical to the non-speculative engine and the sequential
``Generator`` — drafts only set how many of them land per dispatch.
The compiled-program inventory is unchanged (the window IS the decode
step); a GPT drafter adds one single-shape ``draft`` program.

SLA telemetry (TTFT / TPOT / throughput / queue depth / KV-page
utilization / draft acceptance) flows through the round-7 flight
recorder via :class:`ServeTelemetry`; ``dump_flight`` writes a
``tools/flight_report.py``-readable record. Every request additionally
carries a **latency ledger** (``serving/ledger.py``): ``(cause, start,
end)`` intervals stamped at the measurement points this loop already
pays for — seat, chunk boundary, decode iteration, spec rollback,
preemption, swap barrier, journal admission, recovery replay, finish —
whose causes partition the request's wall lifetime; the engine audits
per-request conservation (``sum(intervals) == lifetime`` within
``ledger.EPSILON_S``) at every completion and counts violations
zero-tolerance (docs/OBSERVABILITY.md "Latency ledger").
"""

from __future__ import annotations

import dataclasses
import functools
import threading
import time
from typing import Any

import flax
import jax
import jax.numpy as jnp
import numpy as np

from distributed_training_tpu.config import ServeConfig
from distributed_training_tpu.inference.sampler import (
    SampleConfig,
    cache_budget,
    check_unsharded,
    sample_token,
)
from distributed_training_tpu.models.gpt import init_decode_cache
from distributed_training_tpu.observability import trace as trace_lib
from distributed_training_tpu.parallel.ring_attention import PagedKV
from distributed_training_tpu.resilience.errors import SwapError
from distributed_training_tpu.serving.alerts import (
    AlertEngine,
    IncidentWriter,
    parse_slo_rules,
)
from distributed_training_tpu.serving.journal import RequestJournal, perf_of
from distributed_training_tpu.serving.ledger import (
    CAUSE_CANCELLED,
    CAUSE_DECODE,
    CAUSE_JOURNAL_ADMIT,
    CAUSE_PRE_CRASH,
    CAUSE_PREEMPT_REQUEUE,
    CAUSE_PREFILL,
    CAUSE_PREFIX_HIT,
    CAUSE_QUEUE_WAIT,
    CAUSE_RECOMPUTE,
    CAUSE_RECOVERY,
    CAUSE_SPEC_ACCEPT,
    CAUSE_SPEC_DRAFT,
    CAUSE_SPEC_ROLLBACK,
    CAUSE_SWAP_BARRIER,
    LEDGER_CAUSES,
)
from distributed_training_tpu.serving.metrics import ServeTelemetry
from distributed_training_tpu.serving.pages import PagePool, pages_for
from distributed_training_tpu.serving.prefix_cache import PrefixCache
from distributed_training_tpu.serving.queue import RequestQueue
from distributed_training_tpu.serving.request import (
    FINISH_CANCELLED,
    FINISH_PREEMPT_TIMEOUT,
    FINISH_SHED,
    FINISH_TIMEOUT,
    ActiveSequence,
    FinishedRequest,
    Request,
)
from distributed_training_tpu.serving.scheduler import SlotScheduler
from distributed_training_tpu.serving.speculative import (
    make_drafter,
    truncate_at_eos,
)
from distributed_training_tpu.serving.timeseries import (
    TIMESERIES_DUMP_SAMPLES,
    TelemetryRing,
)


# Where a decode lane's incoming token comes from (``_DeviceStep.d_src``,
# ``Engine._incoming``): the host's own ``d_tok``; the step before's
# ``nxt[slot, 0]``; or, for a slot whose final chunk was that step, its
# ``c_sampled[row]`` as ``_SRC_CHUNK + row``.
_SRC_HOST, _SRC_NXT, _SRC_CHUNK = 0, 1, 2


@dataclasses.dataclass(frozen=True)
class _StepLayout:
    """Where each thing the host hands a device step lies in the ONE
    int32 vector a launch transfers (one transfer costs the host the
    same whatever its size, so six or eleven of them were most of a
    launch: PERF.md §5).

    A block ``[slots, 3·width + 1 + key_words + pages]`` — per decode
    lane its tokens, positions, valid rows as 0/1, source (``_SRC_*``),
    RNG key words bit-cast from uint32, and page-table row — and, for
    the fused program only, a tail of ``3·chunk + 1``: the chunk's
    tokens, positions, valid rows and its SLOT, whose table row and key
    the device takes from the block. :meth:`pack` is host numpy,
    :meth:`unpack` runs under ``jit``; no field changes value between
    the two."""

    slots: int
    width: int
    key_words: int
    pages: int
    chunk: int

    @property
    def lane(self) -> int:
        return 3 * self.width + 1 + self.key_words + self.pages

    @functools.cached_property
    def _cols(self) -> tuple[slice, ...]:
        """A lane's six fields as column slices, in the block's order."""
        widths = (self.width,) * 3 + (1, self.key_words, self.pages)
        ends = np.cumsum(widths).tolist()
        return tuple(map(slice, [0] + ends[:-1], ends))

    def size(self, fused: bool) -> int:
        return self.slots * self.lane + (3 * self.chunk + 1) * fused

    def pack(self, d_tok, d_pos, d_valid, d_src, rngs, tables,
             chunk=(), chunk_slot: int = 0) -> np.ndarray:
        """A fresh buffer each call: the step it is handed to may read
        it where it lies (a CPU backend aliases host memory), and the
        host goes on writing its tables and keys."""
        n = self.slots * self.lane
        fused = len(chunk) > 0
        buf = np.empty((self.size(fused),), np.int32)
        lanes = buf[:n].reshape(self.slots, self.lane)
        for cols, field in zip(self._cols, (
                d_tok, d_pos, d_valid, d_src[:, None],
                rngs.view(np.int32), tables)):
            lanes[:, cols] = field
        if fused:
            buf[n:-1].reshape(3, self.chunk)[:] = chunk
            buf[-1] = chunk_slot
        return buf

    def unpack(self, packed):
        """``(tok, pos, valid, rngs, tables, src), chunk`` as
        ``Engine._decode_step`` and ``_chunk_step`` take them: ``chunk``
        is ``(c_tok, c_pos, c_valid, c_table, c_rng)`` for a buffer with
        the fused tail and None without. Static slices, but for the
        chunk's slot."""
        n = self.slots * self.lane
        if packed.shape not in ((self.size(False),), (self.size(True),)):
            raise ValueError(
                f"a packed step of shape {packed.shape} fits neither "
                f"program of {self}")
        lanes = packed[:n].reshape(self.slots, self.lane)
        tok, pos, valid, src, keys, tables = (
            lanes[:, cols] for cols in self._cols)
        rngs = jax.lax.bitcast_convert_type(keys, jnp.uint32)
        lane = (tok, pos, valid != 0, rngs, tables, src[:, 0])
        if packed.shape[0] == n:
            return lane, None
        c_tok, c_pos, c_valid = packed[n:-1].reshape(3, self.chunk)
        slot = packed[-1]
        return lane, (c_tok, c_pos, c_valid != 0, tables[slot][None],
                      rngs[slot])


@dataclasses.dataclass
class _DeviceStep:
    """One device step of the iteration, from its assembly on the
    host to the landing of its tokens — which may be one call to
    :meth:`Engine.step` later (``Engine._in_flight``)."""

    program: str                       # "fused" or "decode"
    decoding: list                     # the decode lane's sequences
    d_tok: np.ndarray
    d_pos: np.ndarray
    d_valid: np.ndarray
    d_src: np.ndarray                  # [slots] of _SRC_*
    useful_by_slot: dict
    drafted: int
    chunk_seq: ActiveSequence | None   # the chunk lane's sequence
    start: int                         # the chunk's first position
    c: int                             # and its valid rows
    chunk: tuple                       # (c_tok, c_pos, c_valid), or ()
    # slot -> the sequence whose pages this step writes
    lanes: dict
    # slot -> pages it held when this step was assembled, and how many
    # of the pool's pages that assembly drew
    pages_held: dict
    pages_drawn: int
    # this step's counters on the span of the iteration that delivers it
    attrs: dict
    # what the launch returned: the arrays the next launch reads on the
    # device are the very ones the host fetches
    t0: float = 0.0
    nxt: Any = None
    acc: Any = None
    c_sampled: Any = None
    counted: Any = None
    # page releases of sequences that left while this step was in flight:
    # handed to the pool when it has landed
    held: list = dataclasses.field(default_factory=list)


class Engine:
    """Continuous-batching serving engine for a decoder LM.

    The model is any module with the interface the engine drives:
    ``apply(tokens, positions, train, decode, mutable, pages)`` writing a
    ``cache`` collection, ``clone(cache_len, kv_page_size, kv_pages,
    kv_dtype)``, ``max_len`` (the most positions a sequence may have) and,
    about itself, ``paged_lane(t_in, page_size, kv_dtype)`` (the name of
    the attention formulation a paged call that wide takes),
    ``attended_rows(live)`` (how many of a slot's live rows one query
    reads), ``index_rows_scored(live, budget)`` (how many index keys a
    decoding slot's lane scores to select them; 0 without an indexer) and
    ``step_counters`` (names of int32 scalars it sows into a
    ``counters`` collection each step: docs/SERVING.md "What a model
    tells the engine"). :class:`~distributed_training_tpu.models.gpt.
    TransformerLM`, :class:`~distributed_training_tpu.models.
    deepseek_v32.DeepseekV32LM` and :class:`~distributed_training_tpu.
    models.keye_vl2.KeyeVL2LM` are the three.

    >>> eng = Engine(model, params, ServeConfig(max_batch=8))
    >>> eng.submit(prompt_tokens)
    >>> done = eng.run()          # list[FinishedRequest]
    >>> eng.stats()               # SLA summary dict

    Thread model: ``submit`` is safe from any thread (the queue locks);
    ``step``/``run`` belong to one serving thread.

    ``trace`` (an :class:`~distributed_training_tpu.observability.trace.
    TraceSession`, or None = off) draws the engine on a Perfetto
    timeline: the ``serve.iteration`` spans and their
    phases on an 'engine' track (``observability/trace.py::span``), a
    queue-depth counter series, admission marks on a 'queue' track, and
    — the Orca view — one track PER DECODE SLOT carrying each request's
    serve.queued → serve.prefill (per-chunk spans) → decode lifecycle
    and finish marks. All timestamps come from the same ``perf_counter``
    clock as :class:`ServeTelemetry`, so span-derived latencies equal
    the SLA numbers exactly (pinned by tests/test_trace.py).
    """

    @trace_lib.span("setup.engine_init")
    def __init__(self, model: Any, params: Any, cfg: ServeConfig, *,
                 trace=None, weights_epoch: int = -1, drafter=None):
        check_unsharded(model)
        self.cfg = cfg
        self.trace = trace
        self.budget = cache_budget(model, cfg.max_len)
        if self.budget < 2:
            raise ValueError(
                f"cache budget {self.budget} cannot hold a prompt token "
                f"plus a generated token")
        # Quantized execution (serving/quantize.py; docs/SERVING.md
        # "Quantized execution"): per-channel int8 matmul weights,
        # quantized ONCE here — construction is off the hot path by
        # definition — and again for every hot-swap candidate at arm
        # time on the watcher thread (arm_swap). Engine.step only ever
        # binds the already-quantized tree as a step argument.
        self._quantize_weights = bool(cfg.quantize_weights)
        self._weight_quant_s = 0.0
        self._quantized_params_bytes = 0
        # The fp32 abstract tree is pinned BEFORE quantization: hot-swap
        # candidates arrive from checkpoints as fp32 trees and
        # validate_swap must recognize them as armable (arm quantizes).
        self._fp32_params_abstract = (jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(jnp.shape(a),
                                           jnp.result_type(a)), params)
            if self._quantize_weights else None)
        if self._quantize_weights:
            from distributed_training_tpu.serving.quantize import (
                quantize_params,
                quantized_param_bytes,
            )

            t0_q = time.perf_counter()
            params = quantize_params(params)
            self._weight_quant_s = time.perf_counter() - t0_q
            self._quantized_params_bytes = quantized_param_bytes(params)
        self.params = params
        # Speculative decoding (serving/speculative.py): the decode step
        # becomes a [max_batch, spec_k + 1] verify window — spec_k drafts
        # per slot verified alongside the incoming token in one dispatch,
        # with a mask-based accept so every shape stays static. spec_k=0
        # degenerates to the plain one-token step (spec_width 1).
        self.spec_k = int(cfg.spec_k)
        self.spec_width = self.spec_k + 1
        if drafter is not None and not self.spec_k:
            raise ValueError(
                "a drafter requires spec_k >= 1 (speculation is off)")
        self.drafter = (drafter if drafter is not None
                        else make_drafter(cfg, model, params)
                        ) if self.spec_k else None
        # Live weight hot-swap state (serving/hotswap.py). The engine
        # serves exactly one params version at a time; a staged
        # candidate waits under the lock until the next iteration
        # boundary applies it (never mid-iteration — the compiled step
        # already holds its params argument). The abstract tree pinned
        # here at construction is the validation oracle every candidate
        # must match: same structure, shapes, dtypes ⇒ the compiled
        # programs accept the new tree without a retrace.
        self.weights_epoch = int(weights_epoch)
        self._params_abstract = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(jnp.shape(a),
                                           jnp.result_type(a)), params)
        self._swap_lock = threading.Lock()
        self._pending_swap: tuple[Any, int] | None = None
        # Rollback insurance: the previously served tree survives one
        # swap (params are inference-sized; one extra copy is the cost
        # of re-arming the last known-good weights without touching
        # disk).
        self._prev_params: Any = None
        self._prev_epoch: int = -1
        self.last_swap_error: SwapError | None = None
        self.sample_cfg = SampleConfig(
            max_new_tokens=cfg.max_new_tokens,
            temperature=cfg.temperature, top_k=cfg.top_k, top_p=cfg.top_p,
            eos_id=cfg.eos_id, pad_id=cfg.pad_id)

        # int32 scalars the model sows into a ``counters`` collection each
        # step (an expert layer's routed rows): one more output of the
        # step programs, fetched in the iteration's sync, for its span.
        self._step_counters = tuple(model.step_counters)
        self._mutable = ["cache"] + (["counters"] if self._step_counters
                                     else [])
        s = cfg.max_batch
        ps = int(cfg.kv_page_size)
        self.page_size = ps
        self.pages_per_slot = pages_for(self.budget, ps)
        self.pool_pages = (int(cfg.kv_pages) if cfg.kv_pages is not None
                           else s * self.pages_per_slot)
        self.pool = PagePool(self.pool_pages, ps)
        # +1 physical page: the device pool keeps page 0 as the null
        # page (masked writes, unallocated table entries); the
        # allocator serves ids 1..pool_pages.
        self.model = model.clone(cache_len=self.budget,
                                 kv_page_size=ps,
                                 kv_pages=self.pool_pages + 1,
                                 kv_dtype=cfg.kv_dtype)
        # A chunk wider than the longest admissible prompt is pure
        # padding compute.
        self.prefill_chunk = min(int(cfg.prefill_chunk),
                                 max(self.budget - 1, 1))
        # Gather width of one slot's page-table view; verify-window
        # padding rows clamp their positions under this so the
        # per-row overflow poison never fires on a masked lane.
        self._l_all = self.pages_per_slot * ps

        # Radix-tree prefix cache (serving/prefix_cache.py): finished
        # sequences' written page chains stay indexed; a seat whose
        # prompt starts with a resident page-aligned chain aliases
        # those pages, commits only the non-resident tail, and prefills
        # only that tail. _kv_epoch stamps which weights wrote a seat's
        # pages — every hot-swap barrier bumps it and flushes the trie,
        # so old-weight KV can never seed a new-epoch request.
        self.prefix_cache = (PrefixCache(self.page_size,
                                         max_pages=cfg.prefix_cache_pages)
                             if cfg.prefix_cache else None)
        self._kv_epoch = 0
        self.queue = RequestQueue(
            self.budget, default_max_new_tokens=cfg.max_new_tokens,
            max_depth=cfg.max_queue_depth,
            ttft_deadline_ms=cfg.ttft_deadline_ms,
            deadline_ms=cfg.deadline_ms, trace=trace,
            page_size=self.page_size,
            pool_pages=self.pool_pages,
            num_tiers=cfg.num_tiers, tenant_quota=cfg.tenant_quota,
            tenant_weights=cfg.tenant_weights)
        self.scheduler = SlotScheduler(
            s, reserved_slots=cfg.tier_reserved_slots,
            preempt=cfg.preempt)
        self._drained = False
        # Overload latch for /healthz: True while the last admission
        # pass left work queued that could not seat (head-of-line
        # blocked on slots/pages even after any preemption).
        self._overloaded = False
        # Crash-durable serving (serving/journal.py): the write-ahead
        # request journal. Admissions persist synchronously on the
        # producer thread; token/preempt/finish records are enqueued at
        # the iteration tail and persisted by the journal's writer
        # thread — the decode loop never touches the filesystem (pinned
        # by the graftlint hot-path rule). Callers with a journal MUST
        # run recover() before serving: it replays the log, re-delivers
        # finished-but-unacked results exactly once, and re-seats
        # unfinished requests through the preemption resume path.
        self.journal: RequestJournal | None = None
        if cfg.journal_dir:
            self.journal = RequestJournal(
                cfg.journal_dir, fsync=cfg.journal_fsync,
                segment_bytes=cfg.journal_segment_bytes,
                # The RNG/sampling fingerprint: replaying this journal
                # into an engine where any of these differ would not
                # reproduce the journaled token streams — recovery
                # refuses with a typed error instead of silently
                # diverging. (Paging/speculation/batch knobs are
                # deliberately absent: outputs are bitwise independent
                # of them by the lane-independence invariant.)
                fingerprint={
                    "seed": cfg.seed, "temperature": cfg.temperature,
                    "top_k": cfg.top_k, "top_p": cfg.top_p,
                    "eos_id": cfg.eos_id, "pad_id": cfg.pad_id,
                    # Quantization identity: quantized and fp32 engines
                    # emit DIFFERENT (both-deterministic) token streams,
                    # and so do different KV storage dtypes — replaying
                    # one into the other would recompute divergent
                    # "recovered" tokens. Part of the fingerprint for
                    # the same reason seed is.
                    "quantize_weights": bool(cfg.quantize_weights),
                    "kv_dtype": cfg.kv_dtype,
                    # Weights identity: recovery into an engine serving
                    # different weights than the journal's tail would
                    # recompute "lost" tokens under the wrong model —
                    # every hot-swap barrier journals the new epoch
                    # (update_fingerprint below), and recover()
                    # validates against the LAST journaled value.
                    "weights_epoch": int(weights_epoch)},
                trace=trace)
        self._recovering = False
        self.recovery_report: dict[str, Any] | None = None
        self.telemetry = ServeTelemetry(cfg.ring_size,
                                        num_tiers=cfg.num_tiers)
        # Serving control room (serving/timeseries.py + serving/
        # alerts.py): the telemetry time-series ring samples host-side
        # counters/gauges every cfg.sample_every ITERATIONS (iteration
        # cadence, never wall time — deterministic under --virtual-dt),
        # the SLO rule engine evaluates burn-rate alerts at the same
        # boundary, and a firing rule enqueues ONE incident bundle for
        # the background writer thread (the journal writer discipline:
        # the decode loop never opens a file). A bad --slo-rules spec
        # fails HERE, before the engine serves anything.
        self.timeseries = TelemetryRing(cfg.timeseries_capacity,
                                        cfg.sample_every)
        self.alerts = AlertEngine(
            parse_slo_rules(cfg.slo_rules) if cfg.slo_rules else [])
        self.incidents: IncidentWriter | None = (
            IncidentWriter(cfg.incident_dir)
            if cfg.incident_dir else None)
        self._base_rng = jax.random.PRNGKey(cfg.seed)
        self._iteration = 0
        # Network front door (serving/frontend.py): an optional token
        # listener rides the per-iteration landing — _finish_iteration
        # publishes each active sequence's newly landed tokens (host
        # ints, past a per-uid cursor) and every completion, exactly
        # like the journal sweep it mirrors. One dynamic callable, set
        # before serving; None costs nothing.
        self._token_listener = None
        self._stream_cursor: dict[int, int] = {}
        # Client-disconnect cancellation: handler threads MARK a uid
        # here (under the lock — that is their whole write); the engine
        # loop consumes the set at its next step boundary and performs
        # the actual eviction, so slot/page/queue state keeps its
        # single-mutator discipline.
        self._cancel_lock = threading.Lock()
        self._cancel_uids: set[int] = set()

        # Donation keeps one cache resident instead of two per decode
        # step — and lets every layer write its new
        # rows into the donated pool in place (the pool is held row-major,
        # [rows, H·hd], so no relayout stands between the buffer and the
        # scatter; ring_attention._paged_decode_attend). The CPU backend
        # can't donate (it would only warn noisily).
        donate = jax.default_backend() != "cpu"
        # The iteration runs one device step ahead of the host
        # (_iterate_paged): the step launched and not yet fetched, the
        # latch by which an admission pass asks for a preemption with
        # nothing in flight, and what Engine.stats()["run_ahead_share"]
        # counts.
        self._in_flight: _DeviceStep | None = None
        self._preempt_due = False
        self._iters_working = 0
        self._iters_ahead = 0
        # Device state: the page pool (batch-free) and, between two
        # steps, the last step's own outputs: a slot's incoming
        # token is the previous step's `nxt` where the host has not
        # fetched it yet (_incoming). Slot routing (page tables,
        # write heads, RNGs, and the tokens the host has seen) is
        # host-side numpy, shipped as one packed step input
        # (_StepLayout) — so page
        # allocation and slot membership never touch compiled code,
        # and how much of each slot's table is live is data the
        # decode lane's attention kernel reads, not a shape.
        with trace_lib.span("setup.cache_alloc"):
            self._cache = init_decode_cache(self.model, params,
                                            batch_size=1)
        self._tables = np.zeros((s, self.pages_per_slot), np.int32)
        self._slot_rng = np.zeros(
            (s,) + self._base_rng.shape,
            np.asarray(self._base_rng).dtype)
        self._slot_pages: list[list[int]] = [[] for _ in range(s)]
        self._slot_commit_left = [0] * s
        # Prefix-cache routing (serving/prefix_cache.py): how many
        # LEADING entries of each slot's page list are ALIASED trie
        # pages (the sequence holds a reference, never writes them),
        # and the seated sequence itself — the engine needs its
        # written token stream and KV epoch at page-release time to
        # decide what enters the trie.
        self._slot_shared = [0] * s
        self._slot_seq: list[ActiveSequence | None] = [None] * s
        self._layout = _StepLayout(
            slots=s, width=self.spec_width,
            key_words=self._slot_rng.shape[1], pages=self.pages_per_slot,
            chunk=self.prefill_chunk)
        with trace_lib.span("setup.program_build") as build_span:
            # Which attention formulation each lane's shapes select:
            # the model says (it decides by the same call when the
            # programs trace).
            self.lane_formulation = {
                lane: self.model.paged_lane(t_in, self.page_size,
                                            cfg.kv_dtype)
                for lane, t_in in (("decode", self.spec_k + 1),
                                   ("chunk", self.prefill_chunk))}
            build_span.attrs.update(
                decode_lane=self.lane_formulation["decode"],
                chunk_lane=self.lane_formulation["chunk"])
            self._fused = jax.jit(
                self._fused_impl,
                donate_argnums=(1,) if donate else ())
            self._decode = jax.jit(
                self._decode_only_impl,
                donate_argnums=(1,) if donate else ())
            # What a launch with no step before it reads in the
            # previous outputs' place (every source is the host).
            self._no_tokens = (
                jnp.zeros((s, self.spec_width), jnp.int32),
                jnp.zeros((self.prefill_chunk,), jnp.int32))

        # Quantization gauges ride the telemetry from birth:
        # kv_bytes_per_token is measured off the REAL device cache tree
        # (so the int8 scale-plane overhead is counted, not assumed)
        # and the weight gauges carry the construction-time quantize
        # cost/footprint. reset_stats() re-seeds all three — they are
        # facts of the engine build, not of a measurement window.
        self.telemetry.on_weight_quant(self._weight_quant_s,
                                       self._quantized_params_bytes)
        self.telemetry.set_kv_bytes_per_token(self._kv_bytes_per_token())

    def _kv_bytes_per_token(self) -> float:
        """Device-cache bytes per storable KV token position, measured
        from the actual cache pytree and divided by the physical pool
        rows (so int8 pages + their fp32 scale planes both count)."""
        total = sum(int(leaf.nbytes)
                    for leaf in jax.tree_util.tree_leaves(self._cache))
        return total / ((self.pool_pages + 1) * self.page_size)

    # -- compiled pieces ----------------------------------------------------
    def _incoming(self, tok, src, prev_nxt, prev_sampled):
        """Row 0 of every decode lane: the host's value, or — where the
        host had not seen the token when it assembled this step — the
        step before's own output, picked by ``src`` (``_SRC_*``). The
        arrays are the ones the host fetches, so the two cannot differ."""
        row = jnp.clip(src - _SRC_CHUNK, 0, prev_sampled.shape[0] - 1)
        tok0 = jnp.where(src == _SRC_HOST, tok[:, 0],
                         jnp.where(src == _SRC_NXT, prev_nxt[:, 0],
                                   prev_sampled[row]))
        return tok.at[:, 0].set(tok0)

    def _decode_step(self, params, cache, tok, pos, valid, rngs, tables,
                     src, prev_nxt, prev_sampled):
        """One verify window for every slot through the paged pool.

        ``tok``/``pos``/``valid`` are [B, W] host state (W = spec_k + 1;
        W = 1 is the plain decode step), ``rngs`` [B], ``tables``
        [B, pages_per_slot]. Row 0 of each lane is the slot's incoming
        token — ``tok``'s, or the previous step's ``prev_nxt`` [B, W] /
        ``prev_sampled`` [chunk] where ``src`` [B] says so
        (:meth:`_incoming`); rows 1..W-1 are its drafter's proposals.
        Invalid rows
        (inactive slots, budget-clamped or short proposals) still
        compute (static shapes) but write the null page and sample pad —
        a freed slot's pool pages stay bitwise intact until the
        allocator reuses them. Each valid row's arithmetic matches the
        sequential ``Generator``'s one-token step exactly: the window
        extends the same per-row-independent dimension chunked prefill
        already extends (pinned bitwise), and the per-position sample
        uses the sequential ``fold_in(rng, position)`` stream — so every
        emitted token IS the sequential stream's token, drafts only
        decide how many of them this dispatch computes.

        Accept length is computed HERE, static-shape: the first
        mismatching draft position via argmax over a [W] mismatch mask
        with a sentinel column (all-match accepts spec_k). Invalid rows
        count as mismatches, so accept never crosses the valid width.
        Returns (cache, targets [B, W], accept [B]).
        """
        tok = self._incoming(tok, src, prev_nxt, prev_sampled)
        pages = PagedKV(table=tables, positions=pos, valid=valid)
        logits, vars_out = self.model.apply(
            {"params": params, "cache": cache}, tok,
            positions=pos, train=False, decode=True,
            mutable=self._mutable, pages=pages)

        def lane(rng_s, pos_row, rows):
            def one(pos_s, row):
                return sample_token(jax.random.fold_in(rng_s, pos_s),
                                    row[None], self.sample_cfg)[0]

            return jax.vmap(one)(pos_row, rows)

        t = jax.vmap(lane)(rngs, pos, logits)
        t = jnp.where(valid, t, jnp.int32(self.sample_cfg.pad_id))
        return (vars_out["cache"], t, self._accept_len(tok, t, valid),
                self._counted(vars_out))

    def _counted(self, vars_out):
        """The model's step counters as one int32 vector, each summed
        over the layers that sowed it; None for a model that has none."""
        if not self._step_counters:
            return None
        flat = flax.traverse_util.flatten_dict(
            flax.core.unfreeze(vars_out.get("counters", {})))
        return jnp.stack([
            sum((v for k, v in flat.items() if k[-1] == name),
                start=jnp.zeros((), jnp.int32))
            for name in self._step_counters]).astype(jnp.int32)

    def _accept_len(self, tok, t, valid):
        """[B] accepted-draft counts from a verify window (see
        :meth:`_decode_step`); pure ops, no control flow on traced
        values — the mask-based formulation the static-shape discipline
        requires."""
        mismatch = (tok[:, 1:] != t[:, :-1]) | ~valid[:, 1:]
        sentinel = jnp.ones((tok.shape[0], 1), bool)
        return jnp.argmax(jnp.concatenate([mismatch, sentinel], axis=1),
                          axis=1).astype(jnp.int32)

    def _chunk_step(self, params, cache, toks, pos, valid, table, rng):
        """One prefill chunk ``[1, C]`` for the oldest prefilling slot.

        Writes the chunk's K/V through the slot's page table (padding
        rows hit the null page) and samples a candidate token per row
        with ``fold_in(request_rng, position)`` — the host keeps row
        ``true_len-1-start`` as the request's first token when this
        chunk is final, making its RNG and logits row identical to the
        full-prompt prefill's.
        """
        pages = PagedKV(table=table, positions=pos[None],
                        valid=valid[None])
        logits, vars_out = self.model.apply(
            {"params": params, "cache": cache}, toks[None],
            positions=pos[None], train=False, decode=True,
            mutable=self._mutable, pages=pages)

        def row(pos_s, lg):
            return sample_token(jax.random.fold_in(rng, pos_s),
                                lg[None], self.sample_cfg)[0]

        sampled = jax.vmap(row)(pos, logits[0])
        return vars_out["cache"], sampled, self._counted(vars_out)

    def _fused_impl(self, params, cache, packed, prev_nxt, prev_sampled):
        """The fused iteration: one prefill chunk piggybacks onto the
        decode batch's verify window inside one compiled program
        (Sarathi-Serve), so an admission costs decode ZERO extra
        dispatches and never blocks it. The two sub-applies touch
        disjoint pages (the chunk's slot is not decoding), so their
        order is arithmetic-free. ``packed`` is the host's whole hand-over
        (:class:`_StepLayout`); the last two arguments are the step
        before's own outputs (:meth:`_decode_step`)."""
        with jax.named_scope("serve.fused"):
            lane, chunk = self._layout.unpack(packed)
            cache, c_sampled, c_counted = self._chunk_step(
                params, cache, *chunk)
            cache, nxt, accept, counted = self._decode_step(
                params, cache, *lane, prev_nxt, prev_sampled)
        out = (cache, nxt, accept, c_sampled)
        # a model with step counters: the chunk's rows beside the decode's
        return out if counted is None else out + (counted + c_counted,)

    def _decode_only_impl(self, params, cache, packed, prev_nxt,
                          prev_sampled):
        """Iterations with no prefill pending skip the chunk lane's
        compute entirely (the second compiled program)."""
        with jax.named_scope("serve.decode"):
            lane, _ = self._layout.unpack(packed)
            *out, counted = self._decode_step(
                params, cache, *lane, prev_nxt, prev_sampled)
        return tuple(out) if counted is None else (*out, counted)

    # -- host-side lifecycle -------------------------------------------------
    def submit(self, prompt, max_new_tokens: int | None = None,
               arrival_t: float | None = None, priority: int = 0,
               tenant: str = "default",
               deadline_ms: float | None = None,
               trace_id: str | None = None) -> Request:
        """Enqueue a request (thread-safe). ``priority`` is its SLO tier
        (0 = highest, < ``cfg.num_tiers``), ``tenant`` its fairness
        principal, ``deadline_ms`` an optional per-request total
        deadline overriding the configured default (the front door's
        deadline field). Raises :class:`~distributed_training_tpu.
        inference.sampler.CacheBudgetError` when it can never fit a
        slot's page table. With a
        journal, the admission record is durable before this returns —
        a request the journal never saw was never accepted.
        ``trace_id`` is the fleet-tracing correlation id the front door
        propagates (None → the queue self-mints ``uid-<uid>``); it rides
        every trace span the request emits."""
        req = self.queue.submit(prompt, max_new_tokens=max_new_tokens,
                                arrival_t=arrival_t, priority=priority,
                                tenant=tenant, deadline_ms=deadline_ms,
                                trace_id=trace_id)
        if self.journal is not None:
            try:
                self.journal.log_admit(req)
            except BaseException:
                # Acceptance is journal-backed: if the durable record
                # failed, withdraw the queued request before the caller
                # sees the error — otherwise it would decode anyway and
                # the caller's retry would duplicate it.
                self.queue.withdraw(req)
                raise
            # Ledger: the synchronous admission write is the request's
            # first lifetime span (arrival → durable-admit return).
            # Producer-thread HANDOFF only — the request became
            # seatable at enqueue, so the engine thread may already own
            # the ledger; note_admit_done records the timestamp and the
            # engine materializes the interval at its next stamp.
            if req.ledger is not None:
                req.ledger.note_admit_done(time.perf_counter())
        return req

    @property
    def idle(self) -> bool:
        """Nothing queued, seated, or launched and not yet delivered."""
        return (len(self.queue) == 0 and self.scheduler.num_active == 0
                and not self.queue.has_shed_pending
                and self._in_flight is None)

    def _req_pages(self, req: Request) -> int:
        """Worst-case page commitment: the request's whole lifetime
        (prompt + completion budget), page-rounded. The last emitted
        token is never written back, so this strictly covers every
        write the sequence can issue."""
        return pages_for(req.prompt.size + req.max_new_tokens,
                         self.page_size)

    def _ensure_pages(self, slot: int, tokens: int) -> None:
        """Grow ``slot``'s page table to cover ``tokens`` cache
        positions, drawing on-demand from the slot's commitment."""
        need = pages_for(tokens, self.page_size)
        have = len(self._slot_pages[slot])
        if need > have:
            new = self.pool.alloc(need - have)
            for i, p in enumerate(new):
                self._tables[slot, have + i] = p
            self._slot_pages[slot].extend(new)
            self._slot_commit_left[slot] -= len(new)

    @staticmethod
    def _written_tokens(seq: ActiveSequence) -> np.ndarray:
        """The token values of every cache position ``seq`` actually
        holds K/V for: ``prefill_pos`` positions while prefilling,
        prompt + emitted-minus-last once decoding (the last emitted
        token is never written back). This is the trie-insertion key
        stream — K/V at position ``i`` is a pure function of tokens
        ``0..i``, so a future request matching these tokens may alias
        these pages bitwise-safely."""
        if seq.prefilling:
            # graftlint: disable=hot-path-transfer -- prefill_tokens is host numpy by contract (the prompt / resume prefix); no device value involved
            return np.asarray(seq.prefill_tokens[:seq.prefill_pos],
                              np.int32)
        # graftlint: disable=hot-path-transfer -- emitted tokens are host ints by contract (note_token casts at landing); no device value involved
        full = np.concatenate([seq.request.prompt,
                               np.asarray(seq.tokens, np.int32)])
        return full[:seq.request.prompt.size + len(seq.tokens) - 1]

    @staticmethod
    def _hit_cap(entry) -> int:
        """Max cache positions a prefix hit may cover for ``entry``. A
        fresh request keeps at least ONE prompt position to prefill —
        the first token samples from the last prompt position's logits,
        which must be computed, not remembered. A resumption that
        already emitted tokens may be covered entirely: its incoming
        token is known, so a full hit re-seats straight into decode."""
        if isinstance(entry, ActiveSequence):
            n = entry.prefill_tokens.size
            return n if entry.tokens else n - 1
        return entry.prompt.size - 1

    def _free_slot_pages(self, slot: int) -> None:
        """Release a slot's pages (finish, deadline eviction, or
        preemption). With the prefix cache on, the sequence's FULL
        written pages first enter the trie — private pages are adopted
        (the slot's reference becomes the trie's), aliased prefix pages
        just drop the slot's extra reference — so the next request
        sharing the prefix (a preempted victim's own re-seat included)
        hits instead of re-prefilling. Old-epoch pages (written before
        the last hot-swap barrier) are never indexed: stale-weight KV
        must not seed new-epoch requests."""
        pages = self._slot_pages[slot]
        seq = self._slot_seq[slot]
        adopted: set[int] = set()
        if (self.prefix_cache is not None and seq is not None and pages
                and seq.kv_epoch == self._kv_epoch):
            adopted, evicted = self.prefix_cache.insert_chain(
                self._written_tokens(seq), pages, self.pool)
            if adopted or evicted:
                self.telemetry.on_prefix_pages(inserted=len(adopted),
                                               evicted=evicted)
        release = [p for p in pages if p not in adopted]
        uncommit = max(self._slot_commit_left[slot], 0)
        flight = self._in_flight
        if flight is not None and flight.lanes.get(slot) is seq:
            # The step in flight was assembled with this sequence in it
            # (an EOS or a deadline the host could not foresee) and still
            # writes one row into its pages: they go back to the pool
            # when that step has landed (_iterate_paged). What entered
            # the trie above are full pages that row lies beyond.
            flight.held.append((release, uncommit))
        else:
            self.pool.free(release, uncommit=uncommit)
        self._slot_pages[slot] = []
        self._slot_shared[slot] = 0
        self._slot_commit_left[slot] = 0
        self._slot_seq[slot] = None
        self._tables[slot, :] = 0

    def check_balanced(self) -> None:
        """Leak audit at the drained steady state: every pool page free
        or — prefix cache on — held by exactly the trie with exactly one
        reference, nothing committed."""
        self.pool.check_balanced(
            cached=(self.prefix_cache.pages_held()
                    if self.prefix_cache is not None else None))

    # -- latency ledger (serving/ledger.py) ----------------------------------
    @staticmethod
    def _phase_cause(seq: ActiveSequence) -> str:
        """The cause an in-slot sequence's CURRENT span bills to: fresh
        prefill, recompute (re-prefilling a carried prefix after a
        preemption or crash recovery), or decode."""
        if seq.prefilling:
            return (CAUSE_RECOMPUTE
                    if seq.preempts or seq.resume_prefix is not None
                    else CAUSE_PREFILL)
        return CAUSE_DECODE

    @staticmethod
    def _finish_cause(fin: FinishedRequest) -> str:
        """The cause of a completed request's terminal span (its last
        stamp → the completion boundary). Queue-side evictions were
        waiting (first wait or a requeue), slot evictions were serving
        (mid-prefill for deadline evictions without a first token)."""
        led = fin.ledger
        if fin.slot is None:
            if led is not None and led.intervals and \
                    led.intervals[-1][0] not in (CAUSE_QUEUE_WAIT,
                                                 CAUSE_JOURNAL_ADMIT):
                return CAUSE_PREEMPT_REQUEUE
            return CAUSE_QUEUE_WAIT
        # A resumption evicted mid-RE-prefill (before or after its
        # first token) was last doing recompute work, not decode.
        if led is not None and led.intervals and \
                led.intervals[-1][0] == CAUSE_RECOMPUTE:
            return CAUSE_RECOMPUTE
        if fin.first_token_t is None:
            return CAUSE_PREFILL
        return CAUSE_DECODE

    # -- tier-aware admission ------------------------------------------------
    def _queue_evict_finish(self, entry, reason: str) -> FinishedRequest:
        """Complete an entry evicted FROM THE QUEUE (tier-aware shed or
        deadline expiry): a fresh request carries nothing; a requeued
        resumption keeps its emitted tokens and reports the
        preemption-attributed reason."""
        if isinstance(entry, ActiveSequence):
            return FinishedRequest.from_active(entry, reason, slot=None)
        return FinishedRequest.rejected_in_queue(entry, reason)

    def _expire_queue(self, finished: list, now: float) -> None:
        """Deadline sweep BEFORE admission: a queued entry already past
        its TTFT/total deadline must not consume a prefill — it
        completes with finish reason ``timeout`` (fresh) or
        ``preempted_timeout`` (a resumption whose clock ran down while
        it waited for a re-seat)."""
        for entry in self.queue.pop_expired(now):
            finished.append(self._queue_evict_finish(
                entry, FINISH_PREEMPT_TIMEOUT
                if isinstance(entry, ActiveSequence) else FINISH_TIMEOUT))

    def cancel(self, uid: int) -> None:
        """Mark ``uid`` for cancellation (thread-safe, non-blocking).

        The client-disconnect path: a handler thread that catches a
        broken pipe mid-SSE calls this instead of letting the engine
        decode to completion for a dead socket. The mark is the only
        cross-thread write; the engine loop consumes it at its next
        step boundary (:meth:`_cancel_pass`), evicts the entry wherever
        it lives (queue or slot), frees its pages through the ordinary
        finish sweep, and completes it with reason ``cancelled``.
        Unknown / already-finished uids are dropped silently — the
        race with a natural completion is benign."""
        with self._cancel_lock:
            self._cancel_uids.add(int(uid))

    def _cancel_pass(self, finished: list) -> None:
        """Consume pending cancellation marks (engine thread only,
        start-of-step). Sorted drain → deterministic completion order
        when several sockets die between two steps."""
        with self._cancel_lock:
            if not self._cancel_uids:
                return
            uids, self._cancel_uids = sorted(self._cancel_uids), set()
        for uid in uids:
            entry = self.queue.remove_uid(uid)
            if entry is not None:
                finished.append(
                    self._queue_evict_finish(entry, FINISH_CANCELLED))
                continue
            if self._in_flight is not None:
                # A seated request leaves only with nothing in flight
                # (_runs_in_order sees the mark and the next call lands
                # the step first); a mark that raced this pass waits.
                if any(q.request.uid == uid
                       for q in self.scheduler.active()):
                    with self._cancel_lock:
                        self._cancel_uids.add(uid)
                continue
            seq = self.scheduler.evict_uid(uid)
            if seq is not None:
                # Free the pages NOW (the preemption idiom, engine.py
                # on_preempt): this runs before admission, so the slot
                # may be re-seated this very step — deferring the free
                # to _finish_iteration would reclaim the new tenant's
                # pages. slot=None keeps the finish sweep from freeing
                # twice.
                self._free_slot_pages(seq.slot)
                finished.append(FinishedRequest.from_active(
                    seq, FINISH_CANCELLED, slot=None))

    def _admit_pass(self, finished: list) -> list[ActiveSequence]:
        """One tier-aware admission pass: complete pending tier-aware
        shed victims, then seat candidates (preempting lower tiers when
        a higher tier cannot otherwise seat). Returns the newly seated
        sequences; the engine prefills each (resumptions re-prefill
        their carried prefix and continue the same RNG stream).

        Paged resource gate: a candidate seats only when the pool can
        commit its worst case — and, for non-top tiers, only when that
        commitment leaves ``tier_reserved_pages`` of headroom (waived
        when the pool is completely idle, so a lone best-effort request
        on an empty engine cannot deadlock against its own reserve).
        The commitment itself happens in ``on_seat``, so a multi-seat
        pass sees its own earlier reservations.
        """
        for entry in self.queue.take_shed():
            finished.append(self._queue_evict_finish(entry, FINISH_SHED))

        def can_seat(entry) -> bool:
            req = (entry.request if isinstance(entry, ActiveSequence)
                   else entry)
            # Prefix-cache sizing probe (read-only): the candidate
            # commits only its NON-RESIDENT tail — a hit request admits
            # with fewer pages, which is itself an admission-latency
            # win under pool pressure.
            hit_pages: list[int] = []
            if self.prefix_cache is not None:
                toks = (entry.prefill_tokens
                        if isinstance(entry, ActiveSequence)
                        else entry.prompt)
                hit_pages = self.prefix_cache.probe(
                    toks, max_tokens=self._hit_cap(entry))
            n_pages = self._req_pages(req) - len(hit_pages)
            # Reserved-page headroom for non-top tiers; waived when the
            # pool serves nothing (no commitment, no active sequence —
            # trie-held pages are evictable, not "in use"), so a lone
            # best-effort request on an idle engine cannot deadlock
            # against its own reserve.
            headroom = (self.cfg.tier_reserved_pages
                        if req.priority > 0 else 0)
            if headroom and self.pool.committed == 0 \
                    and self.scheduler.num_active == 0:
                headroom = 0
            if (self.prefix_cache is not None
                    and self.pool.available < n_pages + headroom
                    # O(1) futility guard: even reclaiming EVERY trie
                    # page (the upper bound on what eviction can free)
                    # would not cover the commitment — draining the
                    # trie anyway would destroy restore chains and
                    # re-walk it every admission poll for zero seats
                    # gained. Leave it intact; preemption (or a
                    # finishing sequence) is what changes the answer.
                    and n_pages + headroom <= self.pool.available
                    + self.prefix_cache.num_pages):
                # LRU pressure eviction: unreferenced trie pages are
                # reclaimable capacity — oldest first, the candidate's
                # own matched chain pinned (evicting it would trade the
                # hit for the headroom).
                evicted = self.prefix_cache.evict_until(
                    self.pool, n_pages + headroom,
                    pinned=set(hit_pages))
                if evicted:
                    self.telemetry.on_prefix_pages(evicted=evicted)
            if not self.pool.can_commit(n_pages):
                return False
            if headroom and self.pool.available - n_pages < headroom:
                return False
            return True

        def on_seat(seq: ActiveSequence) -> None:
            slot = seq.slot
            # Claim the resident prefix (refcount per page) and alias
            # it into the slot's block table; commit only the tail.
            # can_seat just validated the tail commitment on this same
            # pass — the trie cannot shrink in between (matched pages
            # are pinned and referenced), only grow.
            hit_pages: list[int] = []
            if self.prefix_cache is not None:
                hit_pages = self.prefix_cache.claim(
                    seq.prefill_tokens, self.pool,
                    max_tokens=self._hit_cap(seq))
            worst = self._req_pages(seq.request)
            self.pool.commit(worst - len(hit_pages))
            self._slot_pages[slot] = list(hit_pages)
            self._slot_shared[slot] = len(hit_pages)
            self._slot_commit_left[slot] = worst - len(hit_pages)
            self._slot_seq[slot] = seq
            self._tables[slot, :] = 0
            for i, p in enumerate(hit_pages):
                self._tables[slot, i] = p
            hit = len(hit_pages) * self.page_size
            seq.kv_epoch = self._kv_epoch
            seq.prefix_hit_tokens = hit
            # The chunk lane starts PAST the resident prefix: reused
            # positions are never recomputed, which is the entire
            # prefill-compute/TTFT win — and bitwise-free, because the
            # aliased pages hold exactly the K/V a cold prefill of the
            # same tokens would write (pinned by test_prefix_cache.py).
            seq.prefill_pos = hit
            if hit:
                # Recompute debt covered by residency (a preempted
                # victim re-seating onto its own pages, or a recovered
                # request hitting an earlier recovery's chain): the
                # preempt-and-RESTORE satellite — each recompute
                # counter drops by what IT charged, to the divergent
                # tail actually re-prefilled. Recovery debt credits
                # first (it was billed first, at replay — and a
                # recovered-then-preempted request's preempt charge is
                # the younger one).
                covered = min(hit, seq.recompute_owed)
                seq.recompute_owed -= covered
                rec_credit = min(covered, seq.recovery_owed)
                seq.recovery_owed -= rec_credit
                self.telemetry.on_prefix_hit(
                    hit, restored_preempt=covered - rec_credit,
                    restored_recovery=rec_credit)
                if seq.request.ledger is not None:
                    seq.request.ledger.add_tokens(CAUSE_PREFIX_HIT, hit)
                if self.trace is not None:
                    self.trace.instant(
                        "prefix_cache.hit", track=f"slot {slot}",
                        uid=seq.request.uid, trace=seq.request.trace_id,
                        tokens=hit, pages=len(hit_pages))
            # graftlint: disable=hot-path-transfer -- admission-boundary key landing: slot routing is host-side numpy by design
            self._slot_rng[slot] = np.asarray(
                jax.random.fold_in(self._base_rng, seq.request.uid))

        def on_preempt(seq: ActiveSequence) -> None:
            if self.journal is not None:
                # Tokens synced first, then the preempt mark: the
                # requeued prefix is reconstructible from the journal
                # alone, and a deadline miss after a crash still
                # attributes as preempted_timeout. Enqueue-only — the
                # writer thread persists off the hot loop.
                self.journal.note_preempt(seq)
            # Recompute debt: cache positions the eviction frees and the
            # re-seat must prefill again (the whole preemption cost —
            # the tokens themselves are never lost). Branch on the
            # PREFILLING state, not on emitted tokens: a resumption
            # preempted again mid-RE-prefill has only written
            # prefill_pos positions this seat, not its full prefix.
            recompute = (seq.prefill_pos if seq.prefilling
                         else seq.request.prompt.size
                         + len(seq.tokens) - 1)
            # Ledger: close the in-slot span at the eviction instant
            # (the time from here to the re-seat bills to
            # 'preempt_requeue' when the scheduler seats it again).
            if seq.request.ledger is not None:
                seq.request.ledger.stamp(self._phase_cause(seq),
                                         time.perf_counter())
            # The freed positions become ledger recompute debt: the
            # next prefill chunks consume it before billing 'prefill',
            # keeping ledger_tokens_recompute == the engine's counter.
            seq.recompute_owed += recompute
            self._free_slot_pages(seq.slot)
            self.telemetry.on_preempted(recompute,
                                        seq.request.priority)
            if self.trace is not None:
                self.trace.instant(
                    "request.preempted", track=f"slot {seq.slot}",
                    uid=seq.request.uid, trace=seq.request.trace_id,
                    tier=seq.request.priority,
                    tokens_emitted=len(seq.tokens),
                    # graftlint: disable=hot-path-transfer -- host int for a JSON trace arg (prompt.size/prefill_pos arithmetic, no device value)
                    recompute_tokens=int(recompute))

        def preempt_helps(entry, victims) -> bool:
            # Futility bound: would evicting EVERY strictly-lower-tier
            # active ever let this candidate seat? The
            # preemptible pool must cover the candidate's worst-case
            # commitment minus its resident prefix, with the same
            # reserved-page headroom can_seat applies. Without this
            # bound a too-large candidate would evict best-effort work
            # one sequence at a time for zero admission gained.
            #
            # A victim's reclaimable footprint under the prefix cache:
            # its PRIVATE pages + unused commitment free (or become
            # trie-evictable after its insert) immediately. A SHARED
            # page reclaims iff, once EVERY victim aliasing it lets go,
            # no live holder remains except possibly the trie: count
            # the victims holding it, and it is freeable when the
            # residual holders are zero (frees outright) or exactly the
            # trie's one reference (becomes LRU-evictable — can_seat's
            # pressure eviction reclaims it on the re-poll). A residual
            # NON-trie holder is a surviving sequence (e.g. two
            # post-flush old-epoch sharers), and evicting the victim
            # would free nothing — the futility the bound exists to
            # catch. Never counted when the candidate's own hit chain
            # pins the page.
            req = (entry.request if isinstance(entry, ActiveSequence)
                   else entry)
            need = self._req_pages(req)
            pinned: set[int] = set()
            if self.prefix_cache is not None:
                toks = (entry.prefill_tokens
                        if isinstance(entry, ActiveSequence)
                        else entry.prompt)
                pinned = set(self.prefix_cache.probe(
                    toks, max_tokens=self._hit_cap(entry)))
                need -= len(pinned)
            freeable = 0
            shared_holders: dict[int, int] = {}
            for v in victims:
                slot = v.slot
                shared_n = self._slot_shared[slot]
                freeable += (len(self._slot_pages[slot]) - shared_n
                             + max(self._slot_commit_left[slot], 0))
                for pg in self._slot_pages[slot][:shared_n]:
                    shared_holders[pg] = shared_holders.get(pg, 0) + 1
            for pg, held_by_victims in shared_holders.items():
                if pg in pinned:
                    continue
                residual = self.pool.refcount(pg) - held_by_victims
                if residual == 0 or (
                        residual == 1 and self.prefix_cache is not None
                        and self.prefix_cache.holds(pg)):
                    freeable += 1
            headroom = (self.cfg.tier_reserved_pages
                        if req.priority > 0 else 0)
            if self.pool.available + freeable < need + headroom:
                return False
            if self._in_flight is not None:
                # The victim has a token in flight: admission stops
                # here, this call launches nothing and lands the step,
                # and the next pass — nothing in flight — preempts as
                # it always did.
                self._preempt_due = True
                return False
            return True

        def prefix_probe(entry) -> int:
            # Cache-aware seat ordering (read-only trie walk): among
            # equal-fairness tenant heads, the queue seats the one with
            # the larger resident prefix first — it commits fewer pages
            # and prefills only its tail. With the cache off the probe
            # is never passed, so candidate order is bitwise the old
            # (service, tenant, uid) key (pinned by test_frontend.py).
            toks = (entry.prefill_tokens
                    if isinstance(entry, ActiveSequence) else entry.prompt)
            return len(self.prefix_cache.probe(
                toks, max_tokens=self._hit_cap(entry))) * self.page_size

        self._preempt_due = False
        seated = self.scheduler.admit(
            self.queue, can_seat, on_seat=on_seat, on_preempt=on_preempt,
            preempt_helps=preempt_helps,
            prefix_probe=(prefix_probe if self.prefix_cache is not None
                          else None))
        # Anything still queued is head-of-line blocked on slots or
        # pages until the next boundary (preemption included) — the
        # /healthz "overloaded" signal.
        self._overloaded = len(self.queue) > 0
        return seated

    def _draft_window(self, decoding, unlanded=()):
        """Assemble the [max_batch, spec_width] verify-window inputs for
        one iteration (host-side numpy, like all slot routing).

        A slot in ``unlanded`` has one token more than the host has seen
        (the step in flight emits it): its write head counts that token
        and row 0 of its lane is left to the device (:meth:`_incoming`).

        Row 0 of a decoding slot's lane is its incoming token at write
        head ``p``; rows 1..useful are its drafter's proposals at
        ``p+1..p+useful``, where ``useful = min(spec_k, remaining
        completion budget - 1, proposal length)`` — the budget clamp
        keeps every VALID write inside the request's worst-case page
        commitment, so speculation
        never grows what admission promised. Padding rows are
        validity-masked; their positions additionally
        clamp under the page-table width so the per-row overflow poison
        cannot fire on a masked lane. Returns ``(tok, pos, valid,
        useful_by_slot, drafted)``.
        """
        s = self.cfg.max_batch
        w = self.spec_width
        d_tok = np.full((s, w), self.sample_cfg.pad_id, np.int32)
        d_pos = np.zeros((s, w), np.int32)
        d_valid = np.zeros((s, w), bool)
        useful_by_slot: dict[int, int] = {}
        drafted = 0
        for seq in decoding:
            lag = seq.slot in unlanded
            p = seq.request.prompt.size + len(seq.tokens) + lag - 1
            useful = 0
            if self.spec_k:
                cap = seq.request.max_new_tokens - len(seq.tokens) - 1
                useful = min(self.spec_k, max(cap, 0))
            if useful > 0:
                ctx = np.concatenate([
                    seq.request.prompt,
                    np.asarray(seq.tokens, np.int32)])
                # graftlint: disable=hot-path-transfer -- drafter proposals are host numpy by protocol; this normalizes third-party drafter output, no device value involved
                props = np.asarray(
                    self.drafter.propose(ctx, self.spec_k),
                    np.int32).reshape(-1)
                useful = min(useful, props.size)
                d_tok[seq.slot, 1:1 + useful] = props[:useful]
            if not lag:
                d_tok[seq.slot, 0] = seq.tokens[-1]
            d_pos[seq.slot] = np.minimum(p + np.arange(w), self._l_all - 1)
            d_valid[seq.slot, :useful + 1] = True
            useful_by_slot[seq.slot] = useful
            drafted += useful
        return d_tok, d_pos, d_valid, useful_by_slot, drafted

    def _apply_accepts(self, decoding, toks, accepts, useful_by_slot,
                       t: float) -> tuple[int, int]:
        """Land one verify window's results: each slot emits its
        verified prefix plus the bonus/correction token (``accept + 1``
        tokens, EOS-truncated — the sequential loop would have stopped
        there). The rejected suffix needs no device work to roll back:
        the host write head (derived from ``len(tokens)``) simply does
        not advance past the accepted prefix, and the next window's
        leading valid rows overwrite the stale K/V before any valid
        query can attend it. Returns ``(tokens emitted, drafts
        accepted)``; also draws the per-slot accept marks on the trace.
        """
        emitted = 0
        accepted = 0
        eos = self.sample_cfg.eos_id
        for seq in decoding:
            # graftlint: disable=hot-path-transfer -- accepts already landed host-side with the iteration sync; this indexes a numpy array
            a = int(accepts[seq.slot])
            emit = truncate_at_eos(toks[seq.slot, :a + 1], eos)
            for tk in emit:
                seq.note_token(tk, t)
            emitted += emit.size
            accepted += emit.size - 1
            # Ledger: this iteration's span bills to 'decode' (the
            # verify window IS the decode dispatch) and the landed
            # tokens/draft economics count per request.
            led = seq.request.ledger
            if led is not None:
                led.stamp(CAUSE_DECODE, t)
                led.add_tokens(CAUSE_DECODE, emit.size)
                if self.spec_k:
                    led.add_tokens(CAUSE_SPEC_DRAFT,
                                   useful_by_slot.get(seq.slot, 0))
                    led.add_tokens(CAUSE_SPEC_ACCEPT, emit.size - 1)
            if self.trace is not None and self.spec_k:
                self.trace.instant(
                    "spec.accept", track=f"slot {seq.slot}", t=t,
                    uid=seq.request.uid,
                    drafted=useful_by_slot.get(seq.slot, 0),
                    accepted=emit.size - 1)
        return emitted, accepted

    def _note_first_token(self, seq, first: int, t: float) -> None:
        """Shared first-token bookkeeping: the TTFT measurement point.

        Admission-latency breakdown: queueing (arrival → seat) vs
        prefill compute (seat → first token) — the same endpoints the
        trace spans carry, so the two views agree bitwise."""
        req = seq.request
        seq.note_token(first, t)
        self.telemetry.on_tokens(1, t)
        # Ledger: the prefill span closes AT the first token (the TTFT
        # boundary the conservation sub-invariant checks), and the
        # first token itself counts as an emitted 'decode' token. A
        # resumption that was preempted mid-prefill re-prefills under
        # 'recompute' instead.
        if req.ledger is not None:
            req.ledger.stamp(
                CAUSE_RECOMPUTE
                if seq.preempts or seq.resume_prefix is not None
                else CAUSE_PREFILL, t)
            req.ledger.add_tokens(CAUSE_DECODE, 1)
        self.telemetry.on_admitted((seq.seated_t - req.arrival_t) * 1e3,
                                   (t - seq.seated_t) * 1e3)
        # arrival→seated is queueing, seated→first token is prefill (the
        # chunk lane's wait included): the request's two spans, keyed by
        # its uid.
        track = f"slot {seq.slot}"
        trace_lib.record("serve.queued", req.arrival_t, seq.seated_t,
                         key=req.uid, session=self.trace, track=track,
                         trace=req.trace_id)
        trace_lib.record("serve.prefill", seq.seated_t, t, key=req.uid,
                         session=self.trace, track=track,
                         trace=req.trace_id,
                         # graftlint: disable=hot-path-transfer -- host int for a JSON trace arg (prompt.size, no device value)
                         prompt_len=int(req.prompt.size))
        if self.trace is not None:
            # The raw clock values ride along so the trace-derived TTFT
            # is (t_first_token - t_arrival)*1e3 — bitwise the same
            # arithmetic ServeTelemetry performs.
            self.trace.instant("first_token", track=track, t=t,
                               uid=req.uid, trace=req.trace_id,
                               t_arrival=req.arrival_t,
                               t_first_token=t)

    # -- live weight hot-swap (serving/hotswap.py drives this) ---------------
    def validate_swap(self, params: Any, *, stage: str = "validate",
                      epoch: int | None = None) -> None:
        """Raise :class:`SwapError` unless ``params`` is a tree the
        compiled programs can serve in place of the current weights:
        identical structure, leaf shapes, and dtypes (anything else
        would retrace — or worse, silently reinterpret — mid-flight).
        Runs off the hot path (staging thread / arm call).

        A quantizing engine (``quantize_weights=True``) accepts TWO
        abstract shapes: the quantized serving tree (what rollback
        re-arms — already int8+scales) and the fp32 restore tree (what
        the hot-swap watcher stages from checkpoints — :meth:`arm_swap`
        quantizes it). Anything else is the same hard mismatch as
        always."""
        candidate = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(jnp.shape(a),
                                           jnp.result_type(a)), params)
        if candidate == self._params_abstract:
            return
        if (self._fp32_params_abstract is not None
                and candidate == self._fp32_params_abstract):
            return
        want = jax.tree_util.tree_structure(self._params_abstract)
        got = jax.tree_util.tree_structure(candidate)
        detail = (f"tree structure {got} != serving {want}"
                  if got != want else
                  "leaf shapes/dtypes differ from the serving model")
        if self._fp32_params_abstract is not None:
            detail += (" (matches neither the quantized serving tree "
                       "nor the fp32 restore tree)")
        raise SwapError(
            f"swap candidate does not match the serving model's "
            f"parameter tree ({detail}); the engine keeps its "
            f"current weights (epoch {self.weights_epoch})",
            stage=stage, epoch=epoch)

    def arm_swap(self, params: Any, *, epoch: int) -> None:
        """Stage validated weights for the next iteration boundary
        (thread-safe; the hot-swap watcher calls this from its own
        thread). The live engine is untouched until :meth:`step` applies
        the swap; arming again before that replaces the earlier
        candidate (newest wins). Raises :class:`SwapError`
        (``stage="arm"``) on a tree/shape/dtype mismatch.

        On a quantizing engine an fp32 candidate (the hot-swap
        watcher's restored checkpoint) is quantized HERE — on the
        caller's thread, so the cost lands on the watcher exactly like
        restore/verify staging, never on the serving thread — and the
        wall time is billed to ``weight_quant_s``. An already-quantized
        candidate (rollback's re-arm of the previous tree) stages
        as-is."""
        if self._quantize_weights:
            from distributed_training_tpu.serving.quantize import (
                is_quantized,
                quantize_params,
                quantized_param_bytes,
            )

            if not is_quantized(params):
                # Validate the fp32 tree BEFORE paying for quantization
                # (a malformed candidate should die as cheaply and as
                # early as the unquantized path kills it).
                self.validate_swap(params, stage="arm", epoch=epoch)
                t0_q = time.perf_counter()
                params = quantize_params(params)
                dt_q = time.perf_counter() - t0_q
                self._weight_quant_s += dt_q
                self._quantized_params_bytes = quantized_param_bytes(
                    params)
                self.telemetry.on_weight_quant(
                    dt_q, self._quantized_params_bytes)
        self.validate_swap(params, stage="arm", epoch=epoch)
        with self._swap_lock:
            self._pending_swap = (params, int(epoch))
        if self.trace is not None:
            self.trace.instant("swap.armed", track="engine",
                               epoch=int(epoch))

    def rollback(self) -> int:
        """Re-arm the previously served weights (the last completed
        swap's predecessor) — the recovery lever when a deployed
        checkpoint turns out bad downstream of every mechanical check.
        Returns the re-armed epoch; raises :class:`SwapError`
        (``stage="rollback"``) when no swap has completed.

        The ``(_prev_params, _prev_epoch)`` pair is snapshotted under
        the swap lock: the barrier mutates both on the engine thread,
        and an unlocked read racing it could pair new params with a
        stale epoch label — or re-arm the very weights being backed
        out. (Snapshot-then-arm, not arm-under-lock: ``arm_swap`` takes
        the same non-reentrant lock.)"""
        with self._swap_lock:
            prev_params, prev_epoch = self._prev_params, self._prev_epoch
        if prev_params is None:
            raise SwapError(
                "nothing to roll back to: no weight swap has completed "
                "on this engine", stage="rollback")
        self.arm_swap(prev_params, epoch=prev_epoch)
        return prev_epoch

    def note_swap_rejected(self, err: SwapError) -> None:
        """Record a swap attempt that died in the pipeline (verify /
        stage / validate / arm). Telemetry + trace only — the engine is
        guaranteed untouched, still serving its current weights."""
        self.last_swap_error = err
        self.telemetry.on_swap_rejected()
        if self.trace is not None:
            self.trace.instant("swap.rejected", track="engine",
                               stage=err.stage,
                               epoch=-1 if err.epoch is None
                               else int(err.epoch))

    def _install_params(self, params: Any) -> None:
        """The barrier's only hot-path work: point the compiled programs
        at the staged tree. Same shapes/dtypes (validated at arm), so
        no retrace — the next dispatch just binds a different argument."""
        self.params = params

    def _apply_pending_swap(self) -> None:
        """Iteration-boundary swap barrier: apply a staged candidate, if
        any. In-flight requests keep their slots, KV pages, and RNG
        streams and continue on the new weights; the pause is billed to
        ``swap_blocked_s`` (and compensated out of the in-flight
        requests' TPOT), and the surrounding iteration delta is gap-
        excluded from the decode step-time percentiles — deployment cost
        is attributed explicitly, never smeared into serving SLAs."""
        t0 = time.perf_counter()
        # One lock section for handoff + install: the (_prev_params,
        # _prev_epoch) pair and weights_epoch must mutate atomically
        # with respect to rollback()'s snapshot on the watcher thread.
        with self._swap_lock:
            pending, self._pending_swap = self._pending_swap, None
            if pending is None:
                return
            params, epoch = pending
            self._prev_params = self.params
            self._prev_epoch = self.weights_epoch
            self._install_params(params)
            # graftlint: disable=hot-path-transfer -- epoch is a staged host int, not a device value
            self.weights_epoch = int(epoch)
        # KV-identity barrier (serving/prefix_cache.py): cached pages
        # hold K/V computed under the OLD weights — flush the trie
        # inside the same barrier so no new-epoch request can alias
        # them, and bump the epoch so in-flight old-epoch sequences
        # (which legitimately keep their pages mid-sequence) never
        # re-index them at finish. Pages still aliased by in-flight
        # sequences stay allocated under their remaining references.
        self._kv_epoch += 1
        if self.prefix_cache is not None:
            self.prefix_cache.flush(self.pool)
        if self.journal is not None:
            # The journal's weights-identity tail marker: recovery must
            # be able to see which epoch produced the records after
            # this barrier (enqueue-only; the writer thread persists).
            self.journal.update_fingerprint(
                weights_epoch=self.weights_epoch)
        if self.drafter is not None:
            # No stale-drafter window: a self-drafting (mirror) drafter
            # re-points its params snapshot at the freshly installed
            # tree inside the same barrier, so the very next draft
            # proposes from the weights the verifier now serves
            # (serving/speculative.py; pinned by tests). epoch is
            # already a host int (arm_swap stages it as one).
            self.drafter.on_weights_swap(params, epoch)
        t1 = time.perf_counter()
        dt = t1 - t0
        self.telemetry.recorder.mark_gap()
        self.telemetry.on_swap_applied(dt)
        for seq in self.scheduler.active():
            if seq.first_token_t is not None:
                seq.swap_pause_s += dt
            # Ledger: close the in-flight span at the barrier entry and
            # bill the barrier itself to 'swap_barrier' — deployment
            # cost attributed per request, never smeared into decode.
            if seq.request.ledger is not None:
                seq.request.ledger.stamp(self._phase_cause(seq), t0)
                seq.request.ledger.stamp(CAUSE_SWAP_BARRIER, t1)
        if self.trace is not None:
            self.trace.instant("swap.applied", track="engine",
                               # graftlint: disable=hot-path-transfer -- host int for a JSON trace arg
                               epoch=int(epoch), blocked_ms=dt * 1e3,
                               inflight=self.scheduler.num_active)

    def step(self) -> list[FinishedRequest]:
        """One engine iteration: swap barrier, admit(+chunk-prefill),
        decode, evict.

        Returns the requests that finished this iteration. Safe to call
        when idle (records an excluded gap and returns []). A call
        delivers the tokens of one device step and, where it
        may, has launched the next one first (:meth:`_iterate_paged`);
        the swap barrier waits for a call that enters with nothing in
        flight."""
        if self._in_flight is None:
            self._apply_pending_swap()
        it = self._iteration
        self._iteration += 1
        # The iteration and its phases, in order (docs/OBSERVABILITY.md
        # "Span-level tracing"): admit, assemble, device_step (dispatch,
        # token_wait), commit, finish. The phases take key, session and
        # track from the iteration's span.
        with trace_lib.span("serve.iteration", key=it, session=self.trace,
                            track="engine") as it_span:
            return self._iterate_paged(it, it_span)

    def _runs_in_order(self, deadlines: bool) -> bool:
        """True where the host is about to change what a step in flight
        would have been assembled from, so that none may be: a drafter
        reads the host's tokens, a swap barrier changes the weights, a
        cancelled or an expired seated request frees a slot with a token
        on its way (a preemption too: ``_preempt_due``, which the
        admission pass sets). A call that finds this true with a step in
        flight only lands it; with none it launches, fetches and commits
        one step like the engine before it ran ahead."""
        if self.spec_k:
            return True
        with self._swap_lock:
            if self._pending_swap is not None:
                return True
        with self._cancel_lock:
            marked = set(self._cancel_uids)
        if not marked and not deadlines:
            return False
        now = time.perf_counter()
        return any(q.request.uid in marked
                   or (deadlines and q.finish_reason(None, now) is not None)
                   for q in self.scheduler.active())

    def _plan_step(self, prev: _DeviceStep | None) -> _DeviceStep | None:
        """Assemble the device step that follows ``prev`` — a step whose
        tokens have not landed: the host then works from counts (a slot
        of ``prev``'s decode lane will hold one token more, its chunk's
        slot ``prev.c`` positions more) — or, with None, the state as
        committed. None when no lane would run."""
        s = self.cfg.max_batch
        allocated = self.pool.num_allocated
        src: dict[int, int] = {}        # slot -> where its token comes from
        decoding, prefilling, filled = [], [], {}
        for seq in self.scheduler.active():
            n, pos = len(seq.tokens), seq.prefill_pos
            if prev is not None and prev.lanes.get(seq.slot) is seq:
                if seq is prev.chunk_seq:
                    pos = prev.start + prev.c
                    if pos == seq.prefill_tokens.size and not n:
                        # the final chunk's last valid row is its first
                        # token (a resumption's is already the host's)
                        n, src[seq.slot] = 1, _SRC_CHUNK + prev.c - 1
                else:
                    n, src[seq.slot] = n + 1, _SRC_NXT
                if n >= seq.request.max_new_tokens:
                    # The token in flight is its last: it takes no part
                    # in this step, and its slot and pages are free when
                    # that token lands — its successor is seated one
                    # iteration later than with nothing in flight.
                    continue
            if pos < seq.prefill_tokens.size or not n:
                prefilling.append(seq)
                filled[seq.slot] = pos
            else:
                decoding.append(seq)
        # Oldest prefilling request first (seat order == arrival
        # order): one chunk per iteration keeps the fused step's
        # shape fixed and admission FIFO-fair.
        chunk_seq = min(prefilling, key=lambda q: q.request.uid,
                        default=None)
        if chunk_seq is None and not decoding:
            return None
        # Verify-window assembly (plain one-token decode when
        # spec_k=0): incoming token + drafts per decoding slot;
        # pages ensured only for the VALID width, so speculation
        # draws nothing beyond the admission commitment. A row
        # assembled for a request whose token in flight turns out to be
        # an EOS writes inside that request's own commitment (it was not
        # at its length limit) and is dropped when it lands.
        d_tok, d_pos, d_valid, useful_by_slot, drafted = \
            self._draft_window(decoding, src)
        d_src = np.zeros((s,), np.int32)
        rows_live = []
        for seq in decoding:
            d_src[seq.slot] = src.get(seq.slot, _SRC_HOST)
            # Write positions of this window = tokens already
            # cached (prompt + generated minus the uncached last)
            # through the last valid draft row.
            rows = (seq.request.prompt.size + len(seq.tokens)
                    + (seq.slot in src) + useful_by_slot[seq.slot])
            self._ensure_pages(seq.slot, rows)
            rows_live.append(rows)
        start = c = 0
        chunk = ()
        if chunk_seq is not None:
            # prefill_tokens == the prompt for a fresh seat; for
            # a resumption it carries prompt + emitted-minus-last,
            # so the re-prefill rebuilds exactly the cache prefix
            # the preemption freed (same positions, same fold_in
            # RNG).
            pre_toks = chunk_seq.prefill_tokens
            start = filled[chunk_seq.slot]
            c = min(self.prefill_chunk, pre_toks.size - start)
            self._ensure_pages(chunk_seq.slot, start + c)
            cw = self.prefill_chunk
            c_tok = np.full((cw,), self.sample_cfg.pad_id, np.int32)
            c_pos = np.zeros((cw,), np.int32)
            c_valid = np.zeros((cw,), bool)
            c_tok[:c] = pre_toks[start:start + c]
            c_pos[:c] = np.arange(start, start + c)
            c_valid[:c] = True
            chunk = (c_tok, c_pos, c_valid)
        # What this step's attention has to read: the pages the live
        # slots' positions cover (each decoding slot through its
        # window's last valid row, the chunk's slot through the chunk's
        # last row), beside the fixed budget the gather formulation
        # reads whatever is live; what the decoding slots' queries
        # read of their live rows (a model with a learned selection
        # reads fewer than all); and the rows whose index key such a
        # model's lane scores to make that selection (one layer's).
        pages_live = sum(pages_for(r, self.page_size) for r in rows_live)
        if chunk_seq is not None:
            pages_live += pages_for(start + c, self.page_size)
        lanes = {q.slot: q for q in decoding}
        if chunk_seq is not None:
            lanes[chunk_seq.slot] = chunk_seq
        return _DeviceStep(
            program="fused" if chunk_seq is not None else "decode",
            decoding=decoding, d_tok=d_tok, d_pos=d_pos, d_valid=d_valid,
            d_src=d_src, useful_by_slot=useful_by_slot, drafted=drafted,
            chunk_seq=chunk_seq, start=start, c=c, chunk=chunk,
            lanes=lanes,
            pages_held={slot: len(self._slot_pages[slot])
                        for slot in lanes},
            pages_drawn=self.pool.num_allocated - allocated,
            attrs=dict(
                kv_pages_live=pages_live,
                kv_pages_budget=s * self.pages_per_slot,
                kv_rows_live=sum(rows_live),
                kv_rows_selected=sum(map(self.model.attended_rows,
                                         rows_live)),
                index_rows_scored=sum(
                    self.model.index_rows_scored(r, self._l_all)
                    for r in rows_live)))

    def _launch(self, step: _DeviceStep, prev: _DeviceStep | None) -> int:
        """Hand ``step``'s inputs over as one packed buffer
        (:class:`_StepLayout`) and launch its program behind ``prev``'s
        on the device; nothing is fetched. ``prev``'s outputs go in as
        they came out: the device picks a slot's incoming token from
        them where the host could not (``d_src``). Returns the
        host-to-device transfers made."""
        prev_nxt, prev_sampled = self._no_tokens
        if prev is not None:
            prev_nxt = prev.nxt
            if prev.c_sampled is not None:
                prev_sampled = prev.c_sampled
        step.t0 = time.perf_counter()
        fused = step.chunk_seq is not None
        packed = self._layout.pack(
            step.d_tok, step.d_pos, step.d_valid, step.d_src,
            self._slot_rng, self._tables, step.chunk,
            step.chunk_seq.slot if fused else 0)
        # The numpy buffer goes to the jitted call as it is: the call's
        # own placement of an argument is the cheapest of the ways to hand
        # it over (PERF.md §5: 1.54 ms a launch against 1.62 through
        # jax.device_put and 1.71 through jnp.asarray) — and this one
        # transfer is the launch's contract, so it is allowed by name
        # where a caller forbids implicit ones (jax.transfer_guard).
        with jax.transfer_guard_host_to_device("allow"):
            if fused:
                (self._cache, step.nxt, step.acc, step.c_sampled,
                 *counted) = self._fused(self.params, self._cache, packed,
                                         prev_nxt, prev_sampled)
            else:
                self._cache, step.nxt, step.acc, *counted = self._decode(
                    self.params, self._cache, packed, prev_nxt,
                    prev_sampled)
        if counted:
            step.counted = counted[0]
        # What the host will fetch starts for the host the moment the
        # step ends, not when the host asks: by then the copy has landed.
        for out in (step.nxt, step.acc, step.c_sampled, step.counted):
            if out is not None:
                out.copy_to_host_async()
        return 1

    def _iterate_paged(self, it: int, it_span) -> list[FinishedRequest]:
        """One call's work, one device step ahead of the host where it
        may be: entered with step *k* in flight, assemble and launch
        *k + 1*, and only then fetch, commit and deliver *k*'s tokens —
        the chip starts *k + 1* the moment *k* ends, and this call's
        commit and finish and the next call's admit, assemble and
        dispatch run beside it. Entered with nothing in flight, launch
        *k* first (then *k + 1*, then fetch *k*): the in-order iteration
        is this one where :meth:`_runs_in_order` lets nothing stay in
        flight."""
        span = trace_lib.span
        eos = self.sample_cfg.eos_id
        deadlines = (self.cfg.ttft_deadline_ms is not None
                     or self.cfg.deadline_ms is not None)
        finished: list[FinishedRequest] = []
        step = self._in_flight
        # A step in flight and the world about to change: land it, and
        # act in the next call on a state with nothing on its way.
        in_order = self._runs_in_order(deadlines)
        land_only = step is not None and in_order
        with span("serve.admit"):
            if not land_only:
                if deadlines:
                    self._expire_queue(finished, time.perf_counter())
                self._cancel_pass(finished)

            had_work = not self.idle
            if had_work:
                self.telemetry.begin_work()
            # Tier-aware, page-aware admission (_admit_pass): candidates
            # seat in tier-strict tenant-fair order when the pool can
            # commit their worst case; a blocked higher tier preempts the
            # worst lower-tier active sequence instead of waiting behind
            # it (with a step in flight: at the next call, once it has
            # landed). Seating costs NO device work here; the prompt (or
            # a resumption's carried prefix) prefills chunk-by-chunk,
            # riding the decode iterations. A slot whose last token is
            # in flight is free at the next call's pass.
            if not land_only:
                self._admit_pass(finished)
            # Head-of-line blocking: anything still queued after the
            # admission pass is blocked on a slot OR on pool pages until
            # the next boundary — bill the rest of this iteration as
            # admission-blocked time.
            blocked_t0 = (time.perf_counter() if len(self.queue) > 0
                          else None)
            live = self.scheduler.num_active

        launches: list[_DeviceStep] = []
        prev, successor = step, None
        if step is not None or live:
            with span("serve.assemble") as asm_span:
                if step is None:
                    step = self._plan_step(None)
                    if step is not None:
                        launches.append(step)
                if (step is not None and not in_order
                        and not self._preempt_due):
                    successor = self._plan_step(step)
                    if successor is not None:
                        launches.append(successor)
                if self.spec_k and step is not None and step.decoding:
                    # Proposal assembly (host); its verification is the
                    # device step's (serve.verify, below).
                    trace_lib.record(
                        "serve.draft", asm_span.t0, time.perf_counter(),
                        tokens=step.drafted, slots=len(step.decoding))
        # `program` and the step's counters are those of the step whose
        # tokens this call delivers; `ahead` says whether its successor
        # was launched before they were fetched.
        it_span.attrs.update(
            live=live, queued=len(self.queue),
            program="idle" if step is None else step.program)
        if step is not None:
            ahead = int(successor is not None)
            self._iters_working += 1
            self._iters_ahead += ahead
            it_span.attrs.update(step.attrs, ahead=ahead)
            with span("serve.device_step",
                      program=step.program) as dev_span:
                if launches:
                    with span("serve.dispatch", uploads=0) as disp_span:
                        for q in launches:
                            disp_span.attrs["uploads"] += \
                                self._launch(q, prev)
                            prev = q
                with span("serve.token_wait"):
                    # graftlint: disable=hot-path-transfer -- the iteration's one wait for the device: this step's tokens must land (docs/SERVING.md); its successor is already queued behind it
                    toks = np.asarray(step.nxt)
                    # graftlint: disable=hot-path-transfer -- per-slot accept lengths ride the same fetch
                    accepts = np.asarray(step.acc)
                    if step.counted is not None:
                        # graftlint: disable=hot-path-transfer -- the model's step counters, a few int32 in the same fetch
                        counts = np.asarray(step.counted)
                        it_span.attrs.update(zip(self._step_counters,
                                                 map(int, counts)))
            self._in_flight = successor
            # The tokens' landing time: every ledger stamp, TTFT and
            # deadline below reads this one clock value.
            t = dev_span.t1
            with span("serve.commit"):
                self._commit_step(step, toks, accepts, t)
                if blocked_t0 is not None:
                    self.telemetry.on_admission_blocked(t - blocked_t0)
                finished.extend(self.scheduler.evict_finished(
                    eos, now=t if deadlines else None))

        with span("serve.finish"):
            return self._finish_iteration(it, had_work, finished)

    def _commit_step(self, step: _DeviceStep, toks, accepts,
                     t: float) -> None:
        """Land one device step on the host's state at landing time
        ``t``: tokens, chunk progress, first token, ledgers, KV
        accounting. A lane whose sequence has left its slot since the
        step was assembled (an EOS or a deadline in the step before it)
        computed a row nobody waits for: it is dropped, and the pages it
        wrote go back to the pool now that the step has landed."""
        for pages, uncommit in step.held:
            self.pool.free(pages, uncommit=uncommit)
        decoding = [q for q in step.decoding
                    if self._slot_seq[q.slot] is q]
        chunk_seq = step.chunk_seq
        if chunk_seq is not None \
                and self._slot_seq[chunk_seq.slot] is not chunk_seq:
            chunk_seq = None
        emitted, accepted = self._apply_accepts(
            decoding, toks, accepts, step.useful_by_slot, t)
        if self.spec_k:
            # Host-side accept/rewind bookkeeping cost, attributed
            # explicitly like admission_blocked_s/swap_blocked_s —
            # and billed to each decoding request's ledger as
            # 'spec_rollback' (the batch shares the wall window).
            t_roll = time.perf_counter()
            for seq in decoding:
                if seq.request.ledger is not None:
                    seq.request.ledger.stamp(CAUSE_SPEC_ROLLBACK, t_roll)
            self.telemetry.on_spec(
                drafted=step.drafted, accepted=accepted,
                rollback_s=t_roll - t)
            if decoding:
                # The batched target dispatch that verified the
                # drafts; the per-slot accept marks land in
                # _apply_accepts.
                trace_lib.record(
                    "serve.verify", step.t0, t,
                    drafted=step.drafted, accepted=accepted)
        self.telemetry.on_decode(lanes=len(decoding), tokens=emitted)
        self.telemetry.on_tokens(emitted, t)
        if chunk_seq is not None:
            start, c = step.start, step.c
            chunk_seq.prefill_pos = start + c
            # Ledger chunk boundary: this iteration's span (chunk-
            # lane wait included) and the cache positions the
            # chunk wrote. Positions the chunk REwrites (the
            # sequence's recompute debt from preemptions/crashes)
            # bill to 'recompute'; first-time writes bill to
            # 'prefill' — so the token split mirrors the engine's
            # recompute counters exactly. The wall span takes the
            # chunk's dominant cause.
            led = chunk_seq.request.ledger
            if led is not None:
                rec = min(c, chunk_seq.recompute_owed)
                chunk_seq.recompute_owed -= rec
                # Recovery-attribution share never exceeds the
                # remaining debt (prefix-hit credit bookkeeping).
                chunk_seq.recovery_owed = min(
                    chunk_seq.recovery_owed,
                    chunk_seq.recompute_owed)
                if rec:
                    led.add_tokens(CAUSE_RECOMPUTE, rec)
                if c - rec:
                    led.add_tokens(CAUSE_PREFILL, c - rec)
                led.stamp(CAUSE_RECOMPUTE if rec * 2 >= c
                          else CAUSE_PREFILL, t)
            trace_lib.record(
                "serve.prefill_chunk", step.t0, t,
                key=chunk_seq.request.uid,
                track=f"slot {chunk_seq.slot}",
                trace=chunk_seq.request.trace_id,
                # graftlint: disable=hot-path-transfer -- host ints for JSON trace args
                start=int(start), tokens=int(c))
            if (chunk_seq.prefill_pos
                    == chunk_seq.prefill_tokens.size):
                if chunk_seq.tokens:
                    # Resumed mid-decode: the final chunk's sample
                    # recomputes the last emitted token bitwise
                    # (same logits row, same fold_in position) —
                    # it was already emitted before the
                    # preemption, so nothing lands; the slot just
                    # resumes decoding with it as the incoming
                    # token.
                    pass
                else:
                    # Final chunk: its last valid row is the
                    # request's first token (same RNG fold and
                    # logits row as a full-prompt prefill).
                    # graftlint: disable=hot-path-transfer -- the deliberate sync: the chunked-path TTFT measurement point
                    first = int(np.asarray(step.c_sampled)[c - 1])
                    self._note_first_token(chunk_seq, first, t)
        # KV utilization, host-side only: reserved = pages
        # held by occupied slots as this step was assembled,
        # written = live cache positions, both reconstructed without a
        # device read. The pool's allocation is counted as this step
        # needed it: pages the step ahead has drawn count with that one.
        counted = decoding + ([chunk_seq] if chunk_seq is not None
                              else [])
        reserved = sum(step.pages_held[q.slot]
                       for q in counted) * self.page_size
        written = sum(q.request.prompt.size + len(q.tokens) - 1
                      for q in decoding)
        if chunk_seq is not None:
            written += chunk_seq.prefill_pos
        self.telemetry.on_kv(
            reserved=reserved, written=written,
            active=len(counted), slots=self.cfg.max_batch,
            pages_allocated=self.pool.num_allocated - (
                self._in_flight.pages_drawn
                if self._in_flight is not None else 0),
            pages_total=self.pool.num_pages,
            pages_live=step.attrs["kv_pages_live"],
            pages_budget=step.attrs["kv_pages_budget"])
        if self.trace is not None:
            self.trace.counter("active_slots", len(counted))
            self.trace.counter("kv_written_tokens", written)
            self.trace.counter("kv_pages_allocated",
                               self.pool.num_allocated)

    def _finish_iteration(self, it: int, had_work: bool,
                          finished: list[FinishedRequest]
                          ) -> list[FinishedRequest]:
        """The iteration's tail: page reclamation, journal, telemetry,
        traces."""
        for fin in finished:
            if fin.slot is not None:
                self._free_slot_pages(fin.slot)
        if self.journal is not None:
            # Durability sweep, enqueue-only (the journal's writer
            # thread owns the disk): each active slot's newly emitted
            # tokens, and every completion's authoritative finish
            # record. Tokens landed but not yet durable at a kill -9
            # are recomputed bitwise by the recovery resume path.
            for seq in self.scheduler.active():
                self.journal.note_tokens(seq)
            for fin in finished:
                self.journal.note_finish(fin)
        if self._token_listener is not None:
            # Streaming sweep (serving/frontend.py): publish newly
            # landed tokens per active sequence past the per-uid
            # cursor, then every completion with its authoritative
            # token array — the SSE delivery point, same boundary the
            # journal sweep rides. Host ints only (note_token casts at
            # landing); the listener buffers, it never blocks.
            cb = self._token_listener
            for seq in self.scheduler.active():
                uid = seq.request.uid
                have = self._stream_cursor.get(uid, 0)
                if len(seq.tokens) > have:
                    cb(uid, list(seq.tokens[have:]), None)
                    self._stream_cursor[uid] = len(seq.tokens)
            for fin in finished:
                have = self._stream_cursor.pop(fin.uid, 0)
                # graftlint: disable=hot-path-transfer -- fin.tokens is the host int32 completion array by contract; no device value involved
                tail = [int(t) for t in fin.tokens[have:]]
                cb(fin.uid, tail, fin)
        if had_work:
            self.telemetry.on_iteration(
                it, queue_depth=len(self.queue),
                active=self.scheduler.num_active)
            if self.trace is not None:
                self.trace.counter("queue_depth", len(self.queue))
            if self.idle:  # drained: close the busy segment at last token
                self.telemetry.end_work()
        else:
            self.telemetry.on_idle()
        if finished:
            # Ledger terminal stamp: a request's lifetime ends at the
            # boundary that completed it; the tail span (last stamp →
            # here) bills to the phase it was in. on_finished then
            # audits conservation — so every completion is checked
            # in-engine, at the moment it happens.
            t_fin = time.perf_counter()
            for fin in finished:
                if fin.ledger is not None and not fin.ledger.closed:
                    # A cancelled request's tail bills to ``cancelled``
                    # regardless of phase: the time was spent serving a
                    # socket that was already gone.
                    cause = (CAUSE_CANCELLED
                             if fin.finish_reason == FINISH_CANCELLED
                             else self._finish_cause(fin))
                    fin.ledger.close(cause, t_fin)
        for fin in finished:
            self.telemetry.on_finished(fin)
            if self.trace is not None:
                self._trace_finish(fin)
        if self._iteration % self.cfg.flush_every == 0:
            self.telemetry.flush(it, len(self.queue),
                                 self.scheduler.num_active)
        if self._iteration % self.cfg.sample_every == 0:
            self._sample_telemetry(it)
        return finished

    def _sample_telemetry(self, it: int) -> None:
        """One control-room sample boundary (iteration cadence): append
        a flat sample of host-side counters/gauges to the time-series
        ring, evaluate the SLO rules over it, and enqueue one incident
        bundle per rule that fired. Everything here is host arithmetic
        plus one queue.put — no device read, no file I/O (the incident
        writer thread owns the disk; graftlint's hot-path rule pins
        this)."""
        tm = self.telemetry
        sample: dict[str, float] = {
            "iteration": it,
            # Deterministic schedule counters — what the bitwise alert
            # drill gates on.
            "tokens_emitted": tm.tokens_emitted,
            "requests_finished": tm.requests_finished,
            "requests_submitted": self.queue.submitted,
            "requests_shed": self.queue.shed,
            "requests_timed_out":
                tm.finish_reasons.get(FINISH_TIMEOUT, 0),
            "requests_preempted": tm.requests_preempted,
            "requests_preempt_timed_out":
                tm.finish_reasons.get(FINISH_PREEMPT_TIMEOUT, 0),
            "requests_recovered": tm.requests_recovered,
            "prefix_cache_hit_tokens": tm.prefix_cache_hit_tokens,
            "prefix_cache_evicted_pages": tm.prefix_cache_evicted_pages,
            "drafted_tokens": tm.tokens_drafted,
            "accepted_tokens": tm.tokens_accepted,
            "swaps_completed": tm.swaps_completed,
            "swaps_rejected": tm.swaps_rejected,
            "ledger_conservation_violations":
                tm.ledger_conservation_violations,
            "journal_records_written": (
                self.journal.records_written
                if self.journal is not None else 0),
            "journal_write_errors": (
                self.journal.write_errors
                if self.journal is not None else 0),
            # Gauges (instantaneous, still schedule-deterministic).
            "queue_depth": len(self.queue),
            "active_slots": self.scheduler.num_active,
            "pool_occupancy":
                self.pool.num_allocated / self.pool.num_pages,
            "prefix_cache_pages_held": (
                self.prefix_cache.num_pages
                if self.prefix_cache is not None else 0),
            "weights_epoch": self.weights_epoch,
        }
        for t in range(self.cfg.num_tiers):
            sample[f"tier{t}_requests_shed"] = self.queue.shed_by_tier[t]
            sample[f"tier{t}_requests_preempted"] = tm.tier_preempted[t]
        # Wall-derived columns: per-cause ledger window totals and the
        # TTFT/TPOT histogram cumulative bucket counts (windowed-
        # quantile source). Operators alert on these; the deterministic
        # drill does not.
        for c in LEDGER_CAUSES:
            sample[f"ledger_{c}_ms_total"] = tm.ledger_window_ms[c]
        for prefix, hist in (("ttft_ms", tm.ttft_hist),
                             ("tpot_ms", tm.tpot_hist)):
            for i, n in enumerate(hist.cumulative()):
                suffix = f"{i:02d}" if i < len(hist.bounds) else "inf"
                sample[f"{prefix}_le_{suffix}"] = n
        self.timeseries.record_sample(sample)
        for event in self.alerts.evaluate(self.timeseries, it):
            if self.incidents is not None:
                # One bundle per fire event: the alert, the full alert
                # log, the last slow-window of samples, and a flight
                # snapshot (taken WITHOUT the control-room sections —
                # the bundle already carries them at top level).
                self.incidents.capture(event["rule"], {
                    "format_version": 1,
                    "alert": event,
                    "alerts": self.alerts.to_dict(),
                    "timeseries": self.timeseries.to_dict(
                        last_n=TIMESERIES_DUMP_SAMPLES),
                    "flight": self.telemetry.snapshot(
                        reason=f"incident:{event['rule']}",
                        stats=self.stats()),
                })

    def _trace_finish(self, fin: FinishedRequest) -> None:
        """One request's terminal trace events: the decode span (first →
        last token on its slot track) and a finish mark carrying the
        reason. Queue-side evictions (timeout / shed / expired
        resumption) never hold a slot — they mark on the 'queue' track
        instead."""
        if fin.slot is None:
            self.trace.instant(f"request.{fin.finish_reason}",
                               track="queue", uid=fin.uid,
                               trace=fin.trace_id)
            return
        track = f"slot {fin.slot}"
        if (fin.first_token_t is not None and fin.last_token_t is not None
                and fin.tokens.size > 1):
            self.trace.complete("decode", fin.first_token_t,
                                fin.last_token_t, track=track,
                                uid=fin.uid, trace=fin.trace_id,
                                tokens=int(fin.tokens.size))
        self.trace.instant(f"finish:{fin.finish_reason}", track=track,
                           t=fin.last_token_t, uid=fin.uid,
                           trace=fin.trace_id,
                           tokens=int(fin.tokens.size))

    def run(self, max_iterations: int | None = None
            ) -> list[FinishedRequest]:
        """Drive :meth:`step` until every queued/active request finishes
        (or ``max_iterations``); returns completions in finish order."""
        out: list[FinishedRequest] = []
        n = 0
        while not self.idle:
            out.extend(self.step())
            n += 1
            if max_iterations is not None and n >= max_iterations:
                break
        return out

    def drain(self, max_iterations: int | None = None
              ) -> list[FinishedRequest]:
        """Graceful shutdown: close admission, then complete every
        request already accepted (queued and slotted).

        New submits raise the typed :class:`~distributed_training_tpu.
        resilience.errors.DrainingError` the moment this is called (from
        any thread); the returned completions include deadline evictions.
        Idempotent — calling again just drains whatever arrived before
        the close. The SIGTERM path in ``gpt/jax_tpu/serve.py`` and the
        end of ``tools/serve_bench.py`` both end through here, so no
        tail request is dropped from the SLA percentiles.
        """
        self.queue.close()
        out = self.run(max_iterations)
        self._drained = self.idle
        return out

    def close_admission(self) -> None:
        """Close admission WITHOUT driving the loop (idempotent) — the
        front-door drain path (serving/frontend.py): its serve-loop
        thread keeps stepping until idle, so a blocking :meth:`drain`
        from a handler thread would race it. Pair with
        :meth:`poll_drained` from the loop thread."""
        self.queue.close()

    def poll_drained(self) -> bool:
        """Latch (and report) drain completion: True once admission is
        closed and every accepted request has finished. The frontend's
        serve loop calls this each iteration while draining — the latch
        is what flips :attr:`phase` to ``drained``, the signal a
        rolling-deploy driver waits on before swapping weights."""
        if self.draining and self.idle:
            self._drained = True
        return self._drained

    def reopen(self) -> None:
        """Reopen admission after a completed drain (idempotent): the
        zero-downtime rolling-deploy step (serving/router.py) — drain,
        apply the staged swap at the empty-engine boundary, reopen.
        The engine is the same engine: uid sequence, telemetry, journal
        and fairness state all carry across."""
        self.queue.reopen()
        self._drained = False

    def recover(self) -> dict[str, Any]:
        """Replay the write-ahead journal BEFORE serving (crash-durable
        serving, docs/RESILIENCE.md): call once, right after
        construction and before the first submit/step.

        Three recovery classes, every one exactly-once and — for
        anything that decodes further — bitwise identical to the
        uninterrupted run:

        - **finished but unacked** results re-deliver from the journal
          verbatim (``report["redelivered"]``; the consumer acks them
          via ``journal.ack`` once durably taken, after which they stop
          being redelivered — the client cursor);
        - **unfinished** requests re-seat through the round-16
          preemption resume path in original arrival (uid) order: the
          re-prefill rebuilds prompt + emitted-minus-last and the
          continuation samples the same ``fold_in(rng, position)``
          stream, so tokens past the journal's last durable flush are
          *recomputed*, not lost (``tokens_recomputed_on_recovery`` is
          that debt, in cache positions). Downtime is billed to the
          request's ``swap_pause_s`` (recovery cost, not decode TPOT);
        - requests whose **deadline expired while the engine was dead**
          (or whose journaled stream already met EOS/budget) complete
          at replay — ``timeout``, or ``preempted_timeout`` when the
          journal shows a preemption — instead of resurrecting
          (``report["completed_at_replay"]``).

        Returns the report dict; also stored as ``recovery_report``.
        A journal-less engine returns an empty report. The /healthz
        phase reads ``recovering`` while this runs.

        The prefix cache COLD-STARTS across a restart: the trie is
        in-memory state whose pages died with the old process, and
        rebuilding it is a pure performance concern — reuse changes
        which pages a block table aliases, never a token, so
        redelivered results stay bitwise and resumed requests recompute
        bitwise either way. The trie repopulates naturally as recovered
        requests re-prefill and finish (later recoveries sharing a
        prefix with earlier ones hit it mid-replay).
        """
        report: dict[str, Any] = {
            "redelivered": [], "completed_at_replay": [],
            "resumed": 0, "notes": {}, "torn_bytes": 0}
        self.recovery_report = report
        if self.journal is None:
            return report
        if self._in_flight is not None:
            raise RuntimeError(
                "recover() replays the journal before the engine serves: "
                "a device step is in flight")
        self._recovering = True
        try:
            state = self.journal.recover()
            report["notes"] = dict(state.notes)
            report["torn_bytes"] = int(state.torn_bytes)
            self.queue.reserve_uids(state.max_uid + 1)
            now = time.perf_counter()
            recovered = 0
            recompute = 0
            for uid in sorted(state.requests):
                rr = state.requests[uid]
                recovered += 1
                prompt = np.asarray(rr.prompt, np.int32)
                if rr.finished:
                    report["redelivered"].append(FinishedRequest(
                        uid=uid, prompt=prompt,
                        tokens=np.asarray(rr.finish_tokens or [],
                                          np.int32),
                        finish_reason=rr.finish_reason,
                        ttft_ms=rr.ttft_ms, tpot_ms=rr.tpot_ms,
                        arrival_t=perf_of(rr.arrival_wall),
                        first_token_t=None, priority=rr.priority,
                        tenant=rr.tenant))
                    continue
                arrival_t = perf_of(rr.arrival_wall)
                req = Request(
                    uid=uid, prompt=prompt,
                    max_new_tokens=rr.max_new_tokens,
                    arrival_t=arrival_t,
                    ttft_deadline_t=(
                        arrival_t + rr.ttft_rel_s
                        if rr.ttft_rel_s is not None else None),
                    deadline_t=(
                        arrival_t + rr.deadline_rel_s
                        if rr.deadline_rel_s is not None else None),
                    priority=rr.priority, tenant=rr.tenant)
                seq = ActiveSequence.from_journal(
                    req, rr.tokens, preempts=rr.preempts,
                    first_token_t=(perf_of(rr.first_wall)
                                   if rr.first_wall is not None
                                   else None),
                    last_token_t=(perf_of(rr.last_wall)
                                  if rr.last_wall is not None
                                  else None))
                # Ledger (wall-anchored like the deadline clocks): the
                # dead process's span is 'pre_crash' up to its last
                # durable token (the per-cause detail died with it),
                # and everything from there to the end of this replay
                # — downtime included — bills to 'recovery'. Requests
                # with no durable token bill their whole pre-replay
                # span to 'recovery' (death time is unknowable).
                if req.ledger is not None:
                    if seq.last_token_t is not None:
                        req.ledger.stamp(CAUSE_PRE_CRASH,
                                         seq.last_token_t)
                    req.ledger.stamp(CAUSE_RECOVERY, now)
                reason = seq.finish_reason(self.sample_cfg.eos_id, now)
                if reason is not None:
                    # The journaled stream already completed (a crash
                    # between the last emit and the finish record's
                    # flush), or a deadline ran down during the
                    # downtime: complete at replay, never resurrect.
                    fin = FinishedRequest.from_active(seq, reason,
                                                      slot=None)
                    if fin.ledger is not None:
                        fin.ledger.close(CAUSE_RECOVERY, now)
                    self.journal.note_finish(fin)
                    self.telemetry.on_finished(fin)
                    report["completed_at_replay"].append(fin)
                    continue
                if seq.last_token_t is not None:
                    # Downtime billed like a swap barrier: recovery
                    # cost, attributed explicitly — not smeared into
                    # the request's decode TPOT.
                    seq.swap_pause_s += max(now - seq.last_token_t, 0.0)
                if seq.tokens:
                    recompute += prompt.size + len(seq.tokens) - 1
                # A resumption (tokens, or a journaled preemption whose
                # attribution must survive) restores as the sequence;
                # an untouched admission restores as the bare request.
                self.queue.restore(
                    seq if (seq.tokens or seq.preempts) else req)
                report["resumed"] += 1
            self.telemetry.on_recovered(recovered, recompute)
        finally:
            self._recovering = False
        return report

    @property
    def draining(self) -> bool:
        """True once admission has been closed (drain started)."""
        return self.queue.closed

    @property
    def phase(self) -> str:
        """Coarse lifecycle phase for the /healthz endpoint:
        serving ⇄ swapping ⇄ overloaded → draining → drained (idle =
        alive, nothing queued). ``swapping`` = a staged weight candidate
        is armed and waiting for the next iteration boundary to apply it
        — the window a rollout driver sees between arming and the
        barrier. ``overloaded`` = the last admission pass left work
        queued that could not seat even after preemption — selective
        degradation (tier-aware shed/preempt) is active, and a load
        balancer should prefer another replica for best-effort traffic.
        ``recovering`` = the write-ahead journal is being replayed
        before the port opens (crash restart) — a load balancer must
        not route new traffic yet.
        """
        if self._recovering:
            return "recovering"
        if self._drained:
            return "drained"
        if self.queue.closed:
            return "draining"
        with self._swap_lock:
            if self._pending_swap is not None:
                return "swapping"
        if self._overloaded and len(self.queue) > 0:
            return "overloaded"
        return "idle" if self.idle else "serving"

    def health(self) -> dict[str, Any]:
        """Hot-swap- and overload-aware extras for the exporter's
        /healthz payload: the deployed weights epoch, swap counters, and
        the graceful-degradation counters ride alongside ``phase`` so a
        rollout driver (or load balancer) can confirm a deploy — or see
        that best-effort traffic is being shed/preempted — from the
        health endpoint alone, without parsing /metrics."""
        return {
            "weights_epoch": int(self.weights_epoch),
            "swaps_completed": self.telemetry.swaps_completed,
            "swaps_rejected": self.telemetry.swaps_rejected,
            "requests_preempted": self.telemetry.requests_preempted,
            "requests_shed": self.queue.shed,
            "queue_depth": len(self.queue),
            # Crash-durable serving (serving/journal.py): the recovery
            # drill reads the replay evidence and the journal's write
            # counters straight off /healthz.
            "requests_recovered": self.telemetry.requests_recovered,
            "journal_records_written": (
                self.journal.records_written
                if self.journal is not None else 0),
            "journal_fsyncs": (self.journal.fsyncs
                               if self.journal is not None else 0),
        }

    def set_token_listener(self, listener) -> None:
        """Register (or clear, with None) the per-iteration token
        listener the network front door streams from
        (serving/frontend.py). ``listener(uid, new_tokens, fin)`` is
        called at every iteration tail on the ENGINE thread: once per
        active sequence that landed tokens this iteration
        (``fin=None``), and once per completion with the remaining tail
        and the :class:`FinishedRequest`. Set before serving; the
        listener must only buffer (hot-path discipline: the decode loop
        never blocks on a consumer)."""
        self._token_listener = listener
        self._stream_cursor.clear()

    def stream_attach(self, uid: int):
        """Re-attach a stream to a LIVE uid (ENGINE thread only — the
        front door's serve loop calls this for a mid-stream failover
        resume). Returns the tokens already landed for ``uid`` (host
        ints; ``[]`` for a still-queued fresh request) and aligns the
        listener cursor so the next iteration tail publishes only what
        follows — or None when the uid is neither seated nor queued
        (finished, acked, or never seen here)."""
        for seq in self.scheduler.active():
            if seq.request.uid == uid:
                self._stream_cursor[uid] = len(seq.tokens)
                return [int(t) for t in seq.tokens]
        entry = self.queue.find_uid(uid)
        if entry is None:
            return None
        toks = (list(entry.tokens)
                if isinstance(entry, ActiveSequence) else [])
        self._stream_cursor[uid] = len(toks)
        return [int(t) for t in toks]

    def probe_snapshot(self, tokens=None) -> dict[str, Any]:
        """Read-only routing probe for the front door (serving/
        router.py): the resident-prefix coverage the radix trie holds
        for ``tokens`` plus the replica-selection signals — ledger
        ``queue_wait`` p95 (the fallback routing key), queue/slot
        occupancy, phase, and the deployed weights epoch. Scrape-safe
        by construction (the graftlint scrape-safety rule roots here):
        :meth:`PrefixCache.probe` walks the trie without touching
        refcounts or recency, and everything else is host-side state
        the hot loop already materialized."""
        hit = 0
        if (self.prefix_cache is not None and tokens is not None
                and len(tokens) > 1):
            arr = np.asarray(tokens, np.int32)
            hit = len(self.prefix_cache.probe(
                arr, max_tokens=arr.size - 1)) * self.page_size
        return {
            "hit_tokens": hit,
            "queue_wait_p95_ms": self.telemetry.queue_wait_p95_ms(),
            "queue_depth": len(self.queue),
            "active_slots": self.scheduler.num_active,
            "draining": bool(self.draining or self._drained),
            "phase": self.phase,
            "weights_epoch": int(self.weights_epoch),
        }

    def compiled_programs(self) -> dict[str, int | None]:
        """Name → compiled-shape count per jit program — the sanitizer
        hook (``observability/sanitizer.py``). The documented inventory
        (docs/SERVING.md): ``fused`` + ``decode`` (2 programs, one shape
        each once warm). Speculation does not change these counts — the
        verify window replaces the decode lane at a wider fixed shape —
        but a GPT drafter contributes its own single-shape ``draft``
        program. Values are None when the running jax doesn't expose
        the jit cache."""
        from distributed_training_tpu.observability.sanitizer import (
            jit_cache_size,
        )
        out = {"fused": jit_cache_size(self._fused),
               "decode": jit_cache_size(self._decode)}
        if self.drafter is not None:
            out.update(self.drafter.compiled_programs())
        return out

    # -- telemetry surface ---------------------------------------------------
    def stats(self) -> dict[str, Any]:
        """SLA summary. ``queue_depth_max`` is the submit-time high-water
        (the telemetry's iteration-boundary view misses intra-boundary
        bursts, so the max of both is reported); submitted/rejected come
        from the queue's admission counters."""
        stats = self.telemetry.stats()
        stats["queue_depth_max"] = max(stats["queue_depth_max"],
                                       self.queue.depth_max)
        stats["requests_submitted"] = self.queue.submitted
        stats["requests_rejected"] = self.queue.rejected
        # Graceful-degradation counters (resilience round): load shed by
        # the bounded queue, typed drain rejections, and whether the
        # engine completed a drain (admission closed + everything
        # accepted was finished).
        stats["requests_shed"] = self.queue.shed
        # Per-tier shed breakdown (tier-aware degradation evidence: the
        # CI overload drill asserts tier 0 stays at zero while
        # best-effort tiers absorb the pressure). shed_by_tier holds
        # plain ints (queue.py) — no conversion on this hot-reachable
        # path.
        for t, n in enumerate(self.queue.shed_by_tier):
            stats[f"tier{t}_requests_shed"] = n
        stats["requests_drain_rejected"] = self.queue.drain_rejected
        stats["drained"] = bool(self._drained)
        # Crash-durable serving (serving/journal.py): the journal's
        # durability counters ride the SLA surface (requests_recovered
        # and tokens_recomputed_on_recovery come from the telemetry).
        stats["journal_records_written"] = (
            self.journal.records_written
            if self.journal is not None else 0)
        stats["journal_fsyncs"] = (
            self.journal.fsyncs if self.journal is not None else 0)
        # Live weight hot-swap: the deployed epoch joins the telemetry's
        # swaps_completed/swaps_rejected/swap_blocked_s counters.
        stats["weights_epoch"] = int(self.weights_epoch)
        # Prefix cache: the trie's resident-page gauge (the hit/insert/
        # evict counters live in the telemetry window); 0 when off so
        # downstream JSON consumers need no key guard.
        stats["prefix_cache_pages_held"] = (
            self.prefix_cache.num_pages
            if self.prefix_cache is not None else 0)
        # Serving control room (serving/alerts.py): lifetime alert and
        # incident counters ride the SLA surface — always present (0
        # with no rules configured) so downstream JSON consumers and
        # the bench_compare zero-drift gate need no key guard.
        stats["alerts_fired"] = self.alerts.fired
        stats["alerts_cleared"] = self.alerts.cleared
        stats["alerts_active"] = len(self.alerts.active)
        stats["incidents_captured"] = (
            self.incidents.captured if self.incidents is not None else 0)
        # Of the working iterations, those whose step had its successor
        # launched before its tokens were fetched (_iterate_paged).
        stats["run_ahead_share"] = (
            self._iters_ahead / self._iters_working
            if self._iters_working else 0.0)
        return stats

    def reset_stats(self) -> None:
        """Fresh telemetry window (e.g. after a compile warm-up pass);
        compiled programs, slot state, and page allocations are
        untouched. The crash-recovery counters carry across: recovery
        happened once per process, and a warm-up reset must not erase
        the evidence the recovery drill gates on. The latency ledger's
        per-cause LIFETIME histograms and conservation audit carry the
        same way (the recovery/pre_crash causes are stamped once per
        process, and a violation must never be erasable by a window
        reset); the windowed ledger surfaces — per-cause token
        counters, the slowest-requests list — start fresh."""
        old = self.telemetry
        self.telemetry = ServeTelemetry(self.cfg.ring_size,
                                        num_tiers=self.cfg.num_tiers)
        self.telemetry.on_recovered(old.requests_recovered,
                                    old.tokens_recomputed_on_recovery)
        self.telemetry.adopt_ledger_lifetime(old)
        # Quantization gauges are facts of the engine build, not of a
        # measurement window: re-seed them (weight_quant_s carries its
        # lifetime accumulation — construction + every armed swap —
        # attributed exactly like swap staging cost).
        self.telemetry.on_weight_quant(self._weight_quant_s,
                                       self._quantized_params_bytes)
        self.telemetry.set_kv_bytes_per_token(old.kv_bytes_per_token)
        self.queue.reset_counters()
        # Control room: the sample ring is a windowed instrument — it
        # starts fresh with the new window (stale pre-reset samples
        # must not feed post-reset burn rates). The alert engine and
        # incident writer are process history, exactly like the
        # recovery counters above: an alert that fired (or an incident
        # that was captured) before a warm-up reset really happened,
        # and reset_stats must not erase the evidence.
        self.timeseries = TelemetryRing(self.cfg.timeseries_capacity,
                                        self.cfg.sample_every)
        self._iteration = 0
        self._iters_working = self._iters_ahead = 0

    def _control_room_sections(self) -> dict[str, Any]:
        """The ``alerts`` + ``timeseries`` top-level sections flight
        snapshots and dumps carry (tools/flight_report.py renders both;
        ``tools/incident_report.py`` reads the same shapes from an
        incident bundle). The time-series section is trimmed to the
        newest ``TIMESERIES_DUMP_SAMPLES`` samples — enough to cover
        the slow alert window with margin, small enough that a dump
        stays a quick read."""
        return {
            "alerts": self.alerts.to_dict(),
            "timeseries": self.timeseries.to_dict(
                last_n=TIMESERIES_DUMP_SAMPLES),
        }

    def flight_snapshot(self, *, reason: str = "scrape") -> dict[str, Any]:
        """The live flight snapshot a /metrics scrape serves — same
        composition as :meth:`dump_flight` but no disk write and NO
        flush (a scrape observes, it must not mutate the flush ring).
        Every input is host-side state this thread already owns or
        lock-guarded queue counters — scrape-safe from the exporter's
        handler thread while the serving loop runs."""
        return self.telemetry.snapshot(
            reason=reason, stats=self.stats(),
            extra_sections=self._control_room_sections())

    def dump_flight(self, path: str, *,
                    reason: str = "serving") -> dict[str, Any]:
        """Flight-recorder-compatible JSON dump (tools/flight_report.py)."""
        self.telemetry.flush(self._iteration, len(self.queue),
                             self.scheduler.num_active)
        return self.telemetry.dump(
            path, reason=reason, stats=self.stats(),
            extra_sections=self._control_room_sections())

    def timeseries_snapshot(self) -> dict[str, Any]:
        """Read-only JSON view of the telemetry ring for the exporter's
        ``/timeseries`` endpoint — a scrape copies rows, it never
        mutates (the scrape-safety lint rule pins this)."""
        return self.timeseries.to_dict(last_n=TIMESERIES_DUMP_SAMPLES)

    def alerts_snapshot(self) -> dict[str, Any]:
        """Read-only JSON view of the alert engine (rules, counters,
        active set, event log) for the exporter's ``/alerts`` endpoint.
        Evaluation happens only on the engine thread at sample cadence;
        a scrape only reads the log."""
        return self.alerts.to_dict()

    def close_incidents(self) -> None:
        """Flush and stop the incident writer thread (drains any queued
        bundles to disk synchronously). Idempotent; no-op when no
        incident dir was configured. CLIs call this at exit, after the
        last iteration, exactly like ``journal.shutdown()``."""
        if self.incidents is not None:
            self.incidents.shutdown()
