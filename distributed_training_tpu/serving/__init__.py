"""Serving subsystem: continuous-batching inference over the KV cache.

The first consumer-facing layer of the framework (ROADMAP north star:
"serves heavy traffic from millions of users"). Orca-style
iteration-level batching + vLLM-style fixed-slot cache management,
restated for XLA's static-shape world:

- :mod:`queue` — thread-safe SLO-tiered admission (priority 0 = highest;
  FIFO within a (tier, tenant) lane, weighted-fair across tenants,
  tier-aware shedding on a full queue) with a per-request cache-budget
  guard in page-based accounting (typed rejection, not a wedged queue
  head).
- :mod:`pages` — the fixed-size KV page pool (PagedAttention's memory
  model, host half): free-list allocator with commitment-based
  admission safety and per-page reference counts (shared prefix pages
  free exactly once, at the last holder); physical page 0 reserved as
  the device null page.
- :mod:`prefix_cache` — radix-tree prefix cache (SGLang RadixAttention
  / vLLM automatic-prefix-caching shape): finished sequences' committed
  page chains stay indexed in a content-addressed trie; a request whose
  prompt starts with a resident page-aligned chain aliases those pages
  into its block table and prefills only the tail. Refcounted, LRU
  eviction under pressure, flushed at every hot-swap barrier; cache
  hits are bitwise-neutral by construction.
- :mod:`scheduler` — fixed decode slots; tier-strict tenant-fair refill
  (page-aware via a ``can_seat`` gate), LOSSLESS preempt-and-requeue of
  lower tiers under pressure (the evicted sequence re-prefills its
  emitted tokens and continues the same RNG stream — bitwise identical
  to an uninterrupted run), and EOS/length/deadline eviction at
  iteration boundaries; active masks instead of shape changes.
- :mod:`engine` — paged KV + chunked prefill (a fused
  prefill-chunk+decode step and a decode-only step over one shared page
  pool) and the admit→prefill→decode→evict loop.
- :mod:`speculative` — draft-and-verify speculative decoding: a per-slot
  drafter (prompt-lookup n-gram by default, or a GPT draft model)
  proposes ``spec_k`` tokens and the engine's decode step widens to a
  fixed ``[max_batch, spec_k + 1]`` verify window with a mask-based
  lossless accept — emitted tokens stay bitwise identical to the
  sequential path, one dispatch lands up to ``spec_k + 1`` of them.
- :mod:`metrics` — TTFT/TPOT/throughput/queue-depth SLA telemetry through
  the round-7 flight recorder, plus KV/slot utilization accounting
  (reserved-vs-written cache positions, queue-wait vs prefill breakdown,
  admission-blocked time) — live-scrapeable via ``--metrics-port``
  (``observability/exporter.py``).
- :mod:`timeseries` / :mod:`alerts` — the serving control room: a
  fixed-capacity telemetry sample ring appended at iteration-count
  cadence (windowed delta/rate/quantile queries, bitwise-reproducible
  under ``--virtual-dt``), a declarative multi-window SLO burn-rate
  alert engine (fast AND slow windows must burn to fire; hysteresis to
  clear; typed fire/clear events on a bounded deterministic log), and
  an off-hot-path incident writer that lands one atomic bundle (alert
  + log + time-series window + flight snapshot) per fire
  (``tools/incident_report.py`` renders them). Scrapeable live at
  ``/timeseries`` and ``/alerts``.
- :mod:`journal` — crash-durable serving: an append-only, crc-framed
  write-ahead request journal (admissions durable at submit; token/
  preempt/finish records persisted off the hot loop by a writer
  thread; segment rotation compacts finished work; torn tails are
  truncated and quarantined, never a crash). ``Engine.recover()``
  replays it on restart: finished results re-deliver exactly once via
  a client cursor, unfinished requests re-seat through the preemption
  resume path and complete bitwise identical to an uninterrupted run.
- :mod:`hotswap` — zero-drain live weight hot-swap: a watcher streams
  newly COMMITTED checkpoints through the resilience verification path
  into the running engine at a decode-iteration boundary (in-flight
  requests keep their KV pages); torn/corrupt candidates are
  quarantined and never touch the engine, and ``Engine.rollback()``
  re-arms the previous weights.
- :mod:`supervisor` — fleet fault tolerance over the :mod:`frontend` /
  :mod:`router` network plane: a ReplicaSupervisor that owns replica
  processes, detects death (waitpid + failed health probes) and
  wedged serve loops (frozen ``/healthz`` heartbeat), and restarts
  each with its journal dir so recovery replays before the port
  reopens; the router adds per-replica circuit breakers and
  mid-stream SSE failover with a resume cursor.

Surfaces: ``gpt/jax_tpu/serve.py`` (interactive/file serving CLI) and
``tools/serve_bench.py`` driving the seeded traffic-scenario library
(``tools/traffic.py``: Poisson/bursty/diurnal arrivals, heavy-tailed
sizes, multi-tenant SLO-tier mixes, preemption storms — composable
with hot-swap and speculation chaos drills). See docs/SERVING.md.
"""

from distributed_training_tpu.resilience.errors import (  # noqa: F401
    DrainingError,
    JournalCorruptError,
    QueueFullError,
    SwapError,
)
from distributed_training_tpu.serving.alerts import (  # noqa: F401
    AlertEngine,
    IncidentWriter,
    SLORule,
    default_rules,
    parse_slo_rules,
)
from distributed_training_tpu.serving.engine import Engine  # noqa: F401
from distributed_training_tpu.serving.frontend import (  # noqa: F401
    ServingFrontend,
)
from distributed_training_tpu.serving.journal import (  # noqa: F401
    JournaledRequest,
    RecoveredState,
    RequestJournal,
)
from distributed_training_tpu.serving.hotswap import (  # noqa: F401
    HotSwapper,
    committed_epochs,
)
from distributed_training_tpu.serving.ledger import (  # noqa: F401
    LEDGER_CAUSES,
    TOKEN_CAUSES,
    LatencyLedger,
)
from distributed_training_tpu.serving.metrics import ServeTelemetry  # noqa: F401
from distributed_training_tpu.serving.pages import (  # noqa: F401
    NULL_PAGE,
    PagePool,
    pages_for,
)
from distributed_training_tpu.serving.prefix_cache import (  # noqa: F401
    PrefixCache,
)
from distributed_training_tpu.serving.queue import RequestQueue  # noqa: F401
from distributed_training_tpu.serving.request import (  # noqa: F401
    FINISH_CANCELLED,
    FINISH_EOS,
    FINISH_LENGTH,
    FINISH_PREEMPT_TIMEOUT,
    FINISH_SHED,
    FINISH_TIMEOUT,
    ActiveSequence,
    FinishedRequest,
    Request,
)
from distributed_training_tpu.serving.router import (  # noqa: F401
    HttpReplica,
    Router,
    RouterFrontDoor,
)
from distributed_training_tpu.serving.supervisor import (  # noqa: F401
    ReplicaSupervisor,
)
from distributed_training_tpu.serving.scheduler import SlotScheduler  # noqa: F401
from distributed_training_tpu.serving.speculative import (  # noqa: F401
    Drafter,
    GPTDrafter,
    NGramDrafter,
)
from distributed_training_tpu.serving.timeseries import (  # noqa: F401
    TelemetryRing,
)
