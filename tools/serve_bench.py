#!/usr/bin/env python
"""Synthetic-load benchmark for the continuous-batching serving engine.

Drives ``distributed_training_tpu/serving/`` with a seeded traffic
scenario (``tools/traffic.py``; ``--scenario poisson`` is the classic
exponential-inter-arrival process at ``--rate`` req/s, others add
bursts, diurnal cycles, heavy-tailed sizes, multi-tenant SLO-tier
mixes, and engineered preemption storms) over random-token prompts
against a random-weight GPT, and prints ONE strict-JSON line with the
SLA summary:

    {"throughput_tok_s": ..., "ttft_p50_ms": ..., "ttft_p95_ms": ...,
     "tpot_p50_ms": ..., "tpot_p95_ms": ..., "ttft_hist_p50_ms": ...,
     "ttft_hist_p95_ms": ..., "ttft_hist_p99_ms": ...,
     "tpot_hist_p50_ms": ..., ..., "queue_depth_max": ..., ...}

(The `*_hist_*` percentiles are derived from the fixed-bucket SLO
histograms in serving/metrics.py — bucket-resolution, mergeable, the
numbers a Prometheus scrape of the flight dump would report.)

Same contract as bench.py's JSON lines: machine-readable, last line of
stdout, parseable by ``json.loads`` (the CI smoke step asserts exactly
that plus ``throughput_tok_s > 0``). Warm-up requests (compile) are
served before the measured window unless ``--no-warmup``.

    python tools/serve_bench.py --requests 32 --rate 50 --max-batch 8
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def add_argument() -> argparse.Namespace:
    from distributed_training_tpu.config import kv_page_size_arg

    p = argparse.ArgumentParser(
        description="Poisson-load benchmark for the serving engine")
    p.add_argument("--requests", type=int, default=32,
                   help="measured requests")
    p.add_argument("--rate", type=float, default=50.0,
                   help="mean arrival rate, requests/second")
    p.add_argument("--scenario", type=str, default="poisson",
                   help="traffic scenario (tools/traffic.py): poisson, "
                        "bursty, diurnal, heavy_tail, multi_tenant, "
                        "two_tier_burst, preempt_storm. Multi-tier "
                        "scenarios raise --num-tiers and apply their "
                        "tenant weights automatically; compose chaos "
                        "drills with --swap-at-request / --spec-k")
    p.add_argument("--virtual-dt", type=float, default=0.0,
                   help="deterministic drive: release scenario arrivals "
                        "on a virtual clock advancing this many ms per "
                        "engine iteration instead of wall time — the "
                        "whole admission/preempt/shed schedule becomes "
                        "a pure function of (--scenario, --seed), so "
                        "the scheduling counters are bitwise "
                        "reproducible across runs and machines (the CI "
                        "overload drill gates on this). 0 = wall clock")
    p.add_argument("--num-tiers", type=int, default=0,
                   help="SLO tiers (0 = the scenario's own tier count); "
                        "priority 0 = highest, larger tiers degrade "
                        "first under load")
    p.add_argument("--tenant-quota", type=int, default=None,
                   help="max concurrently seated requests per tenant")
    p.add_argument("--tier-reserved-slots", type=int, default=0,
                   help="decode slots held back from non-top tiers so "
                        "tier-0 arrivals always find headroom")
    p.add_argument("--tier-reserved-pages", type=int, default=0,
                   help="KV pool pages held back from non-top tiers")
    p.add_argument("--no-preempt", action="store_true", default=False,
                   help="disable lossless preempt-and-requeue (tiers "
                        "then only order the queue)")
    p.add_argument("--max-queue-depth", type=int, default=None,
                   help="bounded admission: beyond this depth the "
                        "NEWEST queued best-effort request is shed to "
                        "admit higher-tier work (the incoming request "
                        "itself is shed when nothing lower-tier is "
                        "queued)")
    p.add_argument("--max-batch", type=int, default=8)
    p.add_argument("--max-len", type=int, default=None,
                   help="per-slot KV budget; default model max-len")
    p.add_argument("--prompt-len", type=int, default=32,
                   help="mean prompt length (uniform in [1, 2*mean-1])")
    p.add_argument("--max-new-tokens", type=int, default=32)
    p.add_argument("--temperature", type=float, default=0.0)
    p.add_argument("--eos-id", type=int, default=None)
    p.add_argument("--kv-page-size", type=kv_page_size_arg, default=8,
                   help="paged KV cache: pool page size in tokens")
    p.add_argument("--kv-pages", type=int, default=None,
                   help="KV pool size in pages; default max_batch x "
                        "ceil(budget/page) (no oversubscription)")
    p.add_argument("--prefill-chunk", type=int, default=64,
                   help="chunked prefill: prompt tokens prefilled per "
                        "decode iteration")
    p.add_argument("--prefix-cache", action=argparse.BooleanOptionalAction,
                   default=False,
                   help="radix-tree prefix cache over the paged pool "
                        "(docs/SERVING.md 'Prefix caching'): finished "
                        "requests' KV page chains stay indexed, and a "
                        "request sharing a page-aligned token prefix "
                        "aliases them and prefills only the tail — "
                        "bitwise-neutral, pure TTFT/prefill-compute "
                        "win on shared-boilerplate traffic (pair with "
                        "--scenario shared_prefix)")
    p.add_argument("--prefix-cache-pages", type=int, default=None,
                   help="cap on pool pages the prefix-cache trie may "
                        "hold (LRU leaves evict past it); default "
                        "unbounded within the pool")
    p.add_argument("--spec-k", type=int, default=0,
                   help="speculative decoding: drafts proposed per slot "
                        "per iteration, verified in one fixed-width "
                        "[max_batch, k+1] dispatch with lossless accept "
                        "(docs/SERVING.md). 0 = off")
    p.add_argument("--spec-drafter", type=str, default="ngram",
                   choices=["ngram", "gpt"],
                   help="drafter backend: 'ngram' = prompt-lookup, zero "
                        "extra params; 'gpt' = greedy draft model over "
                        "a fixed window (self-drafts with the serving "
                        "weights; adds one compiled 'draft' program)")
    p.add_argument("--spec-ngram", type=int, default=3,
                   help="longest context suffix the n-gram drafter "
                        "matches (backs off to 1)")
    p.add_argument("--spec-draft-window", type=int, default=16,
                   help="gpt drafter: context tokens re-run per draft "
                        "step")
    p.add_argument("--quantize-weights", action="store_true",
                   default=False,
                   help="quantized execution (docs/SERVING.md "
                        "'Quantized execution'): symmetric per-channel "
                        "int8 for the transformer matmul weights, "
                        "quantized ONCE at engine construction / swap "
                        "staging time (never inside the hot loop); "
                        "layernorms, biases and the logits head stay "
                        "full precision. Deterministic: two quantized "
                        "runs are bitwise-identical")
    p.add_argument("--kv-dtype", type=str, default=None,
                   choices=["int8"],
                   help="paged KV cache storage dtype: 'int8' stores "
                        "pages as int8 with per-row per-head scales "
                        "(quantize-on-scatter / dequantize-in-gather "
                        "inside the same compiled programs — the "
                        "inventory stays at 2). Default: model dtype")
    # Tiny random-weight model (no checkpoint: this benches the ENGINE —
    # scheduling, prefill/decode latency — not model quality).
    p.add_argument("--vocab-size", type=int, default=256)
    p.add_argument("--num-layers", type=int, default=2)
    p.add_argument("--num-heads", type=int, default=2)
    p.add_argument("--hidden-dim", type=int, default=64)
    p.add_argument("--model-max-len", type=int, default=256)
    p.add_argument("--no-warmup", action="store_true", default=False,
                   help="skip the compile warm-up pass (its compile time "
                        "then lands in the measured TTFT tail)")
    p.add_argument("--swap-at-request", type=int, default=0,
                   help="mid-run hot-swap mode: when the Nth measured "
                        "request is submitted, arm a live weight swap "
                        "to a second (differently seeded) random init — "
                        "the engine applies it at the next iteration "
                        "boundary under the Poisson load, so the SLA "
                        "line measures swap cost (swaps_completed, "
                        "swap_blocked_s) alongside latency. 0 = off")
    p.add_argument("--check-compiles", action="store_true", default=False,
                   help="compiled-program sanitizer: after warm-up, pin "
                        "the engine's program inventory (2 programs; "
                        "docs/SERVING.md) and fail — exit 1, "
                        "one-line error — if anything recompiles inside "
                        "the measured window (silent retrace growth). "
                        "Requires warm-up (ignored with --no-warmup)")
    # Crash-durable serving (serving/journal.py; docs/RESILIENCE.md
    # "Crash-durable serving").
    p.add_argument("--journal-dir", type=str, default=None,
                   help="write-ahead request journal: admissions are "
                        "durable before submit returns, progress "
                        "persists off the hot loop, and a restart with "
                        "the SAME flags replays the log — finished "
                        "results re-deliver exactly once, unfinished "
                        "requests resume and complete bitwise-equal "
                        "to the uninterrupted run (the bench continues "
                        "the scenario from its journaled submission "
                        "cursor and skips warm-up)")
    p.add_argument("--journal-fsync", type=str, default="batch",
                   choices=["none", "batch", "always"],
                   help="journal durability: 'none' = OS page cache "
                        "(survives kill -9, not power loss), 'batch' = "
                        "one fsync per writer flush, 'always' = fsync "
                        "per record")
    p.add_argument("--journal-segment-bytes", type=int, default=1 << 20,
                   help="journal segment rotation threshold: past this "
                        "the live state compacts into a fresh segment "
                        "and old segments are deleted (bounded growth)")
    p.add_argument("--kill-at-request", type=int, default=0,
                   help="crash drill (resilience/chaos.py): SIGKILL "
                        "this process the moment the Nth measured "
                        "request has been submitted, after draining "
                        "the journal queue to disk — so the durable "
                        "state at death is deterministic. Restart with "
                        "the same flags + --journal-dir to recover. "
                        "0 = off")
    p.add_argument("--completions-out", type=str, default=None,
                   help="write every delivered completion (uid, finish "
                        "reason, token ids; redelivered recoveries "
                        "included) as one JSON list — the crash "
                        "drill's bitwise-comparison artifact")
    p.add_argument("--ledger-out", type=str, default=None,
                   help="write every delivered completion's latency "
                        "ledger (serving/ledger.py) as one strict-JSON "
                        "list: per-request (cause, start, end) "
                        "intervals partitioning its wall lifetime, "
                        "per-cause totals and token counts, and the "
                        "conservation verdict (sum(intervals) == "
                        "lifetime within the documented epsilon). "
                        "Results redelivered from the journal carry "
                        "ledger null — their wall detail belongs to "
                        "the process that served them")
    p.add_argument("--flight-dump", type=str, default=None)
    p.add_argument("--metrics-port", type=int, default=None,
                   help="live telemetry plane: /metrics (Prometheus "
                        "text), /healthz, /vars, /timeseries and "
                        "/alerts scrapeable while the bench runs "
                        "(loopback; 0 = ephemeral port)")
    # Serving control room (serving/timeseries.py + serving/alerts.py;
    # docs/OBSERVABILITY.md "Serving SLO alerting & incident capture").
    p.add_argument("--slo-rules", type=str, default=None,
                   help="SLO burn-rate alerting: 'default' for the "
                        "built-in rule set, or ';'-separated "
                        "name:metric[/den]>objective[@fast,slow]"
                        "[xburn][~clear] clauses (serving/alerts.py). "
                        "Rules are evaluated every --sample-every "
                        "iterations over the telemetry ring; off when "
                        "unset")
    p.add_argument("--incident-dir", type=str, default=None,
                   help="write one atomic incident bundle (firing "
                        "alert + alert log + last time-series window + "
                        "flight snapshot) per alert fire into this "
                        "directory, off the hot path "
                        "(tools/incident_report.py renders them); "
                        "requires --slo-rules")
    p.add_argument("--sample-every", type=int, default=16,
                   help="telemetry ring sample cadence in iterations "
                        "(iteration count, never wall time — "
                        "--virtual-dt alert drills are bitwise "
                        "reproducible)")
    p.add_argument("--alert-log-out", type=str, default=None,
                   help="write the full alert-engine state (rules, "
                        "counters, fire/clear event log) as strict "
                        "JSON at exit — the CI alert drill's bitwise "
                        "determinism artifact")
    p.add_argument("--trace", action=argparse.BooleanOptionalAction,
                   default=False,
                   help="span-level Perfetto trace of the measured "
                        "window: one track per decode slot with request "
                        "lifecycles (tools/trace_report.py summarizes)")
    p.add_argument("--trace-dir", type=str, default="./trace",
                   help="trace output directory")
    p.add_argument("--seed", type=int, default=0)
    return p.parse_args()


def main() -> int:
    args = add_argument()

    import jax
    import numpy as np

    from distributed_training_tpu.config import ServeConfig
    from distributed_training_tpu.models import get_model
    from distributed_training_tpu.runtime.backend import enable_compile_cache
    from distributed_training_tpu.serving import Engine

    enable_compile_cache()
    # Per-slot budget exactly as the engine computes it; sampled prompt
    # lengths are clamped so every generated request is admissible (an
    # uncaught CacheBudgetError mid-measurement would kill the bench
    # after the warm-up time was already spent).
    budget = min(args.max_len or args.model_max_len, args.model_max_len)
    max_prompt = budget - args.max_new_tokens
    if max_prompt < 1:
        raise SystemExit(
            f"--max-new-tokens {args.max_new_tokens} leaves no room for a "
            f"prompt in the {budget}-token per-slot budget "
            f"(--max-len/--model-max-len)")

    # Scenario first (tools/traffic.py): it decides the tier count and
    # tenant weights the engine config needs, and generating it is
    # jax-free. Deterministic in (--scenario, --seed).
    from tools.traffic import SCENARIOS, make_scenario

    if args.scenario not in SCENARIOS:
        raise SystemExit(
            f"unknown --scenario {args.scenario!r} "
            f"(have: {', '.join(sorted(SCENARIOS))})")
    scen = SCENARIOS[args.scenario]
    # Never below what the scenario submits: an explicit smaller
    # --num-tiers would make every higher-numbered arrival die in
    # submit() with a priority ValueError mid-measurement.
    num_tiers = max(args.num_tiers, scen.num_tiers)
    load = make_scenario(
        args.scenario, seed=args.seed, requests=args.requests,
        rate=args.rate, mean_prompt_len=args.prompt_len,
        max_prompt_len=max_prompt, max_new_tokens=args.max_new_tokens,
        vocab_size=args.vocab_size, budget=budget)

    model = get_model(
        "transformer_lm", num_classes=args.vocab_size,
        num_layers=args.num_layers, num_heads=args.num_heads,
        hidden_dim=args.hidden_dim, max_len=args.model_max_len)
    params = model.init(jax.random.PRNGKey(args.seed),
                        np.zeros((1, 8), np.int32))["params"]

    from distributed_training_tpu.observability.trace import (
        session_for_cli,
    )

    trace, trace_path = session_for_cli(args.trace, args.trace_dir,
                                        "serve_bench")

    engine = Engine(model, params, ServeConfig(
        max_batch=args.max_batch, max_len=args.max_len,
        max_new_tokens=args.max_new_tokens,
        temperature=args.temperature, eos_id=args.eos_id,
        kv_page_size=args.kv_page_size,
        kv_pages=args.kv_pages,
        prefill_chunk=args.prefill_chunk,
        prefix_cache=args.prefix_cache,
        prefix_cache_pages=args.prefix_cache_pages,
        spec_k=args.spec_k, spec_drafter=args.spec_drafter,
        spec_ngram=args.spec_ngram,
        spec_draft_window=args.spec_draft_window,
        quantize_weights=args.quantize_weights,
        kv_dtype=args.kv_dtype,
        num_tiers=num_tiers, tenant_quota=args.tenant_quota,
        tenant_weights=scen.tenant_weights,
        tier_reserved_slots=args.tier_reserved_slots,
        tier_reserved_pages=args.tier_reserved_pages,
        preempt=not args.no_preempt,
        max_queue_depth=args.max_queue_depth,
        journal_dir=args.journal_dir,
        journal_fsync=args.journal_fsync,
        journal_segment_bytes=args.journal_segment_bytes,
        sample_every=args.sample_every,
        slo_rules=args.slo_rules,
        incident_dir=args.incident_dir,
        seed=args.seed), trace=trace)

    # Crash-durable serving: replay the write-ahead journal BEFORE any
    # traffic. Finished-but-undelivered results re-deliver from the
    # log; unfinished requests re-seat through the resume path (their
    # continued outputs are bitwise the uninterrupted run's); the
    # journaled submission cursor tells this process where the
    # scenario left off.
    report = engine.recover()
    recovered_n = (len(report["redelivered"])
                   + len(report["completed_at_replay"])
                   + report["resumed"])
    submitted_start = int(report["notes"].get("submitted", 0))
    recovering = recovered_n > 0 or submitted_start > 0
    if recovering:
        print(f"[serve_bench] journal recovery: "
              f"{len(report['redelivered'])} redelivered, "
              f"{report['resumed']} resumed, "
              f"{len(report['completed_at_replay'])} completed at "
              f"replay; scenario continues at request "
              f"{submitted_start}/{args.requests}", file=sys.stderr)

    # Live telemetry plane: the measured window is scrapeable while it
    # runs.
    exporter = None
    if args.metrics_port is not None:
        from distributed_training_tpu.observability.exporter import (
            attach_engine,
        )

        exporter = attach_engine(
            engine, args.metrics_port, component="serve_bench",
            printer=lambda msg: print(msg, file=sys.stderr, flush=True))

    rng = np.random.RandomState(args.seed)

    if not args.no_warmup and recovering:
        # Recovery replay re-prefills and decodes through the normal
        # compiled paths, so it IS the warm-up; re-running the warm-up
        # pass here would also burn journaled uids and shift every
        # subsequent request's fold_in(seed, uid) stream off the
        # uninterrupted run's.
        print("[serve_bench] warm-up skipped (journal recovery warms "
              "the compiled paths)", file=sys.stderr)
    elif not args.no_warmup:
        # Compile on the measured engine itself (compiles are
        # per-jit-closure, so a throwaway engine would not warm this
        # one), then reset the telemetry window. The engine has exactly
        # two shapes — the fused chunk+decode step and the decode-only
        # step — so two short requests cover them.
        # Speculation needs at least one drafted decode iteration in
        # the warm-up (remaining budget > 1) so a GPT drafter's
        # 'draft' program compiles outside the measured window; the
        # verify window itself is one fixed shape either way.
        # Each warm-up request runs to completion before the next
        # submits: a tight --max-queue-depth must not shed (crash) the
        # warm-up, and one request per shape covers every compiled
        # program either way (shapes are fixed-width, independent of
        # how many slots are active).
        warm_new = 4 if args.spec_k else 2
        warm_fins = []
        for _ in range(2):
            engine.submit(rng.randint(0, args.vocab_size,
                                      size=2).astype(np.int32),
                          max_new_tokens=warm_new)
            warm_fins.extend(engine.run())
        if engine.journal is not None:
            # Warm-up results are consumed here and now: ack them so a
            # later recovery neither redelivers them nor carries them
            # through compaction.
            engine.journal.ack([f.uid for f in warm_fins])
        engine.reset_stats()
        print(f"[serve_bench] warm-up done "
              f"({sum(f.tokens.size for f in warm_fins)} tokens)",
              file=sys.stderr)

    compile_watch = None
    if args.check_compiles and recovering:
        # A recovery restart starts cold (warm-up is skipped so uids
        # stay on the oracle's RNG streams): the measured window's
        # first dispatches MUST compile, so the no-growth pin cannot
        # apply — same reason it requires warm-up.
        print("[serve_bench] --check-compiles skipped (journal "
              "recovery restart runs cold)", file=sys.stderr)
    elif args.check_compiles and not args.no_warmup:
        # Sanitizer (observability/sanitizer.py): the warm engine's
        # program inventory must match docs/SERVING.md, and the measured
        # window below must not compile anything at all.
        from distributed_training_tpu.observability.sanitizer import (
            CompileWatch,
            RecompileError,
            check_engine_inventory,
        )

        try:
            inventory = check_engine_inventory(engine)
        except RecompileError as e:
            print(f"serve_bench: error: {e}", file=sys.stderr)
            return 1
        print(f"[serve_bench] compiled-program inventory OK: "
              f"{inventory}", file=sys.stderr)
        compile_watch = CompileWatch()

    n = args.requests

    # Mid-run hot-swap mode: the staged tree is built BEFORE the
    # measured window (staging is off the engine's hot path in real
    # deployments too — only the arm + iteration-boundary barrier land
    # inside the measurement, which is exactly the cost being gated).
    swap_params = None
    if args.swap_at_request:
        if not 1 <= args.swap_at_request <= n:
            raise SystemExit(f"--swap-at-request must be in [1, "
                             f"{n}], got {args.swap_at_request}")
        swap_params = model.init(jax.random.PRNGKey(args.seed + 1),
                                 np.zeros((1, 8), np.int32))["params"]
    if args.kill_at_request:
        if not 1 <= args.kill_at_request <= n:
            raise SystemExit(f"--kill-at-request must be in [1, {n}], "
                             f"got {args.kill_at_request}")

    from distributed_training_tpu.resilience.errors import QueueFullError

    # Delivered completions: journal recoveries first (redelivered
    # finished results + requests completed at replay), then everything
    # the measured loop and the drain finish. The crash drill compares
    # this set bitwise against the uninterrupted oracle.
    completions = list(report["redelivered"]) \
        + list(report["completed_at_replay"])
    submitted = submitted_start
    finished = 0
    shed_at_submit = 0

    def submit_next(arrival_t=None):
        """Submit the next scenario arrival; a bounded-queue shed of the
        INCOMING request counts here (a shed of a queued lower-tier
        victim instead surfaces as a 'shed' completion from step()).
        With a journal, the submission cursor persists BEFORE the
        admission record: a crash between the two drops a request that
        was never durably accepted (at-most-once), never duplicates
        one."""
        nonlocal submitted, shed_at_submit
        r = load[submitted]
        if engine.journal is not None:
            # Enqueue-only: the admit inside engine.submit persists the
            # same ordered batch, so the cursor is durable whenever the
            # admit is — one fsync per request, not two.
            engine.journal.log_note({"submitted": submitted + 1},
                                    flush=False)
        try:
            engine.submit(r.prompt, max_new_tokens=r.max_new_tokens,
                          arrival_t=arrival_t, priority=r.priority,
                          tenant=r.tenant)
        except QueueFullError:
            shed_at_submit += 1
        submitted += 1
        if swap_params is not None and submitted == args.swap_at_request:
            engine.arm_swap(swap_params, epoch=engine.weights_epoch + 1)
        if args.kill_at_request and submitted == args.kill_at_request:
            from distributed_training_tpu.resilience.chaos import (
                hard_kill,
            )

            hard_kill(flush=None if engine.journal is None
                      else engine.journal.persist)

    if args.virtual_dt > 0:
        # Deterministic drive: arrivals release on a virtual clock that
        # advances --virtual-dt ms per engine iteration. Token streams
        # are deterministic, so the full admission/preempt/shed schedule
        # is a pure function of (scenario, seed) — bitwise reproducible
        # across runs AND machines. TTFT/TPOT keep wall semantics
        # (arrival_t = the submit instant); only release timing is
        # virtualized, so latency stats remain real, merely paced by
        # iterations instead of seconds.
        # After a recovery restart the scenario clock re-anchors at the
        # first still-pending arrival, so the continuation releases
        # immediately instead of replaying the dead process's idle
        # time. A fresh run keeps the scenario origin (bitwise-stable
        # schedule vs the committed baseline).
        v0 = (load[submitted].arrival_s
              if recovering and submitted < n else 0.0)
        it = 0
        while submitted < n:
            vnow = v0 + it * args.virtual_dt / 1e3
            while submitted < n and load[submitted].arrival_s <= vnow:
                submit_next()
            step_fins = engine.step()
            completions.extend(step_fins)
            finished += len(step_fins)
            it += 1
    else:
        w0 = (load[submitted].arrival_s
              if recovering and submitted < n else 0.0)
        t0 = time.perf_counter() - w0
        while submitted < n:
            now = time.perf_counter() - t0
            while submitted < n and load[submitted].arrival_s <= now:
                submit_next(arrival_t=t0 + load[submitted].arrival_s)
            if engine.idle and submitted < n:
                # Ahead of the arrival process: sleep to the next
                # arrival instead of spinning empty iterations.
                time.sleep(min(load[submitted].arrival_s - now, 0.05))
                continue
            step_fins = engine.step()
            completions.extend(step_fins)
            finished += len(step_fins)
    # End through a graceful drain: admission closes and every accepted
    # request completes — preempted-and-requeued sequences included —
    # and is COUNTED before the SLA line is emitted; a hard stop here
    # used to drop tail requests from the percentiles.
    drain_fins = engine.drain()
    completions.extend(drain_fins)
    finished += len(drain_fins)
    # Completion accounting: this process's deliveries (recoveries +
    # finishes) plus its sheds must cover what it drove — the scenario
    # tail it submitted plus everything the journal owed it. A fresh
    # run degenerates to the old finished + shed == n identity.
    delivered = finished + len(report["redelivered"]) \
        + len(report["completed_at_replay"])
    expected = (n - submitted_start) + recovered_n
    assert delivered + shed_at_submit == expected, (
        f"delivered {delivered} + {shed_at_submit} shed-at-submit, "
        f"expected {expected} ({n} requests, scenario resumed at "
        f"{submitted_start}, {recovered_n} recovered)")
    # Leak audit: every page back on the free list (or held by
    # exactly the prefix-cache trie at one reference each), no
    # stranded commitment — speculation's accept-rewind and the
    # prefix cache's aliasing/eviction churn included (the CI
    # speculation and prefix-cache legs run on this assertion).
    engine.check_balanced()

    if compile_watch is not None:
        from distributed_training_tpu.observability.sanitizer import (
            RecompileError,
        )

        try:
            compile_watch.check_no_growth("the measured serving window")
        except RecompileError as e:
            print(f"serve_bench: error: {e}", file=sys.stderr)
            return 1

    stats = engine.stats()
    stats["requests"] = n
    stats["arrival_rate_req_s"] = args.rate
    stats["max_batch"] = args.max_batch
    stats["scenario"] = args.scenario
    stats["shed_at_submit"] = shed_at_submit
    # Network front door (serving/router.py): this bench drives ONE
    # engine in-process, so the router counters are definitionally zero
    # — emitted anyway so bench_compare's zero-drift gate pins them on
    # every non-network row (serve_net.py fills them in for real).
    stats["router_requests_routed"] = 0
    stats["router_prefix_routed"] = 0
    stats["router_fallback_routed"] = 0
    if args.completions_out:
        with open(args.completions_out, "w") as fh:
            json.dump([{"uid": int(f.uid), "reason": f.finish_reason,
                        "tokens": [int(t) for t in f.tokens]}
                       for f in sorted(completions,
                                       key=lambda f: f.uid)], fh)
        print(f"[serve_bench] completions: {args.completions_out} "
              f"({len(completions)} requests)", file=sys.stderr)
    if args.ledger_out:
        from distributed_training_tpu.serving.ledger import dump_ledgers

        n_rows, bad = dump_ledgers(args.ledger_out, completions)
        print(f"[serve_bench] latency ledgers: {args.ledger_out} "
              f"({n_rows} requests, {bad} conservation "
              f"violation(s))", file=sys.stderr)
    if engine.journal is not None:
        # The client cursor: everything above is durably consumed
        # (printed / written out), so a future recovery must not
        # redeliver it — and compaction may drop it.
        engine.journal.ack([f.uid for f in completions])
        engine.journal.shutdown()
    if args.flight_dump:
        engine.dump_flight(args.flight_dump, reason="serve_bench")
        print(f"[serve_bench] flight record: {args.flight_dump}",
              file=sys.stderr)
    # Control room artifacts: drain the incident writer (bundles hit
    # disk before the process exits), then the alert log — the CI
    # drill diffs two --virtual-dt runs' logs byte for byte.
    engine.close_incidents()
    if args.incident_dir and engine.incidents is not None:
        print(f"[serve_bench] incidents: {args.incident_dir} "
              f"({engine.incidents.captured} captured, "
              f"{engine.incidents.write_errors} write error(s))",
              file=sys.stderr)
    if args.alert_log_out:
        with open(args.alert_log_out, "w") as fh:
            json.dump(engine.alerts.to_dict(), fh, indent=1,
                      allow_nan=False)
            fh.write("\n")
        print(f"[serve_bench] alert log: {args.alert_log_out} "
              f"({engine.alerts.fired} fired, "
              f"{engine.alerts.cleared} cleared)", file=sys.stderr)
    if trace is not None:
        trace.save(trace_path)
        print(f"[serve_bench] trace: {trace_path} ({len(trace)} events)",
              file=sys.stderr)
    if exporter is not None:
        exporter.close()
    print(json.dumps(stats, allow_nan=False))
    return 0


if __name__ == "__main__":
    sys.exit(main())
