"""Continuous-batching serving CLI for TransformerLM checkpoints.

The consumer end of ``distributed_training_tpu/serving/``: reads one
prompt per line (stdin by default, or ``--prompts-file``), serves them
all through the continuous-batching engine — up to ``--max-batch``
sequences decode together, freed slots refill mid-flight — and prints
completions in submission order plus an SLA summary (TTFT/TPOT
percentiles, throughput, queue depth).

Model flags must mirror the training run so the checkpoint restores
(same contract as ``generate.py``); byte-level I/O (vocab 256 = one
token per byte) like the rest of the gpt/jax_tpu surface.

    echo -e "The \\nOnce upon" | python gpt/jax_tpu/serve.py \\
        -c ./checkpoint --max-batch 8 --max-new-tokens 64
"""

from __future__ import annotations

import argparse
import os
import sys

# Script-style backend dir (like tools/serve_bench.py): make the package
# importable when run from anywhere, not just the repo root.
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def add_argument() -> argparse.Namespace:
    from distributed_training_tpu.config import kv_page_size_arg

    parser = argparse.ArgumentParser(
        description="TransformerLM continuous-batching serving")
    parser.add_argument("--prompts-file", type=str, default=None,
                        help="one UTF-8 prompt per line; default: stdin")
    # Serving knobs (ServeConfig).
    parser.add_argument("--max-batch", type=int, default=8,
                        help="decode slots (sequences batched/iteration)")
    parser.add_argument("--max-len", type=int, default=None,
                        help="per-slot KV-cache tokens (prompt + output); "
                             "default: the model's --max-len table")
    parser.add_argument("--max-new-tokens", type=int, default=128)
    parser.add_argument("--temperature", type=float, default=0.0,
                        help="0 = greedy")
    parser.add_argument("--top-k", type=int, default=None)
    parser.add_argument("--top-p", type=float, default=None)
    parser.add_argument("--eos-id", type=int, default=None)
    parser.add_argument("--kv-page-size", type=kv_page_size_arg,
                        default=8,
                        help="paged KV cache (docs/SERVING.md): KV "
                             "memory is a pool of this-many-token pages "
                             "with per-slot page tables; pages allocate "
                             "as written, so admission gates on actual "
                             "footprint, not max-len")
    parser.add_argument("--kv-pages", type=int, default=None,
                        help="KV pool size in pages; default max_batch x "
                             "ceil(budget/page_size) = every slot's "
                             "full budget. Smaller oversubscribes: bursts "
                             "queue on pages instead of slots")
    parser.add_argument("--prefill-chunk", type=int, default=64,
                        help="chunked prefill: prompt "
                             "tokens prefilled per decode iteration, "
                             "riding the fused step so admission never "
                             "blocks decode")
    parser.add_argument("--prefix-cache",
                        action=argparse.BooleanOptionalAction,
                        default=False,
                        help="radix-tree prefix cache over the paged "
                             "pool (docs/SERVING.md 'Prefix caching'): "
                             "finished requests' KV page chains stay "
                             "indexed and a prompt sharing a "
                             "page-aligned prefix aliases them, "
                             "prefilling only the tail — shared system "
                             "prompts prefill once. Bitwise-neutral; "
                             "flushed at every hot-swap barrier")
    parser.add_argument("--prefix-cache-pages", type=int, default=None,
                        help="cap on pool pages the prefix-cache trie "
                             "may hold (LRU leaves evict past it); "
                             "default unbounded within the pool")
    # Speculative decoding (docs/SERVING.md "Speculative decoding").
    parser.add_argument("--spec-k", type=int, default=0,
                        help="speculative decoding: draft tokens "
                             "proposed per slot per iteration and "
                             "verified by the serving model in one "
                             "fixed-width dispatch; acceptance is "
                             "lossless (greedy output stays bitwise "
                             "identical to sequential decode, sampled "
                             "output distribution-identical). 0 = off")
    parser.add_argument("--spec-drafter", type=str, default="ngram",
                        choices=["ngram", "gpt"],
                        help="'ngram' = prompt-lookup drafter (zero "
                             "extra params); 'gpt' = greedy draft "
                             "model over a --spec-draft-window token "
                             "window, self-drafting with the serving "
                             "weights (hot-swap keeps it fresh). A "
                             "separately trained draft checkpoint "
                             "plugs in via the Engine API "
                             "(serving/speculative.py::GPTDrafter)")
    parser.add_argument("--spec-ngram", type=int, default=3,
                        help="longest context suffix the n-gram "
                             "drafter matches (backs off to 1)")
    parser.add_argument("--spec-draft-window", type=int, default=16,
                        help="gpt drafter: context tokens re-run per "
                             "draft step")
    # Quantized execution (docs/SERVING.md "Quantized execution").
    parser.add_argument("--quantize-weights", action="store_true",
                        default=False,
                        help="symmetric per-channel int8 for the "
                             "transformer matmul weights (embedding, "
                             "attention, MLP); layernorms, biases and "
                             "the logits head stay full precision. "
                             "Quantization happens ONCE at engine "
                             "construction and at hot-swap staging "
                             "time on the watcher thread — never "
                             "inside the decode loop. Deterministic: "
                             "two quantized runs are bitwise-identical")
    parser.add_argument("--kv-dtype", type=str, default=None,
                        choices=["int8"],
                        help="paged KV cache storage dtype: 'int8' "
                             "stores pool pages as int8 with per-row "
                             "per-head scales, quantizing on scatter "
                             "and dequantizing in the gather inside "
                             "the same compiled programs (inventory "
                             "stays at 2). Default: model dtype")
    # SLO tiers + multi-tenant fairness (docs/SERVING.md "Tiered
    # scheduling & preemption").
    parser.add_argument("--num-tiers", type=int, default=1,
                        help="SLO tiers: priority 0 = highest "
                             "(interactive); larger tiers are shed and "
                             "preempted first under overload. 1 = the "
                             "single-FIFO behavior")
    parser.add_argument("--priority", type=int, default=0,
                        help="SLO tier for this CLI's prompts (a "
                             "multi-tier deployment submits per-request "
                             "via Engine.submit(priority=, tenant=))")
    parser.add_argument("--tenant", type=str, default="default",
                        help="tenant principal for this CLI's prompts "
                             "(per-tenant quota + weighted-fair "
                             "admission)")
    parser.add_argument("--tenant-quota", type=int, default=None,
                        help="max concurrently seated requests per "
                             "tenant (None = uncapped)")
    parser.add_argument("--tier-reserved-slots", type=int, default=0,
                        help="decode slots held back from non-top "
                             "tiers so tier-0 arrivals always find "
                             "headroom")
    parser.add_argument("--tier-reserved-pages", type=int, default=0,
                        help="KV pool pages held back from non-top "
                             "tiers (paged engine)")
    parser.add_argument("--no-preempt", action="store_true",
                        default=False,
                        help="disable lossless preempt-and-requeue of "
                             "lower tiers (tiers then only order the "
                             "queue)")
    # Graceful degradation (resilience round; docs/RESILIENCE.md).
    parser.add_argument("--max-queue-depth", type=int, default=None,
                        help="bounded admission: beyond this depth the "
                             "newest queued lower-tier request is shed "
                             "to admit higher-tier work; the incoming "
                             "request itself is shed with a typed "
                             "QueueFullError when nothing lower-tier "
                             "is queued")
    parser.add_argument("--ttft-deadline-ms", type=float, default=None,
                        help="evict requests still queued past this "
                             "time-to-first-token deadline (finish "
                             "reason 'timeout')")
    parser.add_argument("--deadline-ms", type=float, default=None,
                        help="evict requests still decoding past this "
                             "total deadline (partial tokens returned, "
                             "finish reason 'timeout')")
    # Live weight hot-swap (docs/SERVING.md "Live weight hot-swap").
    parser.add_argument("--watch-ckpt-dir", type=str, default=None,
                        help="zero-drain continuous deployment: watch "
                             "this checkpoint directory and hot-swap "
                             "each newly COMMITTED epoch into the "
                             "running engine at a decode-iteration "
                             "boundary (verified staging; torn/corrupt "
                             "candidates are quarantined and never "
                             "touch the engine). SIGHUP triggers one "
                             "immediate poll; SIGUSR1 re-arms the "
                             "previously served weights (rollback)")
    parser.add_argument("--watch-interval", type=float, default=2.0,
                        help="seconds between checkpoint-watcher polls")
    # Crash-durable serving (serving/journal.py; docs/RESILIENCE.md
    # "Crash-durable serving").
    parser.add_argument("--journal-dir", type=str, default=None,
                        help="write-ahead request journal: accepted "
                             "requests are durable before submit "
                             "returns; on restart with the same flags "
                             "the log replays BEFORE serving — "
                             "finished results re-deliver exactly "
                             "once, unfinished requests resume and "
                             "complete bitwise-equal to the "
                             "uninterrupted run, and already-consumed "
                             "prompt lines are skipped")
    parser.add_argument("--journal-fsync", type=str, default="batch",
                        choices=["none", "batch", "always"],
                        help="journal durability: 'none' = OS page "
                             "cache (survives kill -9, not power "
                             "loss), 'batch' = one fsync per writer "
                             "flush, 'always' = fsync per record")
    parser.add_argument("--journal-segment-bytes", type=int,
                        default=1 << 20,
                        help="journal segment rotation threshold "
                             "(live state compacts into a fresh "
                             "segment past this; bounded growth)")
    parser.add_argument("--flight-dump", type=str, default=None,
                        help="write a flight-recorder JSON here at exit "
                             "(tools/flight_report.py renders it)")
    parser.add_argument("--ledger-out", type=str, default=None,
                        help="write each completed request's latency "
                             "ledger (serving/ledger.py) as one "
                             "strict-JSON list: per-request (cause, "
                             "start, end) intervals partitioning its "
                             "wall lifetime — queue wait, prefill, "
                             "decode, preemption requeue/recompute, "
                             "swap barriers, journal admission, "
                             "crash-recovery downtime — plus the "
                             "conservation verdict")
    parser.add_argument("--metrics-port", type=int, default=None,
                        help="live telemetry plane: /metrics (Prometheus "
                             "text, incl. TTFT/TPOT histograms + KV/slot "
                             "utilization), /healthz (serving/swapping/"
                             "draining/drained phase + weights_epoch and "
                             "swap counters), /vars, /timeseries and "
                             "/alerts, scrapeable while the engine "
                             "serves (loopback; 0 = ephemeral)")
    # Serving control room (serving/timeseries.py + serving/alerts.py;
    # docs/OBSERVABILITY.md "Serving SLO alerting & incident capture").
    parser.add_argument("--slo-rules", type=str, default=None,
                        help="SLO burn-rate alerting: 'default' for "
                             "the built-in rule set, or ';'-separated "
                             "name:metric[/den]>objective[@fast,slow]"
                             "[xburn][~clear] clauses "
                             "(serving/alerts.py); evaluated every "
                             "--sample-every iterations; off when "
                             "unset")
    parser.add_argument("--incident-dir", type=str, default=None,
                        help="write one atomic incident bundle per "
                             "alert fire (firing alert + alert log + "
                             "last time-series window + flight "
                             "snapshot) into this directory, off the "
                             "hot path (tools/incident_report.py "
                             "renders them); requires --slo-rules")
    parser.add_argument("--sample-every", type=int, default=16,
                        help="telemetry time-series sample cadence in "
                             "iterations (never wall time)")
    parser.add_argument("--trace", action=argparse.BooleanOptionalAction,
                        default=False,
                        help="span-level Perfetto trace: one track per "
                             "decode slot with each request's queued/"
                             "prefill/decode lifecycle (open in "
                             "ui.perfetto.dev or tools/trace_report.py)")
    parser.add_argument("--trace-dir", type=str, default="./trace",
                        help="trace output directory")
    parser.add_argument("--json", action="store_true", default=False,
                        help="emit the SLA stats as one JSON line")
    # Model flags (mirror training; generate.py contract).
    parser.add_argument("--vocab-size", type=int, default=256)
    parser.add_argument("--num-layers", type=int, default=4)
    parser.add_argument("--num-heads", type=int, default=4)
    parser.add_argument("--hidden-dim", type=int, default=256)
    parser.add_argument("--model-max-len", type=int, default=2048,
                        help="positional-table length used at training")
    parser.add_argument("--dtype", type=str, default="fp32",
                        choices=["bf16", "fp16", "fp32"])
    parser.add_argument("--head-bias", action=argparse.BooleanOptionalAction,
                        default=False)
    parser.add_argument("--logits-dtype", type=str, default="bf16",
                        choices=["fp32", "bf16"])
    # MoE model flags (must match training; generate.py contract — the
    # engine's vmapped decode runs MoE FFNs position-wise like training).
    parser.add_argument("--moe", action="store_true", default=False)
    parser.add_argument("--num-experts", type=int, nargs="+", default=[8])
    parser.add_argument("--moe-top-k", type=int, default=1)
    parser.add_argument("--min-capacity", type=int, default=0)
    parser.add_argument("--mlp-type", type=str, default="standard",
                        choices=["standard", "residual"])
    parser.add_argument("-c", "--checkpoint", type=str, default="./checkpoint")
    parser.add_argument("-r", "--resume", type=int, default=-1,
                        help="epoch to load; -1 = latest (random init if "
                             "no checkpoint exists)")
    parser.add_argument("--ema-decay", type=float, default=None,
                        help="must mirror training (restore-template tree)")
    parser.add_argument("--use-ema", action="store_true", default=False,
                        help="serve the EMA parameter average")
    parser.add_argument("--seed", type=int, default=0)
    return parser.parse_args()


def main() -> int:
    args = add_argument()

    import numpy as np

    from distributed_training_tpu.config import ServeConfig
    from distributed_training_tpu.inference.restore import (
        build_lm_and_restorer,
        moe_kwargs_from_flags,
    )
    from distributed_training_tpu.inference.sampler import CacheBudgetError
    from distributed_training_tpu.runtime.backend import (
        device_banner,
        enable_compile_cache,
    )
    from distributed_training_tpu.runtime.preemption import PreemptionGuard
    from distributed_training_tpu.serving import (
        DrainingError,
        Engine,
        HotSwapper,
        QueueFullError,
    )

    enable_compile_cache()
    moe_kwargs = moe_kwargs_from_flags(
        enabled=args.moe, num_experts=args.num_experts,
        top_k=args.moe_top_k, min_capacity=args.min_capacity,
        mlp_type=args.mlp_type)

    model, params, restored_epoch, restore_fn = build_lm_and_restorer(
        vocab_size=args.vocab_size,
        num_layers=args.num_layers,
        num_heads=args.num_heads,
        hidden_dim=args.hidden_dim,
        max_len=args.model_max_len,
        dtype=args.dtype,
        head_bias=args.head_bias,
        logits_dtype=args.logits_dtype,
        moe_kwargs=moe_kwargs,
        checkpoint=args.checkpoint,
        resume=args.resume,
        ema_decay=args.ema_decay,
        use_ema=args.use_ema,
        seed=args.seed,
        printer=lambda msg: print(f"[serve] {msg}", file=sys.stderr),
    )

    from distributed_training_tpu.observability.trace import (
        session_for_cli,
    )

    trace, trace_path = session_for_cli(args.trace, args.trace_dir,
                                        "serve")

    engine = Engine(model, params, ServeConfig(
        max_batch=args.max_batch,
        max_len=args.max_len,
        max_new_tokens=args.max_new_tokens,
        temperature=args.temperature,
        top_k=args.top_k,
        top_p=args.top_p,
        eos_id=args.eos_id,
        kv_page_size=args.kv_page_size,
        kv_pages=args.kv_pages,
        prefill_chunk=args.prefill_chunk,
        prefix_cache=args.prefix_cache,
        prefix_cache_pages=args.prefix_cache_pages,
        spec_k=args.spec_k,
        spec_drafter=args.spec_drafter,
        spec_ngram=args.spec_ngram,
        spec_draft_window=args.spec_draft_window,
        quantize_weights=args.quantize_weights,
        kv_dtype=args.kv_dtype,
        num_tiers=args.num_tiers,
        tenant_quota=args.tenant_quota,
        tier_reserved_slots=args.tier_reserved_slots,
        tier_reserved_pages=args.tier_reserved_pages,
        preempt=not args.no_preempt,
        max_queue_depth=args.max_queue_depth,
        ttft_deadline_ms=args.ttft_deadline_ms,
        deadline_ms=args.deadline_ms,
        journal_dir=args.journal_dir,
        journal_fsync=args.journal_fsync,
        journal_segment_bytes=args.journal_segment_bytes,
        sample_every=args.sample_every,
        slo_rules=args.slo_rules,
        incident_dir=args.incident_dir,
        seed=args.seed,
    ), trace=trace, weights_epoch=restored_epoch)

    # Zero-drain live weight hot-swap (docs/SERVING.md): a background
    # watcher streams newly COMMITTED epochs from --watch-ckpt-dir
    # through the resilience verification path into the running engine.
    # SIGHUP wakes the watcher for one immediate poll; SIGUSR1 asks the
    # watcher thread to re-arm the previously served weights (rollback).
    # Both handlers only set events — signal-safe: the rollback itself
    # takes the engine's swap lock, which the serving loop (this very
    # thread) holds around the barrier, so it must run on the watcher
    # thread, never on the signal frame.
    swapper = None
    if args.watch_ckpt_dir is not None:
        import signal as signal_mod

        watch_dir = args.watch_ckpt_dir
        swapper = HotSwapper(
            engine, watch_dir,
            lambda e: restore_fn(e, watch_dir),
            printer=lambda msg: print(msg, file=sys.stderr, flush=True))
        swapper.start(interval_s=args.watch_interval)
        if hasattr(signal_mod, "SIGHUP"):
            signal_mod.signal(signal_mod.SIGHUP,
                              lambda *_: swapper.trigger())
        if hasattr(signal_mod, "SIGUSR1"):
            signal_mod.signal(signal_mod.SIGUSR1,
                              lambda *_: swapper.request_rollback())
        print(f"[serve] hot-swap watcher on {watch_dir} "
              f"(every {args.watch_interval:g}s; SIGHUP = poll now, "
              f"SIGUSR1 = rollback)", file=sys.stderr, flush=True)

    # Live telemetry plane: scrape the engine while it serves. The
    # handler thread reads host-side telemetry the decode loop already
    # materialized (engine.flight_snapshot never flushes or syncs).
    exporter = None
    if args.metrics_port is not None:
        from distributed_training_tpu.observability.exporter import (
            attach_engine,
        )

        exporter = attach_engine(
            engine, args.metrics_port, component="serve",
            printer=lambda msg: print(msg, file=sys.stderr, flush=True))

    # Crash-durable serving: replay the write-ahead journal BEFORE the
    # prompt stream (the exporter is already up, so /healthz reads
    # 'recovering' while this runs). Finished-but-undelivered results
    # re-surface in the final report exactly once; unfinished requests
    # re-seat through the resume path and complete bitwise; the
    # journaled line cursor skips prompts this process already
    # consumed on a previous life.
    report = engine.recover()
    recovered = (report["redelivered"]
                 + report["completed_at_replay"])
    lines_consumed = int(report["notes"].get("lines_consumed", 0))
    if recovered or report["resumed"] or lines_consumed:
        print(f"[serve] journal recovery: {len(recovered)} "
              f"redelivered/expired, {report['resumed']} resumed; "
              f"skipping {lines_consumed} already-consumed prompt "
              f"line(s)", file=sys.stderr)

    if args.prompts_file:
        with open(args.prompts_file) as fh:
            lines = [ln.rstrip("\n") for ln in fh]
    else:
        lines = [ln.rstrip("\n") for ln in sys.stdin]
    lines = [ln for ln in lines if ln]
    if not lines and not (recovered or report["resumed"]):
        raise SystemExit("no prompts (stdin/--prompts-file was empty)")
    lines = lines[lines_consumed:]

    # Graceful drain: SIGTERM latches (PreemptionGuard); the submit loop
    # then closes admission — remaining prompts are rejected with the
    # typed DrainingError — and the engine completes every request it
    # already accepted before the SLA/flight dump is emitted. A second
    # SIGTERM re-raises through the previous handler ("now" semantics).
    texts: dict[int, str] = {}
    with PreemptionGuard() as guard:
        print(f"[serve] engine ready {device_banner()}", file=sys.stderr,
              flush=True)
        for text in lines:
            if guard.triggered:
                engine.queue.close()  # idempotent; typed rejects below
            if engine.journal is not None:
                # The line cursor persists BEFORE the line is acted on:
                # a crash inside this loop body drops a line that was
                # never durably accepted (at-most-once) — it never
                # duplicates one on restart.
                lines_consumed += 1
                # Enqueue-only: the admit below persists the same
                # ordered batch (one fsync per line, not two); a
                # skipped/rejected line's cursor rides the writer
                # thread's next flush.
                engine.journal.log_note(
                    {"lines_consumed": lines_consumed}, flush=False)
            tokens = np.frombuffer(text.encode("utf-8"), np.uint8)
            if (tokens >= args.vocab_size).any():
                print(f"[serve] SKIP (bytes outside vocab "
                      f"{args.vocab_size}): {text!r}", file=sys.stderr)
                continue
            try:
                req = engine.submit(tokens.astype(np.int32),
                                    priority=args.priority,
                                    tenant=args.tenant)
            except DrainingError as e:
                print(f"[serve] DRAINING, reject {text!r}: {e}",
                      file=sys.stderr)
                continue
            except (CacheBudgetError, QueueFullError) as e:
                print(f"[serve] REJECT {text!r}: {e}", file=sys.stderr)
                continue
            texts[req.uid] = text

        # One-shot CLI: no more submits are coming, so ending through
        # drain() is free for the normal path and makes the SIGTERM path
        # identical — close admission, finish in-flight, then report.
        # Journal recoveries (redelivered + completed-at-replay) join
        # the report: they are this process's deliveries too.
        done = recovered + engine.drain()
        # Drained: every page must be back in the pool (or held by the
        # prefix trie at exactly one reference) — a leak raises here
        # rather than exit 0 (serve_bench's discipline).
        engine.check_balanced()
        if guard.triggered:
            print(f"[serve] SIGTERM: drained {len(done)} in-flight "
                  f"request(s), admission closed", file=sys.stderr)
    if swapper is not None:
        swapper.close()
        print(f"[serve] hot-swap: {swapper.counters['armed']} armed / "
              f"{swapper.counters['rejected']} rejected over "
              f"{swapper.counters['polls']} polls; serving weights "
              f"epoch {engine.weights_epoch}", file=sys.stderr)

    def decode_bytes(toks):
        return bytes(int(t) % 256 for t in toks).decode(
            "utf-8", errors="replace")

    for fin in sorted(done, key=lambda f: f.uid):
        ttft = ("-" if fin.ttft_ms is None else f"{fin.ttft_ms:.1f} ms")
        # A recovered request's prompt text predates this process; its
        # byte tokens reconstruct it (vocab 256 = one token per byte).
        text = texts.get(fin.uid, decode_bytes(fin.prompt))
        print(f"[serve] #{fin.uid} ({fin.finish_reason}, "
              f"ttft {ttft}): "
              f"{text!r} -> {decode_bytes(fin.tokens)!r}")
    if engine.journal is not None:
        # Client cursor: the completions above are consumed — a future
        # recovery must not redeliver them, and compaction may drop
        # them.
        engine.journal.ack([f.uid for f in done])
        engine.journal.shutdown()

    stats = engine.stats()
    if args.json:
        import json

        print(json.dumps(stats, allow_nan=False))
    else:
        print(f"[serve] {stats['requests_finished']} requests, "
              f"{stats['tokens_emitted']} tokens, "
              f"{stats['throughput_tok_s']:.1f} tok/s | "
              f"ttft p50 {stats['ttft_p50_ms']:.1f} / "
              f"p95 {stats['ttft_p95_ms']:.1f} ms | "
              f"tpot p50 {stats['tpot_p50_ms']:.2f} / "
              f"p95 {stats['tpot_p95_ms']:.2f} ms | "
              f"queue depth max {stats['queue_depth_max']}",
              file=sys.stderr)
        if args.slo_rules:
            print(f"[serve] alerts: {stats['alerts_fired']} fired, "
                  f"{stats['alerts_cleared']} cleared, "
                  f"{stats['alerts_active']} active | "
                  f"incidents {stats['incidents_captured']}",
                  file=sys.stderr)
    if args.ledger_out:
        from distributed_training_tpu.serving.ledger import dump_ledgers

        n_rows, bad = dump_ledgers(args.ledger_out, done)
        print(f"[serve] latency ledgers: {args.ledger_out} "
              f"({n_rows} requests, {bad} conservation violation(s))",
              file=sys.stderr)
    if args.flight_dump:
        engine.dump_flight(args.flight_dump)
        print(f"[serve] flight record: {args.flight_dump}", file=sys.stderr)
    # Drain the incident writer so every captured bundle is on disk
    # before the process exits (same discipline as journal.shutdown).
    engine.close_incidents()
    if args.incident_dir and engine.incidents is not None:
        print(f"[serve] incidents: {args.incident_dir} "
              f"({engine.incidents.captured} captured, "
              f"{engine.incidents.write_errors} write error(s))",
              file=sys.stderr)
    if trace is not None:
        trace.save(trace_path)
        print(f"[serve] trace: {trace_path} ({len(trace)} events)",
              file=sys.stderr)
    if exporter is not None:
        exporter.close()  # daemon thread; close just frees the port early
    return 0


if __name__ == "__main__":
    sys.exit(main())
