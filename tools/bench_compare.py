#!/usr/bin/env python
"""Bench regression gate: diff two bench JSON files, fail on regression.

The repo's throughput story used to be asserted by eyeballing one bench
record against the last; this turns it into an automated gate. Give it a committed
baseline and a fresh run — ``bench.py`` JSON lines, a ``serve_bench.py``
SLA line, or the driver's BENCH wrapper object — and it compares the
metrics both sides share against per-metric thresholds, prints one line
per metric, and exits non-zero when any regresses:

    python tools/bench_compare.py profiles/serve_smoke_baseline.json \\
        /tmp/serve_now.json --metric throughput_tok_s=0.5:higher

Direction matters: throughput regresses DOWN, latency regresses UP,
and a workload-deterministic counter (the KV utilization accounting)
regresses in EITHER direction — ``both`` gates the absolute change. A
built-in table covers the repo's known metric families (override or
extend with ``--metric KEY=FRAC[:higher|lower|both]``); unknown numeric
keys are ignored unless explicitly requested, so adding a telemetry
field never breaks the gate. ``FRAC`` is the tolerated fractional
change (0.5 = current may be up to 50% worse than baseline before the
gate trips). A zero/absent baseline value skips that metric (no
signal, not a failure).

Input formats accepted per file:
- one JSON object (serve_bench's SLA line saved via ``tail -n 1``);
- JSON lines (bare ``python bench.py`` emits image AND LM lines) —
  records pair up by their ``metric`` name field, else by position;
- the driver's BENCH wrapper ``{"parsed": {...}}``.

Exit codes mirror flight_report.py: 0 ok, 1 regression, 2 malformed
input. ``--json`` emits the full comparison as one machine-readable
object (last line of stdout).
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import Any

# (direction, tolerated fractional change). Generous by design: the
# gate exists to catch order-of-magnitude cliffs and dropped requests
# on shared CI hardware, not 5% jitter — tighten per-call with
# --metric for controlled A/B hardware.
DEFAULT_METRICS: dict[str, tuple[str, float]] = {
    # bench.py image/LM lines
    "value": ("higher", 0.25),
    # serve_bench SLA line: capacity
    "throughput_tok_s": ("higher", 0.50),
    # latency tails (sample + fixed-bucket views)
    "ttft_p50_ms": ("lower", 3.0),
    "ttft_p95_ms": ("lower", 3.0),
    "tpot_p50_ms": ("lower", 3.0),
    "tpot_p95_ms": ("lower", 3.0),
    "ttft_hist_p50_ms": ("lower", 3.0),
    "ttft_hist_p95_ms": ("lower", 3.0),
    "ttft_hist_p99_ms": ("lower", 3.0),
    "tpot_hist_p50_ms": ("lower", 3.0),
    "tpot_hist_p95_ms": ("lower", 3.0),
    "tpot_hist_p99_ms": ("lower", 3.0),
    "queue_wait_p95_ms": ("lower", 3.0),
    "prefill_p95_ms": ("lower", 3.0),
    # correctness-shaped counters: any drop is a dropped request
    "requests_finished": ("higher", 0.0),
    "tokens_emitted": ("higher", 0.0),
    # utilization accounting is workload-deterministic (per-slot sums,
    # batch-composition-independent): ANY drift is accounting breakage,
    # not noise — the paged-KV rewrite changed it legitimately and
    # refreshed the baseline, which is the point of a gate
    "kv_reserved_vs_written": ("both", 0.05),
    # paged-KV pool accounting: allocated page-iterations are the same
    # per-request-deterministic sums in page units (zero-drift like the
    # token counters); pool occupancy divides by the iteration count,
    # which breathes with host timing — gate it loosely, both ways
    "kv_pages_allocated_iters": ("both", 0.0),
    "page_pool_occupancy_mean": ("both", 0.75),
    # live weight hot-swap (serving/hotswap.py): the smoke's mid-run
    # swap mode makes swaps_completed deterministic (exactly the
    # configured swap count), and ANY rejected swap in a clean smoke is
    # a broken staging pipeline — zero tolerance, enforced even from a
    # zero baseline (see compare()).
    "swaps_completed": ("both", 0.0),
    "swaps_rejected": ("lower", 0.0),
    # speculative decoding (serving/speculative.py): drafts and accepts
    # are pure functions of each request's own token stream (never of
    # batch neighbors or host timing), so both counters are zero-drift
    # workload-deterministic like the KV accounting; acceptance-rate
    # falling is the drafter getting worse — a real regression even
    # when throughput jitter hides it
    "drafted_tokens": ("both", 0.0),
    "accepted_tokens": ("both", 0.0),
    "spec_acceptance_rate": ("higher", 0.25),
    # tokens landed per decode dispatch — the deterministic speculation
    # speedup factor (derived from the zero-drift counters, so it only
    # moves when the accept economics really change)
    "spec_tokens_per_dispatch": ("higher", 0.05),
    # SLO-tiered scheduling (docs/SERVING.md "Tiered scheduling &
    # preemption"): under the bench's --virtual-dt drive the whole
    # admission/preempt/shed schedule is a pure function of the seeded
    # scenario, so these counters are zero-drift workload-deterministic
    # — ANY movement is a scheduling-policy change, not noise. In a
    # clean (single-tier) smoke all of them are zero, and the
    # zero-baseline zero-tolerance semantics keep growth from hiding.
    "requests_preempted": ("both", 0.0),
    "preempted_token_recompute": ("both", 0.0),
    "requests_preempt_timed_out": ("lower", 0.0),
    "requests_shed": ("both", 0.0),
    "tier0_requests_shed": ("lower", 0.0),
    "tier0_requests_finished": ("both", 0.0),
    "tier1_requests_shed": ("both", 0.0),
    "tier1_requests_finished": ("both", 0.0),
    # high-tier latency SLO (wall-clock: cliff thresholds only)
    "tier0_ttft_hist_p99_ms": ("lower", 3.0),
    "tier0_tpot_hist_p95_ms": ("lower", 3.0),
    # latency ledger (serving/ledger.py): conservation is a structural
    # invariant — ONE finished request whose intervals fail to tile its
    # lifetime is an attribution bug, so the violation counter is
    # zero-tolerance from any baseline; the per-cause token counters
    # are pure functions of each request's own token stream and the
    # deterministic schedule (the per-request twins of tokens_emitted /
    # preempted_token_recompute / drafted-accepted), so ANY drift is
    # accounting breakage, not noise
    "ledger_conservation_violations": ("both", 0.0),
    "ledger_tokens_prefill": ("both", 0.0),
    "ledger_tokens_decode": ("both", 0.0),
    "ledger_tokens_recompute": ("both", 0.0),
    "ledger_tokens_spec_draft": ("both", 0.0),
    "ledger_tokens_spec_accept": ("both", 0.0),
    # radix-tree prefix cache (serving/prefix_cache.py): under the
    # bench's --virtual-dt drive the trie's state is a pure function of
    # the seeded completion order, so the reuse counters are zero-drift
    # workload-deterministic like the scheduling counters. hit_tokens
    # is prefill compute SAVED — falling means the cache stopped
    # hitting (a keying or eviction regression) even when wall numbers
    # hide it; the page-churn counters gate bitwise. All exactly zero
    # on prefix-cache-off rows (zero-baseline semantics keep growth
    # from hiding there).
    "prefix_cache_hit_tokens": ("higher", 0.0),
    "prefix_cache_hit_requests": ("both", 0.0),
    "prefix_cache_inserted_pages": ("both", 0.0),
    "prefix_cache_evicted_pages": ("both", 0.0),
    "ledger_tokens_prefix_hit": ("both", 0.0),
    # quantized execution (serving/quantize.py; docs/SERVING.md
    # "Quantized execution"): kv_bytes_per_token is a pure function of
    # the engine config (cache geometry + storage dtype) and
    # quantized_params_bytes of the parameter tree — both are
    # zero-drift: ANY movement is a cache-layout or quantization-
    # coverage change, not noise. Exactly zero params-bytes on
    # quantization-off rows (zero-baseline semantics). weight_quant_s
    # is wall time and deliberately NOT gated.
    "kv_bytes_per_token": ("both", 0.0),
    "quantized_params_bytes": ("both", 0.0),
    # crash-durable serving (serving/journal.py): recovery counters are
    # pure functions of the journal's durable state — on the no-crash
    # smoke rows BOTH must stay exactly zero (any drift means requests
    # were resurrected or recomputed in a run with no crash), and the
    # CI crash drill separately pins them bitwise-equal across two
    # kill/restart cycles
    "requests_recovered": ("both", 0.0),
    "tokens_recomputed_on_recovery": ("both", 0.0),
    # serving control room (serving/alerts.py): on every baseline row
    # the bench runs with no SLO rules configured, so all three
    # counters are exactly zero — and the zero-baseline zero-tolerance
    # semantics turn ANY fired alert or captured incident in a clean
    # smoke into a gate failure (false-positive rate pinned at 0). The
    # CI alert drill separately proves the rules DO fire (bitwise) on
    # the degrading scenario.
    "alerts_fired": ("both", 0.0),
    "alerts_cleared": ("both", 0.0),
    "incidents_captured": ("both", 0.0),
    # network front door (serving/router.py; docs/SERVING.md "Network
    # front door & routing"): the network smoke's sequential seeded
    # client makes routing deterministic — each decision is a pure
    # function of the replicas' trie state, which is itself a pure
    # function of the request order — so all three counters are
    # zero-drift. On single-replica (non-network) rows every one is
    # exactly zero and the zero-baseline zero-tolerance semantics keep
    # stray routing from hiding there.
    "router_requests_routed": ("both", 0.0),
    "router_prefix_routed": ("both", 0.0),
    "router_fallback_routed": ("both", 0.0),
    # Fleet fault tolerance (serving/supervisor.py + the router's
    # circuit breakers; docs/RESILIENCE.md "Fleet fault tolerance"):
    # on every no-fault row all four are exactly zero — the
    # zero-baseline zero-tolerance semantics turn any spurious
    # restart, breaker trip, cancel, or failover on a healthy run
    # into a regression. Chaos drills pin their nonzero values
    # bitwise in CI instead of here.
    "replica_restarts": ("both", 0.0),
    "breaker_opens": ("both", 0.0),
    "requests_cancelled": ("both", 0.0),
    "failover_resumes": ("both", 0.0),
    # Federated telemetry plane (serving/router.py fleet ledger;
    # docs/OBSERVABILITY.md "Fleet tracing & federated metrics"): the
    # door's per-request fleet ledger is conserved by the same
    # telescoping-cursor construction as the engine ledger, and the
    # cross-hop audit (door intervals tile the client wall time;
    # replica lifetime fits inside the relay span) is structural — ONE
    # violating request is an attribution bug, zero-tolerance from any
    # baseline. The request count is workload-deterministic on
    # network rows and exactly zero on single-process rows.
    "fleet_ledger_requests": ("both", 0.0),
    "fleet_ledger_conservation_violations": ("both", 0.0),
}


def parse_metric_spec(spec: str) -> tuple[str, str, float]:
    """``KEY=FRAC[:higher|lower|both]`` → (key, direction, frac)."""
    key, _, rest = spec.partition("=")
    if not key or not rest:
        raise ValueError(f"bad --metric spec {spec!r} "
                         f"(want KEY=FRAC[:higher|lower|both])")
    frac_s, _, direction = rest.partition(":")
    direction = direction or DEFAULT_METRICS.get(key, ("higher",))[0]
    if direction not in ("higher", "lower", "both"):
        raise ValueError(f"bad direction {direction!r} in {spec!r} "
                         f"(higher | lower | both)")
    frac = float(frac_s)
    if frac < 0:
        raise ValueError(f"threshold must be >= 0 in {spec!r}")
    return key, direction, frac


def load_records(path: str) -> list[dict[str, Any]]:
    """Bench records from one file (see module docstring for formats)."""
    with open(path) as fh:
        text = fh.read()
    try:
        obj = json.loads(text)
    except json.JSONDecodeError:
        obj = None
    if isinstance(obj, dict):
        if isinstance(obj.get("parsed"), dict):  # driver BENCH wrapper
            return [obj["parsed"]]
        return [obj]
    if isinstance(obj, list):
        recs = [r for r in obj if isinstance(r, dict)]
        if recs:
            return recs
        raise ValueError(f"{path}: JSON array holds no objects")
    # JSON-lines: keep every line that parses to an object.
    recs = []
    for line in text.splitlines():
        line = line.strip()
        if not line:
            continue
        try:
            rec = json.loads(line)
        except json.JSONDecodeError:
            continue  # human log lines interleave with the JSON contract
        if isinstance(rec, dict):
            recs.append(rec)
    if not recs:
        raise ValueError(f"{path}: no JSON object found "
                         f"(not a bench/serve_bench output?)")
    return recs


def pair_records(base: list[dict], cur: list[dict]
                 ) -> list[tuple[str, dict, dict]]:
    """Match records across the two files: by ``metric`` name when both
    sides carry one (bench.py multi-line output), positionally
    otherwise. Unmatched records are skipped — a baseline missing a
    workload is no signal either way."""
    if all("metric" in r for r in base) and all("metric" in r for r in cur):
        cur_by_name = {r["metric"]: r for r in cur}
        return [(r["metric"], r, cur_by_name[r["metric"]])
                for r in base if r["metric"] in cur_by_name]
    n = min(len(base), len(cur))
    return [(f"record[{i}]", base[i], cur[i]) for i in range(n)]


def compare(base: dict, cur: dict,
            metrics: dict[str, tuple[str, float]]) -> list[dict[str, Any]]:
    """Per-metric verdicts for one record pair."""
    out = []
    for key, (direction, frac) in metrics.items():
        b, c = base.get(key), cur.get(key)
        if not isinstance(b, (int, float)) or isinstance(b, bool):
            continue  # metric absent from the baseline: nothing to gate
        if not isinstance(c, (int, float)) or isinstance(c, bool):
            out.append({"metric": key, "status": "MISSING",
                        "baseline": b, "current": None})
            continue
        if b == 0:
            # No ratio exists, so fractional thresholds cannot gate —
            # EXCEPT a zero-tolerance not-allowed-to-grow metric (e.g.
            # swaps_rejected), where "baseline 0, current nonzero" is
            # precisely the drift the gate exists to catch.
            if frac == 0.0 and direction in ("lower", "both") and c != 0:
                out.append({"metric": key, "direction": direction,
                            "threshold": frac, "baseline": 0.0,
                            "current": c, "change": None,
                            "status": "REGRESSION",
                            "note": "zero-tolerance metric grew from a "
                                    "zero baseline"})
            else:
                out.append({"metric": key, "status": "skipped",
                            "baseline": 0.0, "current": c,
                            "note": "zero baseline, no ratio"})
            continue
        change = (c - b) / abs(b)
        if direction == "higher":
            regressed = c < b * (1.0 - frac)
        elif direction == "lower":
            regressed = c > b * (1.0 + frac)
        else:  # both: absolute drift beyond the allowance regresses
            regressed = abs(change) > frac
        out.append({
            "metric": key, "direction": direction, "threshold": frac,
            "baseline": b, "current": c, "change": change,
            "status": "REGRESSION" if regressed else "ok",
        })
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="diff two bench JSON files; exit 1 on regression")
    ap.add_argument("baseline", help="committed baseline JSON "
                                     "(bench/serve_bench output)")
    ap.add_argument("current", help="fresh run to gate")
    ap.add_argument("--metric", action="append", default=[],
                    metavar="KEY=FRAC[:higher|lower|both]",
                    help="override/extend the built-in threshold table "
                         "(repeatable). FRAC = tolerated fractional "
                         "change, e.g. 0.5 = 50%% worse allowed")
    ap.add_argument("--only", type=str, default=None,
                    help="comma-separated metric keys: gate just these")
    ap.add_argument("--json", action="store_true", default=False,
                    help="emit the comparison as one JSON object")
    args = ap.parse_args(argv)

    metrics = dict(DEFAULT_METRICS)
    try:
        for spec in args.metric:
            key, direction, frac = parse_metric_spec(spec)
            metrics[key] = (direction, frac)
        if args.only:
            keep = {k.strip() for k in args.only.split(",") if k.strip()}
            unknown = keep - set(metrics)
            if unknown:
                raise ValueError(
                    f"--only names unknown metrics {sorted(unknown)} "
                    f"(add them via --metric KEY=FRAC[:dir])")
            metrics = {k: v for k, v in metrics.items() if k in keep}
        base = load_records(args.baseline)
        cur = load_records(args.current)
    except (OSError, ValueError) as e:
        print(f"bench_compare: error: {e}", file=sys.stderr)
        return 2

    pairs = pair_records(base, cur)
    if not pairs:
        print("bench_compare: error: no comparable records between "
              f"{args.baseline} and {args.current}", file=sys.stderr)
        return 2

    results = []
    failed = False
    for label, b, c in pairs:
        verdicts = compare(b, c, metrics)
        results.append({"record": label, "comparisons": verdicts})
        for v in verdicts:
            bad = v["status"] in ("REGRESSION", "MISSING")
            failed = failed or bad
            if args.json:
                continue
            if v["status"] == "MISSING":
                print(f"MISSING     {label} :: {v['metric']}: baseline "
                      f"{v['baseline']:g}, absent from current run")
            elif v["status"] == "skipped":
                print(f"skipped     {label} :: {v['metric']}: "
                      f"{v['note']}")
            else:
                arrow = {"higher": "↑", "lower": "↓",
                         "both": "↕"}[v["direction"]]
                change = ("" if v.get("change") is None
                          else f" ({v['change']:+.1%})")
                print(f"{v['status']:<11} {label} :: {v['metric']} "
                      f"[{arrow} ok within {v['threshold']:.0%}]: "
                      f"{v['baseline']:g} -> {v['current']:g}"
                      f"{change}")
    if args.json:
        print(json.dumps({"regressed": failed, "records": results},
                         allow_nan=False))
    elif failed:
        print("bench_compare: REGRESSION (see lines above)",
              file=sys.stderr)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
