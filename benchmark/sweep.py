"""Find the knee of an open-loop cell once, on the chip: a few arrival
rates in one process, each for ``--seconds``; the highest whose backlog
does not grow is the knee, and the cell's traffic file then fixes 4/5 of
it. Not part of a benchmark run.

    python benchmark/sweep.py --workload gpt2l-serve-chat --rates 2,3,4,5
"""

from __future__ import annotations

import argparse
import copy
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import run as harness  # noqa: E402
from benchmark import servestats, trafficgen  # noqa: E402
from benchmark.drivers import serve  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=20260930)
    args = ap.parse_args(argv)
    bench = harness.load_benchmark()
    cell = harness.find_cell(bench, args.workload)
    harness.enable_compile_cache()
    harness.require_chips(int(cell["chips"]))
    cfg = harness.load_config(cell["config"], bench)
    base = trafficgen.load(cell["traffic"])
    engine = serve.build_engine(cfg, base, args.seed)
    for rate in (float(r) for r in args.rates.split(",")):
        spec = copy.deepcopy(base)
        spec["arrival_rate_per_s"] = rate
        while not engine.idle:
            engine.step()
        s = serve.prepare(engine, cfg, spec, args.seed)
        ctx = harness.make_ctx(cell, cfg, spec, args.seed, args.seconds)
        w = serve.measure(ctx, s)
        its = w["iterations"]
        q = [it["queued"] for it in its]
        k = max(len(q) // 4, 1)
        done = sum(1 for u, i in s.finished_iteration.items()
                   if i >= s.preroll_iterations)
        print(json.dumps({
            "rate": rate, "iterations": len(its),
            "queued_first_quarter": sum(q[:k]) / k,
            "queued_last_quarter": sum(q[-k:]) / k,
            "finished_per_s": done / w["notes"]["window_s"],
            "tpot_p90_ms": w["end_to_end"]["tpot_p90_ms"],
            "ttft_p50_ms": servestats.percentile(w["ttft_ms"], 50),
            "ttft_p90_ms": servestats.percentile(w["ttft_ms"], 90),
            "live_mean": sum(it["live"] for it in its) / len(its),
            "iter_ms_p50": servestats.percentile(
                [(it["t1"] - it["t0"]) * 1e3 for it in its], 50),
            "late_ms_max": w["notes"]["generator_late_ms_max"]}), flush=True)
        s.engine.set_token_listener(None)
    return 0


if __name__ == "__main__":
    sys.exit(main())
