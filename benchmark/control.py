"""Readings for the limits of ``correct``: the program's own gaps and the
control's, on several seeds in one process (set-up is long). Run on the
chip at the cell's own size; the benchmark's own runs never run this.

    python benchmark/control.py --workload <cell> --seeds 1,2,3 --seconds 40

Prints one JSON line per seed: ``{"seed", "program": {name: value},
"control": {...}}``. ``--seconds 0`` skips the window (training cells need
none: their readings come from set-up).
"""

from __future__ import annotations

import argparse
import gc
import importlib
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import run as harness  # noqa: E402
from benchmark import trafficgen  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=0.0)
    ap.add_argument("--no-program", action="store_true",
                    help="training cells: the control alone, on the batches "
                         "the seed gives, without building the trainer")
    args = ap.parse_args(argv)
    bench = harness.load_benchmark()
    cell = harness.find_cell(bench, args.workload)
    harness.enable_compile_cache()
    harness.require_chips(int(cell["chips"]))
    cfg = harness.load_config(cell["config"], bench)
    spec = trafficgen.load(cell["traffic"])
    driver = importlib.import_module(f"benchmark.drivers.{spec['driver']}")
    for seed in (int(s) for s in args.seeds.split(",")):
        ctx = harness.make_ctx(cell, cfg, spec, seed, args.seconds)
        if args.no_program:
            held = driver.seed_batches(ctx)
            program = {}
        else:
            session = driver.setup(ctx)
            if args.seconds > 0:
                driver.measure(ctx, session)
            held = driver.release(ctx, session)
            del session
            gc.collect()
            program = dict(driver.check(ctx, held))
        out = {"seed": seed, "program": program,
               "control": driver.control(ctx, held),
               "compared": held.get("compared_tokens")}
        print(json.dumps(out), flush=True)
        del held
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
