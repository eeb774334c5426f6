"""Run one cell once: ``python benchmark/run.py --workload <name> --seed <n>
--seconds <s> --trace <0|1>``.

The last line of standard output is the result object. Without a TPU, or
with fewer chips than the cell asks for, or outside a checkout of the
program, it exits non-zero and prints no result.
"""

from __future__ import annotations

import argparse
import gc
import importlib
import importlib.util
import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

WORK_DIR = os.path.join(ROOT, ".bench_work")       # git-ignored scratch
DEFAULT_CACHE_DIR = os.path.join(ROOT, ".jax_cache")  # fixed: part of the key
# The traced run traces the last seconds of its window (at most half of it);
# rates that per-layer metrics read come from the part before.
TRACE_SECONDS = 6.0


def process_age_s() -> float:
    """Seconds since this process started (interpreter and imports too)."""
    with open("/proc/self/stat") as fh:
        start_ticks = int(fh.read().rsplit(")", 1)[1].split()[19])
    with open("/proc/uptime") as fh:
        uptime = float(fh.read().split()[0])
    return uptime - start_ticks / os.sysconf("SC_CLK_TCK")


def load_benchmark() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        return json.load(fh)


def find_cell(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit(f"no workload {name!r} in BENCHMARK.json")


def load_config(name: str, bench: dict) -> dict:
    entry = next(c for c in bench["configs"] if c["name"] == name)
    with open(os.path.join(ROOT, entry["file"])) as fh:
        return json.load(fh)


def load_limits(cell: str) -> dict:
    """The cell's limits, one per number compared (``limits/<cell>.json``)."""
    with open(os.path.join(HERE, "limits", f"{cell}.json")) as fh:
        return json.load(fh)["limits"]


def load_reader(metric: str):
    """The reader of one per-layer metric: ``metrics/<metric>.py::read``."""
    path = os.path.join(HERE, "metrics", f"{metric}.py")
    spec = importlib.util.spec_from_file_location(
        "benchmark_metric_" + metric.replace(".", "_").replace("-", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def enable_compile_cache() -> str:
    """JAX's persistent cache at ``$JAX_COMPILATION_CACHE_DIR`` if set, else
    at a fixed path in the checkout, keeping every program however small
    or quick to compile. Call before the first compile."""
    import jax

    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not placed:
        jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return placed or DEFAULT_CACHE_DIR


class SetupCounters:
    """Compile seconds and persistent-cache hits, from JAX's own events."""

    def __init__(self):
        from jax import monitoring

        self.compile_s = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        self.frozen: dict | None = None
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event: str, seconds: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += seconds

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def snapshot(self) -> dict:
        return {"compile_s": self.compile_s, "cache_hits": self.cache_hits,
                "cache_misses": self.cache_misses}


def require_chips(chips: int) -> dict:
    """Exit non-zero unless JAX gives a TPU with at least ``chips`` chips."""
    import jax

    devices = jax.devices()
    d = {"platform": devices[0].platform, "kind": devices[0].device_kind,
         "count": len(devices)}
    if d["platform"] != "tpu" or d["count"] < chips:
        raise SystemExit(
            f"benchmark: the cell needs {chips} TPU chip(s); JAX gave "
            f"{d['count']} x {d['platform']} ({d['kind']}); nothing measured")
    return d


def memory_peak_bytes() -> int:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()]
    return int(max(peaks))


def make_ctx(cell: dict, cfg: dict, spec: dict, seed: int, seconds: float,
             trace: bool = False) -> dict:
    """What a driver is told about the run it makes."""
    return {"cell": cell, "config": cfg, "traffic": spec, "seed": int(seed),
            "seconds": float(seconds), "trace": bool(trace),
            "chips": int(cell["chips"]), "work_dir": WORK_DIR,
            "trace_seconds": min(TRACE_SECONDS, float(seconds) / 2),
            "trace_dir": os.path.join(WORK_DIR, "trace", cell["name"])}


def run_cell(cell: dict, bench: dict, seed: int, seconds: float, trace: bool,
             *, device: dict, counters: SetupCounters | None = None,
             limits: dict | None = None,
             traffic_dir: str | None = None) -> dict:
    """Everything of a run after the look for a chip; returns the result
    object. Tests call this on the CPU at a toy size."""
    from benchmark import peaks as peaks_mod
    from benchmark import (spanreaders, tracereduce, tracing, trafficgen,
                           workmodel)

    cfg = load_config(cell["config"], bench)
    workmodel.family(cfg).validate(cfg)
    spec = trafficgen.load(cell["traffic"], traffic_dir)
    driver = importlib.import_module(f"benchmark.drivers.{spec['driver']}")
    if limits is None:
        limits = load_limits(cell["name"])
    ctx = make_ctx(cell, cfg, spec, seed, seconds, trace)
    os.makedirs(WORK_DIR, exist_ok=True)
    stages = {"start": process_age_s()}
    ctx["mark"] = lambda name: stages.__setitem__(name, process_age_s())

    session = driver.setup(ctx)
    setup_s = process_age_s()
    # now, before the window's spans can turn the program's ring over
    init_s = spanreaders.constructor_s(device)
    setup_counts = counters.snapshot() if counters else {}
    window = driver.measure(ctx, session)
    compiled_in_window = ((counters.snapshot()["compile_s"]
                           - setup_counts["compile_s"]) if counters else 0.0)
    peak = memory_peak_bytes()
    reduced = None
    if trace:
        reduced = tracereduce.reduce(tracereduce.load(
            tracereduce.find_xplane(ctx["trace_dir"]),
            tracing.SPANS | {tracereduce.WINDOW_SPAN}))
        shutil.rmtree(ctx["trace_dir"], ignore_errors=True)
    held = driver.release(ctx, session)
    del session
    gc.collect()
    checks = driver.check(ctx, held)     # [(name, value)]

    compared = []
    for name, value in checks:
        if name not in limits:
            raise SystemExit(f"no limit for {name!r} in limits/"
                             f"{cell['name']}.json")
        compared.append({"name": name, "value": value,
                         "limit": limits[name]})
    correct = all(c["value"] <= c["limit"] for c in compared)

    end_to_end = dict(window["end_to_end"])
    end_to_end["setup_s"] = setup_s
    units = {m["name"]: m["unit"]
             for m in bench["end_to_end"] + bench["per_layer"]}
    dev = dict(device)
    dev["memory_peak_bytes"] = peak
    if not trace:
        wanted = [m["name"] for m in bench["end_to_end"]
                  if cell["name"] in m.get("workloads", [cell["name"]])]
        values = {n: end_to_end.get(n) for n in wanted}
    else:
        reported = set(end_to_end)
        rctx = {**ctx, "window": window, "trace_reduced": reduced,
                "setup": setup_counts, "init_s": init_s,
                "memory_peak_bytes": peak,
                "peaks": peaks_mod.peaks_for(device["kind"]),
                "device": device, "end_to_end": end_to_end}
        values = {}
        for m in bench["per_layer"]:
            cells = m.get("workloads")
            if (cell["name"] not in cells if cells is not None
                    else m["moves"] not in reported):
                continue
            values[m["name"]] = load_reader(m["name"])(rctx)
        dev["busy_s"] = reduced["busy_s"]
        dev["window_s"] = reduced["window_s"]
    metrics = {n: {"value": v, "unit": units[n]}
               for n, v in values.items() if v is not None}
    result = {"correct": bool(correct), "attempted": window["attempted"],
              "failed": window["failed"], "metrics": metrics, "device": dev}
    if trace:
        result["breakdown"] = reduced["breakdown"]
    result["notes"] = {**window.get("notes", {}),
                       "compile_s_in_window": compiled_in_window,
                       "setup_stages_s": stages,
                       "check_s": held.get("check_s"),
                       "check_detail": held.get("worst_leaves")}
    result["checks"] = compared
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = load_benchmark()
    cell = find_cell(bench, args.workload)
    if not os.path.isdir(os.path.join(ROOT, "distributed_training_tpu")):
        raise SystemExit("benchmark: no program in this directory "
                         "(distributed_training_tpu/ is missing)")
    enable_compile_cache()
    counters = SetupCounters()
    device = require_chips(int(cell["chips"]))
    result = run_cell(cell, bench, args.seed, args.seconds, bool(args.trace),
                      device=device, counters=counters)
    for c in result["checks"]:
        print(f"check {c['name']}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
