"""Toy cells for CPU rehearsals of the drivers: the real ``BENCHMARK.json``
with a toy configuration and toy traffic from ``data/`` put in."""

import copy
import importlib.util
import json
import os
import sys

from benchmark import run as harness

HERE = os.path.dirname(os.path.abspath(__file__))
DATA = os.path.join(HERE, "data")
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
# toy readings (fp32 both sides): program 0 exactly; float8 control
# gap_max 5.5e-4 .. 6.7e-3, gap_mean 2.1e-5 .. 6.1e-4 over three seeds
SERVE_LIMITS = {"gap_max": 1e-4, "gap_mean": 5e-6, "wrong_length": 0}
TRAIN_LIMITS = {"loss2_gap": 1e-3,
                "grad_norm_gap": 1e-3, "change_norm_gap": 1e-3}
STANDS_FOR = {"toy-serve-batch": "gpt2l-serve-batch",
              "toy-serve-chat": "gpt2l-serve-chat",
              "toy-train": "gpt2s-train-t1024"}


def toy_bench(traffic: str, config: str = "toy-gpt2"):
    """(cell, bench): the toy cell reports what the real cell it stands
    for reports."""
    bench = copy.deepcopy(harness.load_benchmark())
    bench["configs"].append({"name": "toy",
                             "file": f"tests/benchmark/data/{config}.json"})
    cell = {"name": "toy." + traffic, "config": "toy", "traffic": traffic,
            "chips": 1, "why": "toy"}
    for m in bench["end_to_end"] + bench["per_layer"]:
        if STANDS_FOR[traffic] in m.get("workloads", []):
            m["workloads"].append(cell["name"])
    bench["workloads"].append(cell)
    return cell, bench


def toy_config(config: str = "toy-gpt2") -> dict:
    with open(os.path.join(DATA, f"{config}.json")) as fh:
        return json.load(fh)


def toy_family(monkeypatch) -> None:
    """Put ``data/toy_lm.py`` where the ``reference`` key ``toy_lm`` is
    looked for, as a family and as a reference, for this test."""
    spec = importlib.util.spec_from_file_location(
        "bm_toy_lm", os.path.join(DATA, "toy_lm.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    for package in ("families", "reference"):
        monkeypatch.setitem(sys.modules, f"benchmark.{package}.toy_lm", mod)


def run_toy(traffic: str, seed: int, seconds: float, limits: dict,
            trace: bool = False, config: str = "toy-gpt2") -> dict:
    cell, bench = toy_bench(traffic, config)
    return harness.run_cell(cell, bench, seed, seconds, trace, device=CPU,
                            limits=limits, traffic_dir=DATA)


def fake_trace(_path, _spans):
    """A device plane for CPU rehearsals of the traced path: the real
    profiler ran, but a CPU has no TPU operations line to read."""
    ops = [("fusion.1", 1e9 + i * 1e6, 6e5) for i in range(900)]
    ops += [("flash_fwd.2", 2e9, 3e7), ("all-reduce.3", 2.1e9, 1e7)]
    return {"devices": {"/device:TPU:0": ops},
            "host": [("bench.window", 1e9, 2e9),
                     ("engine.step", 1.0004e9, 5e5)]}
