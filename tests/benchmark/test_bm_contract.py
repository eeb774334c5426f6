"""``BENCHMARK.json`` against the contract, and the files it names."""

import json
import os
import re
import subprocess
import sys

import pytest

from benchmark import workmodel

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
with open(os.path.join(ROOT, "BENCHMARK.json")) as _fh:
    BENCH = json.load(_fh)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]
CELLS = {w["name"]: w for w in BENCH["workloads"]}
E2E = {m["name"]: m for m in BENCH["end_to_end"]}


# Keys that `reduced` may never name, by what their names say: hidden,
# feed-forward, expert, latent, state and projection sizes, head sizes,
# ranks, experts per token, selection and window sizes. Keys that count what
# is held here (layers, experts, heads, rows of the vocabulary, prediction
# modules) may be cut. A family whose width keys do not say so lists them
# itself (``WIDTH_KEYS``).
WIDTH = re.compile(r"(_dim|_rank|_width|(?<!vocab)_size)$|^d_|topk|per_tok"
                   r"|window|state|expan|latent|proj")


def is_width(key: str, family) -> bool:
    return bool(WIDTH.search(key)) or key in family.WIDTH_KEYS


def _cells_reporting(metric: dict) -> set:
    return set(metric.get("workloads", CELLS))


def test_top_level_keys_and_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert isinstance(BENCH["run_seconds"], int)
    assert 1 <= BENCH["run_seconds"] <= 51
    assert 1 <= len(BENCH["workloads"]) <= 24
    assert len(json.dumps(BENCH)) < 64 * 1024
    assert BENCH["command"][-1].startswith(BENCH["paths"][0] + "/")
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(BENCH["workloads"]) // 4)
    assert {m["name"] for m in BENCH["end_to_end"]} <= {
        "train_tokens_per_s", "serve_tokens_per_s", "tpot_p90_ms", "setup_s"}
    assert E2E["setup_s"]["bound"] <= 0.1 and "workloads" not in E2E["setup_s"]


@pytest.mark.parametrize("m", METRICS, ids=lambda m: m["name"])
def test_metric_entry(m):
    per_layer = m in BENCH["per_layer"]
    allowed = {"name", "unit", "better", "source", "workloads"} | (
        {"layer", "moves"} if per_layer else {"bound"})
    assert set(m) <= allowed and allowed - {"workloads"} <= set(m)
    assert NAME.match(m["name"]) and UNIT.match(m["unit"])
    assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
    assert set(m.get("workloads", [])) <= set(CELLS)
    if per_layer:
        moved = E2E[m["moves"]]
        assert _cells_reporting(m) <= _cells_reporting(moved), \
            "a cell that reports this metric does not report what it moves"
        assert "\n" not in m["layer"] and 1 <= len(m["layer"]) <= 200
        if "roofline" in m["name"] or "mfu" in m["name"]:
            assert m["unit"] == "%"
        assert os.path.isfile(os.path.join(
            ROOT, "benchmark", "metrics", m["name"] + ".py"))
    else:
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")


def test_names_are_unique():
    for group in (METRICS, BENCH["workloads"], BENCH["configs"]):
        names = [x["name"] for x in group]
        assert len(names) == len(set(names))
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))


@pytest.mark.parametrize("w", BENCH["workloads"], ids=lambda w: w["name"])
def test_cell_entry_and_its_files(w):
    assert set(w) == {"name", "config", "traffic", "chips", "why"}
    assert all(NAME.match(w[k]) for k in ("name", "config", "traffic"))
    assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    assert w["config"] in {c["name"] for c in BENCH["configs"]}
    traffic = os.path.join(ROOT, "benchmark", "traffic",
                           w["traffic"] + ".json")
    with open(traffic) as fh:
        spec = json.load(fh)
    assert os.path.isfile(os.path.join(ROOT, "benchmark", "drivers",
                                       spec["driver"] + ".py"))
    with open(os.path.join(ROOT, "benchmark", "limits",
                           w["name"] + ".json")) as fh:
        limits = json.load(fh)["limits"]
    assert limits and all(v >= 0 for v in limits.values())
    reporting = [m["name"] for m in BENCH["end_to_end"]
                 if w["name"] in _cells_reporting(m)]
    assert "setup_s" in reporting and len(reporting) >= 2
    layer = [m["name"] for m in BENCH["per_layer"]
             if w["name"] in m.get("workloads", [])]
    for part in ("mfu.", "device_idle_share.", "hbm_peak_gb."):
        assert any(n.startswith(part) for n in layer), part


@pytest.mark.parametrize("c", BENCH["configs"], ids=lambda c: c["name"])
def test_config_entry_and_its_file(c):
    assert set(c) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(c["name"]) and c["source"].startswith("https://")
    assert c["file"].startswith("benchmark/configs/")
    assert any(w["config"] == c["name"] for w in BENCH["workloads"])
    with open(os.path.join(ROOT, c["file"])) as fh:
        cfg = json.load(fh)
    assert cfg["source"] == c["source"]
    for part in ("reference", "families"):
        assert os.path.isfile(os.path.join(
            ROOT, "benchmark", part, cfg["reference"] + ".py"))
    family = workmodel.family(cfg)
    family.validate(cfg)
    assert not [k for k in c["reduced"] if is_width(k, family)]


def _run(cwd, env_extra):
    env = {**os.environ, **env_extra}
    return subprocess.run(
        [sys.executable, os.path.join(cwd, "benchmark", "run.py"),
         "--workload", BENCH["workloads"][0]["name"], "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_result_without_a_tpu():
    out = _run(ROOT, {"JAX_PLATFORMS": "cpu"})
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "TPU" in out.stderr


def test_no_result_outside_a_checkout_of_the_program(tmp_path):
    import shutil

    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = _run(str(tmp_path), {"JAX_PLATFORMS": "cpu"})
    assert out.returncode != 0 and out.stdout.strip() == ""
