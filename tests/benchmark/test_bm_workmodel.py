"""FLOP and byte functions against hand sums, and the table of peaks."""

import json
import os

import pytest

from benchmark import peaks, workmodel

CONFIGS = os.path.join(os.path.dirname(workmodel.__file__), "configs")


def _cfg(name):
    with open(os.path.join(CONFIGS, f"{name}.json")) as fh:
        return json.load(fh)


@pytest.mark.parametrize("name,layers,d,heads", [
    ("gpt2-small", 12, 768, 12), ("gpt2-large", 36, 1280, 20)])
def test_parameters_and_flops_by_hand(name, layers, d, heads):
    cfg = _cfg(name)
    counts = workmodel.family(cfg)
    rows, pos = 50304, 1024
    per_layer = 12 * d * d + 13 * d      # 4 matrices' worth + biases, norms
    want = layers * per_layer + 2 * rows * d + pos * d + 2 * d
    assert counts.param_count(cfg) == want == cfg["parameters"]
    assert counts.matmul_params_read(cfg) == want - rows * d - pos * d
    keys = 100
    fwd = layers * (24 * d * d + 4 * d * keys) + 2 * d * rows
    assert counts.forward_flops_token(cfg, keys) == fwd
    t = 1024
    mean = layers * (24 * d * d + 4 * d * (t + 1) / 2) + 2 * d * rows
    assert counts.train_flops_token(cfg, t) == pytest.approx(3 * mean)
    assert counts.prompt_forward_flops(cfg, 64) == pytest.approx(
        64 * (layers * (24 * d * d + 4 * d * 32.5) + 2 * d * rows))
    assert counts.dims(cfg)["head_dim"] == 64 == d // heads


@pytest.mark.parametrize("name", ["gpt2-small", "gpt2-large"])
def test_decode_bytes_count_live_rows_not_the_pool(name):
    cfg = _cfg(name)
    counts = workmodel.family(cfg)
    s = counts.dims(cfg)
    none = counts.decode_iteration_bytes(cfg, [])
    assert none == 2 * counts.matmul_params_read(cfg)
    some = counts.decode_iteration_bytes(cfg, [700, 250, 50])
    assert some - none == 1000 * 2 * s["layers"] * s["d"] * 2


def test_causal_attention_counts_the_causal_half():
    c = workmodel.causal_attention_call(batch=16, heads=12, seq=1024,
                                        head_dim=64)
    pairs = 16 * 12 * 1024 * 1025 / 2
    assert c["fwd_flops"] == 4 * 64 * pairs
    assert c["bwd_flops"] == 2 * c["fwd_flops"]
    tensor = 16 * 12 * 1024 * 64 * 2
    assert c["fwd_bytes"] == 4 * tensor and c["bwd_bytes"] == 8 * tensor


def test_least_seconds_names_the_bound():
    p = peaks.peaks_for("TPU v5 lite")
    assert p == {"flops_per_s": 197e12, "bytes_per_s": 819e9,
                 "memory_bytes": 16e9}
    assert workmodel.least_seconds(197e12, 1.0, p) == (1.0, "flops")
    assert workmodel.least_seconds(1.0, 819e9, p) == (1.0, "bytes")


@pytest.mark.parametrize("kind", ["TPU v5", "cpu", "", "_source",
                                  "TPU v5 lite "])
def test_unknown_device_kind_is_an_error(kind, monkeypatch):
    monkeypatch.setenv("OBS_PEAK_FLOPS", "1e15")     # no override is read
    with pytest.raises(KeyError):
        peaks.peaks_for(kind)
