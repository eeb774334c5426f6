"""CPU rehearsal of the serving driver at toy size: the result line, the
traced path, a planted fault and the control."""

import json

import numpy as np
import pytest

import bm_toy
from benchmark import tracereduce, trafficgen
from benchmark.drivers import serve

BIG = 2 ** 31 + 77
KEYS = ["correct", "attempted", "failed", "metrics", "device"]


@pytest.mark.parametrize("traffic,metric", [
    ("toy-serve-batch", "serve_tokens_per_s"),
    ("toy-serve-chat", "tpot_p90_ms")])
def test_untraced_run_prints_the_contracts_object(traffic, metric):
    r = bm_toy.run_toy(traffic, BIG, 1.0, bm_toy.SERVE_LIMITS)
    assert list(r)[:5] == KEYS and list(r)[-1] == "checks"
    assert set(r["metrics"]) == {metric, "setup_s"}
    assert all(m["value"] > 0 for m in r["metrics"].values())
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    assert {c["name"] for c in r["checks"]} == set(bm_toy.SERVE_LIMITS)
    assert all(set(c) == {"name", "value", "limit"} for c in r["checks"])
    assert r["notes"]["compile_s_in_window"] == 0.0
    json.dumps(r)


@pytest.mark.parametrize("traffic,suffix", [
    ("toy-serve-batch", "tokens"), ("toy-serve-chat", "tpot")])
def test_traced_run_reports_the_cells_per_layer_metrics(
        traffic, suffix, monkeypatch):
    monkeypatch.setattr(tracereduce, "load", bm_toy.fake_trace)
    monkeypatch.setattr("benchmark.peaks.peaks_for", lambda kind: {
        "flops_per_s": 197e12, "bytes_per_s": 819e9, "memory_bytes": 16e9})
    r = bm_toy.run_toy(traffic, 5, 1.0, bm_toy.SERVE_LIMITS, trace=True)
    want = {f"{n}.{suffix}" for n in (
        "engine_iter_ms_p50", "slot_occupancy", "decode_roofline", "mfu",
        "device_idle_share")}
    if suffix == "tpot":
        want |= {"ttft_p50_ms.tpot", "ttft_p90_ms.tpot"}
    # a CPU reports no memory peak, so hbm_peak_gb finds nothing to read
    assert want <= set(r["metrics"]) <= want | {
        "compile_s", "cache_hits", f"hbm_peak_gb.{suffix}"}
    assert 0 < r["device"]["busy_s"] <= r["device"]["window_s"]
    assert set(r["breakdown"]) == {"device_ops", "idle_gaps"}
    assert 0 < r["metrics"][f"slot_occupancy.{suffix}"]["value"] <= 100
    assert r["correct"] is True


def test_an_altered_token_is_not_correct(monkeypatch):
    real = serve.build_engine

    def altered(cfg, spec, seed, mark):
        engine = real(cfg, spec, seed, mark)
        decode, fused = engine._decode, engine._fused
        rows = int(cfg["assumed"]["padded_vocab_size"])

        def bad_decode(*a):
            cache, nxt, acc = decode(*a)
            return cache, (nxt + 1) % rows, acc

        def bad_fused(*a):
            cache, nxt, acc, c = fused(*a)
            return cache, (nxt + 1) % rows, acc, c

        engine._decode, engine._fused = bad_decode, bad_fused
        return engine

    monkeypatch.setattr(serve, "build_engine", altered)
    r = bm_toy.run_toy("toy-serve-batch", 11, 1.0, bm_toy.SERVE_LIMITS)
    assert r["correct"] is False
    failed = {c["name"] for c in r["checks"] if c["value"] > c["limit"]}
    assert "gap_max" in failed and "gap_mean" in failed


@pytest.mark.parametrize("seed", [1, 2, BIG])
def test_the_float8_control_is_not_correct(seed):
    cfg = bm_toy.toy_config()
    spec = trafficgen.load("toy-serve-batch", bm_toy.DATA)
    ctx = {"config": cfg, "traffic": spec, "seed": seed, "seconds": 1.0,
           "trace": False, "trace_seconds": 0.0, "trace_dir": ""}
    s = serve.setup(ctx)
    serve.measure(ctx, s)
    held = serve.release(ctx, s)
    program = dict(serve.check(ctx, held))
    control = serve.control(ctx, held)
    lim = bm_toy.SERVE_LIMITS
    assert all(program[k] <= lim[k] for k in program)
    assert any(control[k] > lim[k] for k in control), (program, control)
    assert control["gap_mean"] >= 3 * max(program["gap_mean"], 1e-9)
    assert np.isfinite(list(control.values())).all()
