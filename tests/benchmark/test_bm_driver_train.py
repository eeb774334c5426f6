"""CPU rehearsal of the training driver at toy size: the result line, the
traced path, the planted faults and the control."""

import json

import jax
import jax.numpy as jnp
import pytest

import bm_toy
from benchmark import tracereduce, trafficgen
from benchmark.drivers import train

BIG = 2 ** 31 + 78


@pytest.fixture(autouse=True)
def quiet_bars(monkeypatch):
    monkeypatch.setenv("TQDM_DISABLE", "1")


def test_untraced_run_prints_the_contracts_object():
    r = bm_toy.run_toy("toy-train", BIG, 1.0, bm_toy.TRAIN_LIMITS)
    assert list(r)[:5] == ["correct", "attempted", "failed", "metrics",
                           "device"] and list(r)[-1] == "checks"
    assert set(r["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert r["metrics"]["train_tokens_per_s"]["value"] > 0
    assert r["correct"] is True and r["attempted"] == r["notes"]["steps"] > 3
    assert {c["name"] for c in r["checks"]} == set(bm_toy.TRAIN_LIMITS)
    assert r["notes"]["compile_s_in_window"] == 0.0
    json.dumps(r)


def test_traced_run_reports_the_cells_per_layer_metrics(monkeypatch):
    monkeypatch.setattr(tracereduce, "load", bm_toy.fake_trace)
    monkeypatch.setattr("benchmark.peaks.peaks_for", lambda kind: {
        "flops_per_s": 197e12, "bytes_per_s": 819e9, "memory_bytes": 16e9})
    r = bm_toy.run_toy("toy-train", 6, 1.5, bm_toy.TRAIN_LIMITS, trace=True)
    want = {"step_ms_p50.train", "step_ms_p95.train", "mfu.train",
            "device_idle_share.train"}
    # exact attention at toy size: no flash kernel to read a roofline from
    assert want <= set(r["metrics"]) <= want | {
        "compile_s", "cache_hits", "hbm_peak_gb.train"}
    assert 0 < r["metrics"]["mfu.train"]["value"] < 100
    assert r["correct"] is True and "breakdown" in r


class _Wrapped:
    """A train step with something broken underneath; everything else
    (shardings, attributes) is the real step's."""

    def __init__(self, real, fault):
        self._real, self._fault = real, fault

    def __getattr__(self, name):
        return getattr(self._real, name)

    def __call__(self, state, batch, rng):
        if self._fault == "state_unchanged":
            kept = jax.tree.map(jnp.copy, state)    # the step donates its
            _, metrics = self._real(state, batch, rng)   # argument
            return kept, metrics
        half = {k: jax.device_put(
            jnp.concatenate([v[:v.shape[0] // 2]] * 2), v.sharding)
            for k, v in batch.items()}
        return self._real(state, half, rng)


@pytest.mark.parametrize("fault,number", [
    ("state_unchanged", "change_norm_gap"), ("half_batch", "grad_norm_gap")])
def test_a_broken_step_is_not_correct(fault, number, monkeypatch):
    from distributed_training_tpu.train import lm_trainer

    real = lm_trainer.make_tp_lm_train_step
    monkeypatch.setattr(
        lm_trainer, "make_tp_lm_train_step",
        lambda *a, **k: _Wrapped(real(*a, **k), fault))
    r = bm_toy.run_toy("toy-train", 13, 0.5, bm_toy.TRAIN_LIMITS)
    assert r["correct"] is False
    got = {c["name"]: c for c in r["checks"]}
    assert got[number]["value"] > 10 * got[number]["limit"]
    if fault == "state_unchanged":
        assert got["change_norm_gap"]["value"] == pytest.approx(1.0)


@pytest.mark.parametrize("seed", [1, 2, BIG])
def test_the_control_and_the_planted_faults_are_not_correct(seed):
    spec = trafficgen.load("toy-train", bm_toy.DATA)
    ctx = {"config": bm_toy.toy_config(), "traffic": spec, "seed": seed}
    readings = train.control(ctx, train.seed_batches(ctx))
    assert set(readings) == {"float8", "half_batch", "state_unchanged"}
    lim = bm_toy.TRAIN_LIMITS
    for name, got in readings.items():
        assert any(got[k] > lim[k] for k in got), name
    assert readings["state_unchanged"]["change_norm_gap"] == \
        pytest.approx(1.0)
    assert readings["half_batch"]["grad_norm_gap"] > 0.1
