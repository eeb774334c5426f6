"""The readers of the program's own spans (``benchmark/spanreaders.py``):
each on hand-made records, silent off the TPU and on a program without the
ring, and a toy traced run that reports every new metric of its cell."""

import collections
import os
import re

import pytest

import bm_toy
from benchmark import run as harness
from benchmark import spanreaders, tracereduce
from distributed_training_tpu.observability import trace as trace_lib

TPU = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
SERVE_CTX = {"device": TPU, "seconds": 40.0,
             "window": {"open_t": 100.0, "close_t": 140.0}}
# the training driver returns the traced part's bounds only
TRAIN_CTX = {"device": TPU, "seconds": 40.0,
             "window": {"traced": [134.0, 140.0]}}


def _at_setups_end(ctx):
    """The reader's context as ``run_cell`` makes it: with what it read of
    the constructor's span when set-up ended."""
    return {**ctx, "init_s": spanreaders.constructor_s(ctx["device"])}


def _iteration(key, t0, t1, program, dev=None, dispatch_ms=0.0):
    """One ``serve.iteration`` with its device step, from endpoints in s."""
    it = trace_lib.record("serve.iteration", t0, t1, key=key, live=4,
                          queued=0, program=program)
    if dev is not None:
        d0, d1 = dev
        split = d0 + dispatch_ms / 1e3
        trace_lib.record("serve.device_step", d0, d1, key=key,
                         program=program)
        trace_lib.record("serve.dispatch", d0, split, key=key)
        trace_lib.record("serve.token_wait", split, d1, key=key)
    return it


@pytest.fixture
def hand_made(monkeypatch):
    """A window of 100 s .. 140 s on the spans' clock, filled by hand."""
    monkeypatch.setattr(trace_lib, "_ring", collections.deque(maxlen=4096))
    rec = trace_lib.record
    rec("setup.trainer_init", 30.0, 49.0)
    rec("setup.engine_init", 50.0, 52.0)
    # before the window opens: counted by nobody
    _iteration(0, 99.0, 99.4, "decode", dev=(99.0, 99.3), dispatch_ms=9.0)
    # host = iteration - token_wait: 8, 12 and 6 ms
    _iteration(1, 100.0, 100.175, "decode", dev=(100.004, 100.173),
               dispatch_ms=2.0)
    _iteration(2, 100.2, 100.41, "fused", dev=(100.205, 100.406),
               dispatch_ms=3.0)
    _iteration(3, 100.5, 100.676, "decode", dev=(100.503, 100.674),
               dispatch_ms=1.0)
    _iteration(4, 100.7, 100.7001, "idle")
    # uid 3 arrived before the window: neither of its spans counts
    for uid, arrived, seated, first in [(3, 99.5, 100.2, 100.4),
                                        (1, 101.0, 101.1, 101.3),
                                        (2, 102.0, 102.3, 102.8)]:
        rec("serve.queued", arrived, seated, key=uid)
        rec("serve.prefill", seated, first, key=uid)
    for step, (wait, dispatch) in enumerate(
            [(1.0, 120.0), (2.0, 121.0), (3.0, 125.0)], start=1):
        t = 110.0 + step
        rec("train.batch_wait", t, t + wait / 1e3, key=step)
        rec("train.dispatch", t + 0.01, t + 0.01 + dispatch / 1e3, key=step)
    rec("train.metrics_fetch", 120.0, 120.0005, key=50)
    rec("train.metrics_fetch", 126.0, 126.0007, key=100)


READERS = [
    # metric, context, what the hand-made records give
    ("decode_step_ms_p50.tokens", SERVE_CTX, 170.0),
    ("decode_step_ms_p50.tpot", SERVE_CTX, 170.0),
    ("fused_step_ms_p50.tokens", SERVE_CTX, 201.0),
    ("fused_step_ms_p50.tpot", SERVE_CTX, 201.0),
    ("fused_iter_share.tokens", SERVE_CTX, 100.0 / 3),
    ("fused_iter_share.tpot", SERVE_CTX, 100.0 / 3),
    ("serve_host_ms_p50.tokens", SERVE_CTX, 8.0),
    ("serve_host_ms_p50.tpot", SERVE_CTX, 8.0),
    ("serve_dispatch_ms_p50.tokens", SERVE_CTX, 2.0),
    ("serve_dispatch_ms_p50.tpot", SERVE_CTX, 2.0),
    ("queue_wait_ms_p90.tpot", SERVE_CTX, 280.0),
    ("prefill_ms_p90.tpot", SERVE_CTX, 470.0),
    ("batch_wait_ms_p50.train", TRAIN_CTX, 2.0),
    ("dispatch_ms_p50.train", TRAIN_CTX, 121.0),
    ("metrics_fetch_ms_p50.train", TRAIN_CTX, 0.6),
    ("init_s", SERVE_CTX, 2.0),
]


def test_every_metric_read_from_the_ring_has_a_case():
    from_ring = {m["name"] for m in harness.load_benchmark()["per_layer"]
                 if harness.load_reader(m["name"]).__module__
                 == spanreaders.__name__}
    assert from_ring == {name for name, _, _ in READERS}
    assert len(from_ring) == 16


@pytest.mark.parametrize("metric,ctx,expected", READERS,
                         ids=[r[0] for r in READERS])
def test_reader_on_hand_made_records(hand_made, metric, ctx, expected):
    read = harness.load_reader(metric)
    assert read(_at_setups_end(ctx)) == pytest.approx(expected, rel=1e-6)
    # a time from a CPU run is not written under a device metric's name
    off_tpu = {**ctx, "device": {**TPU, "platform": "cpu"}}
    assert read(_at_setups_end(off_tpu)) is None


def test_init_s_reads_the_trainers_constructor_where_there_is_no_engine(
        monkeypatch):
    monkeypatch.setattr(trace_lib, "_ring", collections.deque(maxlen=16))
    trace_lib.record("setup.trainer_init", 30.0, 49.0)
    assert spanreaders.init_s(_at_setups_end(TRAIN_CTX)) == \
        pytest.approx(19.0)


def test_init_s_outlives_a_ring_that_turns_over_inside_the_window(
        monkeypatch):
    """A serving window opens more spans than the ring holds; what the
    harness took when set-up ended is still what ``init_s`` reads."""
    monkeypatch.setattr(trace_lib, "_ring",
                        collections.deque(maxlen=trace_lib.RING_SPANS))
    trace_lib.record("setup.engine_init", 50.0, 52.0)
    ctx = _at_setups_end(SERVE_CTX)
    for i in range(trace_lib.RING_SPANS + 10):
        trace_lib.record("serve.iteration", 100.0 + i * 1e-3,
                         100.0005 + i * 1e-3, key=i, program="decode")
    assert len(trace_lib.host_spans()) == trace_lib.RING_SPANS
    assert not [s for s in trace_lib.host_spans()
                if s.name == "setup.engine_init"]
    assert harness.load_reader("init_s")(ctx) == pytest.approx(2.0)
    # read at the close, as before this test, it finds nothing
    assert harness.load_reader("init_s")(_at_setups_end(SERVE_CTX)) is None


@pytest.mark.parametrize("metric,ctx", [(r[0], r[1]) for r in READERS],
                         ids=[r[0] for r in READERS])
def test_reader_finds_nothing_without_raising(monkeypatch, metric, ctx):
    """An empty window, and a program from before the spans (no ring at
    all): the metric is left out of the line, the run goes on."""
    read = harness.load_reader(metric)
    monkeypatch.setattr(trace_lib, "_ring", collections.deque(maxlen=16))
    assert read(_at_setups_end(ctx)) is None
    assert read(_at_setups_end({**ctx, "window": {}})) is None
    monkeypatch.delattr(trace_lib, "host_spans")
    assert read(_at_setups_end(ctx)) is None


def test_the_program_imports_no_benchmark():
    root = os.path.join(harness.ROOT, "distributed_training_tpu")
    for d, _dirs, files in os.walk(root):
        for f in files:
            if f.endswith(".py"):
                with open(os.path.join(d, f)) as fh:
                    assert not re.search(r"^\s*(from|import) benchmark\b",
                                         fh.read(), re.M), f


@pytest.mark.parametrize("traffic,limits,seconds,want", [
    ("toy-serve-batch", bm_toy.SERVE_LIMITS, 1.0, [
        "decode_step_ms_p50.tokens", "fused_step_ms_p50.tokens",
        "fused_iter_share.tokens", "serve_host_ms_p50.tokens",
        "serve_dispatch_ms_p50.tokens", "init_s"]),
    ("toy-serve-chat", bm_toy.SERVE_LIMITS, 1.0, [
        "decode_step_ms_p50.tpot", "fused_step_ms_p50.tpot",
        "fused_iter_share.tpot", "serve_host_ms_p50.tpot",
        "serve_dispatch_ms_p50.tpot", "queue_wait_ms_p90.tpot",
        "prefill_ms_p90.tpot", "init_s"]),
    ("toy-train", bm_toy.TRAIN_LIMITS, 1.5, [
        "batch_wait_ms_p50.train", "dispatch_ms_p50.train",
        "metrics_fetch_ms_p50.train", "init_s"]),
])
def test_a_traced_toy_run_reports_every_new_metric_of_its_cell(
        traffic, limits, seconds, want, monkeypatch):
    """The harness's own path, told that its device is a TPU so that the
    readers speak: the values are a CPU's and are only looked at for
    their sense."""
    monkeypatch.setenv("TQDM_DISABLE", "1")
    monkeypatch.setattr(tracereduce, "load", bm_toy.fake_trace)
    cell, bench = bm_toy.toy_bench(traffic)
    r = harness.run_cell(cell, bench, 7, seconds, True, device=TPU,
                         limits=limits, traffic_dir=bm_toy.DATA)
    got = r["metrics"]
    assert set(want) <= set(got), set(want) - set(got)
    units = {m["name"]: m["unit"] for m in bench["per_layer"]}
    for name in want:
        assert got[name]["value"] > 0 and got[name]["unit"] == units[name]
    assert r["correct"] is True
    if traffic != "toy-train":
        suffix = want[0].rsplit(".", 1)[1]
        assert 0 < got[f"fused_iter_share.{suffix}"]["value"] <= 100
        # the device step is the larger part of the outside clock's reading
        assert got[f"decode_step_ms_p50.{suffix}"]["value"] \
            <= got[f"engine_iter_ms_p50.{suffix}"]["value"]
