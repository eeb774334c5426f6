"""The readers of the device steps the engine stamps on its iterations
(``benchmark/stepreaders.py``): their arithmetic on hand-made records, their
silence off the TPU and on spans without the stamps, and a toy traced run
that reports them."""

import collections

import pytest

import bm_toy
from benchmark import run as harness
from benchmark import stepreaders, tracereduce
from distributed_training_tpu.observability import trace as trace_lib

TPU = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
CTX = {"device": TPU, "seconds": 40.0,
       "window": {"open_t": 100.0, "close_t": 140.0}}
MS = 1e-3


def _iteration(key, t0, t1, program, stamps=None):
    """One ``serve.iteration``; ``stamps`` = (launch_t0, launch_t1,
    landed_lo, landed_hi) in ms after 100 s."""
    attrs = {}
    if stamps is not None:
        attrs = dict(zip(("launch_t0", "launch_t1", "landed_lo",
                          "landed_hi"), (100.0 + x * MS for x in stamps)))
    trace_lib.record("serve.iteration", 100.0 + t0 * MS, 100.0 + t1 * MS,
                     key=key, program=program, **attrs)


def _fill(stamped=True):
    """Eight iterations, one of them idle; pauses inside and outside."""
    rows = [
        # key, iteration ms, program, (launch_t0, launch_t1, lo, hi)
        (1, (0, 10), "decode", (1, 2, 9, 9)),
        # launched ahead, waited for: starts at the waited landing before
        (2, (10, 20), "decode", (3, 4, 17, 17)),
        # launched 3.5 ms after that landing: a gap, and a start at 20.5
        (3, (20, 41), "fused", (19.5, 20.5, 40.5, 40.5)),
        (4, (41, 42), "idle", None),
        # after an idle iteration; its landing is only bracketed
        (5, (50, 57), "decode", (49, 50, 55, 57)),
        # the step before was seen landed (57) before this launch ended
        # (57.5): its start is pinned there
        (6, (57, 70), "decode", (50.5, 57.5, 63, 63)),
        # a bracketed landing: not pinned
        (7, (70, 80), "decode", (64, 65, 70, 74)),
        # waited for, but the step before may have landed after its launch;
        # 4 ms after the landing before, under the program's 6.75
        (8, (80, 90), "decode", (66, 67, 78, 78)),
    ]
    trace_lib.record("serve.iteration", 99.0, 99.01, key=0,
                     program="decode", launch_t0=98.0, launch_t1=98.0,
                     landed_lo=99.0, landed_hi=99.0)     # before the window
    for key, (t0, t1), program, stamps in rows:
        _iteration(key, t0, t1, program,
                   stamps if stamped and program != "idle" else None)
    for name, t0, t1 in [("proc.gc", 30, 34), ("proc.compile", 32, 37),
                         ("proc.gc", 80, 95),       # past the last working
                         ("proc.gc", -500, -400)]:  # before the window
        trace_lib.record(name, 100.0 + t0 * MS, 100.0 + t1 * MS, key=3,
                         generation=2, collected=0)


@pytest.fixture
def ring(monkeypatch):
    monkeypatch.setattr(trace_lib, "_ring", collections.deque(maxlen=256))


# pinned device ms: decode 8 (2), 5.5 (6) -> p50 6.75; fused 20 (3).
# gap share, over the steps after a working iteration (2, 3, 6, 7, 8):
# landing (a bracket's upper end) to landing 8 + 23.5 + 6 + 11 + 4 = 52.5
# ms; gaps 0, 3.5, 0.5 where pinned, and 11 - 6.75 = 4.25 and
# max(0, 4 - 6.75) = 0 where not: 8.25 ms.
# pauses: the union 30-37 ms of the working iterations' 0-90 ms.
READERS = [
    ("decode_device_ms_p50.tokens", 6.75),
    ("decode_device_ms_p50.tpot", 6.75),
    ("fused_device_ms_p50.tokens", 20.0),
    ("fused_device_ms_p50.tpot", 20.0),
    ("host_gap_share.tokens", 100.0 * 8.25 / 52.5),
    ("host_gap_share.tpot", 100.0 * 8.25 / 52.5),
    ("host_pause_share.tokens", 100.0 * 7 / 90),
    ("host_pause_share.tpot", 100.0 * 7 / 90),
]


def test_every_metric_read_from_the_stamps_has_a_case():
    from_stamps = {m["name"] for m in harness.load_benchmark()["per_layer"]
                   if harness.load_reader(m["name"]).__module__
                   == stepreaders.__name__}
    assert from_stamps == {name for name, _ in READERS}


@pytest.mark.parametrize("metric,expected", READERS,
                         ids=[r[0] for r in READERS])
def test_reader_on_hand_made_records(ring, metric, expected):
    _fill()
    read = harness.load_reader(metric)
    assert read(CTX) == pytest.approx(expected, rel=1e-6)
    assert read({**CTX, "device": {**TPU, "platform": "cpu"}}) is None


@pytest.mark.parametrize("metric", [r[0] for r in READERS])
def test_reader_is_silent_without_the_stamps(ring, monkeypatch, metric):
    """A program from before the stamps (its iterations carry none), an
    empty window and a program without the ring: no value, no raise."""
    read = harness.load_reader(metric)
    _fill(stamped=False)
    assert read(CTX) is None
    assert read({**CTX, "window": {}}) is None
    monkeypatch.setattr(trace_lib, "_ring", collections.deque(maxlen=16))
    assert read(CTX) is None
    monkeypatch.delattr(trace_lib, "host_spans")
    assert read(CTX) is None


def test_a_step_is_timed_where_both_its_ends_are_pinned(ring):
    _fill()
    got = stepreaders.steps(CTX)
    assert [st["program"] for st in got] == [
        "decode", "fused", "decode", "decode", "decode", "decode"]
    assert [st["adjacent"] for st in got] == [
        True, True, False, True, True, True]
    # landing (a bracket's upper end) to landing
    assert [st["span"] * 1e3 for st in got] == pytest.approx(
        [8.0, 23.5, 16.5, 6.0, 11.0, 4.0])
    # a waited landing less the later of the launch's end and a waited
    # landing before, or less the launch's end where the step before had
    # been seen landed by then; else nothing
    device = [st["device"] for st in got]
    assert device[0] * 1e3 == pytest.approx(8.0)
    assert device[1] * 1e3 == pytest.approx(20.0)
    assert device[3] * 1e3 == pytest.approx(5.5)
    assert device[2] is None and device[4] is None and device[5] is None


def test_the_gap_share_needs_a_pinned_step_of_each_program(ring):
    """A program none of whose steps is pinned has no device time to
    stand in for its others: no share rather than a made-up one."""
    trace_lib.record("serve.iteration", 100.0, 100.01, key=1,
                     program="decode", launch_t0=100.0, launch_t1=100.001,
                     landed_lo=100.009, landed_hi=100.009)
    trace_lib.record("serve.iteration", 100.01, 100.03, key=2,
                     program="fused", launch_t0=100.002, launch_t1=100.003,
                     landed_lo=100.02, landed_hi=100.025)
    assert stepreaders.steps(CTX)[0]["device"] is None
    assert stepreaders.host_gap_share(CTX) is None
    assert stepreaders.fused_device_ms_p50(CTX) is None


@pytest.mark.parametrize("traffic,suffix", [("toy-serve-batch", "tokens"),
                                            ("toy-serve-chat", "tpot")])
def test_a_traced_toy_run_reports_the_stamped_metrics(traffic, suffix,
                                                      monkeypatch):
    """The harness's own path over the real engine, told that its device is
    a TPU so that the readers speak: a CPU's values, read for their sense."""
    monkeypatch.setenv("TQDM_DISABLE", "1")
    monkeypatch.setattr(tracereduce, "load", bm_toy.fake_trace)
    cell, bench = bm_toy.toy_bench(traffic)
    r = harness.run_cell(cell, bench, 7, 1.0, True, device=TPU,
                         limits=bm_toy.SERVE_LIMITS, traffic_dir=bm_toy.DATA)
    got = {n: v["value"] for n, v in r["metrics"].items()}
    assert 0 <= got[f"host_pause_share.{suffix}"] <= 100
    # a toy step is far shorter than the host's turn, so the fetch seldom
    # waits and a short run may pin no step of a program: then those
    # metrics are left out of the line, and where they are in it they
    # make sense
    for name in ("decode_device_ms_p50", "fused_device_ms_p50"):
        assert got.get(f"{name}.{suffix}", 1.0) > 0
    assert 0 <= got.get(f"host_gap_share.{suffix}", 0.0) <= 100
    assert r["correct"] is True
