"""The reduction from a trace to numbers, on hand-made event lists and on
a small trace recorded on the chip (``data/recorded_trace.json``)."""

import json
import os

import pytest

from benchmark import tracereduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")

# One device, times in ns. A loop (while.2) holds two children; a collective
# runs alone from 250 to 290; the device idles 150-200, 300-400 and 410-500.
OPS = [("fusion.1", 100, 50), ("while.2", 200, 100), ("fusion.3", 210, 30),
       ("copy.9", 240, 10), ("all-reduce.4", 250, 40), ("copy.5", 400, 10)]
HOST = [("bench.window", 50, 450), ("engine.step", 140, 70),
        ("loader.next", 300, 95), ("loss.fetch", 405, 200)]
TRACE = {"devices": {"/device:TPU:0": OPS}, "host": HOST}


def test_busy_is_the_union_not_the_sum():
    assert tr.busy_intervals(OPS) == [(100, 150), (200, 300), (400, 410)]
    assert tr.busy_ns(OPS) == 160
    assert tr.busy_ns(OPS + [("fusion.1", 120, 10)]) == 160


def test_self_time_takes_children_out_of_their_parent():
    st = tr.self_times(OPS)
    assert st["while.2"] == 100 - 30 - 10 - 40
    assert st["fusion.3"] == 30 and st["all-reduce.4"] == 40
    assert sum(st.values()) == tr.busy_ns(OPS)


def test_exposed_collective_time():
    assert tr.exposed_collective_ns(OPS) == 40
    assert tr.exposed_collective_ns(
        OPS + [("all-gather-start.7", 420, 5), ("all-gather-done.7", 430, 15),
               ("fusion.8", 425, 5)]) == 60
    assert tr.exposed_collective_ns([("fusion.1", 0, 10)]) == 0


def test_window_clipping_and_gap_owners():
    lo, hi = tr.window_of(TRACE)
    assert (lo, hi) == (50, 500)
    clipped = tr.clip(OPS + [("late.1", 490, 100), ("gone.2", 600, 5)],
                      lo, hi)
    assert ("late.1", 490, 10) in clipped
    assert not [e for e in clipped if e[0] == "gone.2"]
    gaps = tr.idle_gaps(OPS, lo, hi)
    assert gaps == [(50, 100), (150, 200), (300, 400), (410, 500)]
    owners = tr.gap_owners([(a * 1000, b * 1000) for a, b in gaps],
                           [(n, s * 1000, d * 1000) for n, s, d in HOST])
    assert owners == {"_no_span_": 50_000, "engine.step": 50_000,
                      "loader.next": 100_000, "loss.fetch": 90_000}
    assert tr.gap_owners([(0, 1000)], HOST) == {"_gaps_under_20_us_": 1000}


def test_reduce_gives_what_the_readers_take():
    r = tr.reduce(TRACE)
    assert r["window_s"] == pytest.approx(450e-9)
    assert r["busy_s"] == r["busy_s_busiest"] == pytest.approx(160e-9)
    assert r["idle_share"] == pytest.approx(1 - 160 / 450)
    assert r["exposed_collective_share"] == pytest.approx(40 / 450)
    assert r["ops"]["fusion"] == pytest.approx(80e-9)
    assert len(r["breakdown"]["device_ops"]) <= 10
    assert r["breakdown"]["device_ops"][0][0] == "fusion"


def test_busiest_of_several_devices_sets_the_idle_share():
    two = {"devices": {"/device:TPU:0": OPS,
                       "/device:TPU:1": OPS + [("fusion.6", 300, 90)]},
           "host": HOST}
    r = tr.reduce(two)
    assert r["n_devices"] == 2
    assert r["busy_s_busiest"] == pytest.approx(250e-9)
    assert r["busy_s"] == pytest.approx((160 + 250) / 2 * 1e-9)
    assert r["idle_share"] == pytest.approx(1 - 250 / 450)


@pytest.mark.parametrize("broken,message", [
    ({"devices": {}, "host": HOST}, "no TPU operations"),
    ({"devices": {"/device:TPU:0": OPS}, "host": []}, "bench.window")])
def test_a_trace_with_nothing_to_read_is_an_error(broken, message):
    with pytest.raises(ValueError, match=message):
        tr.reduce(broken)


HLO = ("%convert.434 = f32[32768,20,64]{2,1,0:T(8,128)} convert(bf16[32768,"
       "20,64]{2,1,0:T(8,128)(2,1)} %fusion.2)")


@pytest.mark.parametrize("name,kind,key", [
    ("fusion.123", "fusion", "fusion"),
    ("all-reduce-start.2", "all-reduce-start", "all-reduce-start"),
    ("copy", "copy", "copy"),
    (HLO, "convert", "convert f32[32768,20,64]"),
    ("%copy-start.4 = (f32[32]{0:T(128)S(1)}, f32[32]{0}, u32[]) copy-start("
     "f32[32]{0} %copy-done.3)", "copy-start", "copy-start f32[32]"),
    ("%all-reduce.5 = bf16[8,1024,1280]{2,1,0} all-reduce(bf16[8,1024,1280] "
     "%fusion.1)", "all-reduce", "all-reduce bf16[8,1024,1280]")])
def test_op_kind_and_key(name, kind, key):
    assert tr.op_kind(name) == kind and tr.op_key(name) == key
    assert bool(tr.COLLECTIVE.match(kind)) == kind.startswith("all-reduce")


def test_recorded_trace_from_the_chip():
    path = os.path.join(DATA, "recorded_trace.json")
    with open(path) as fh:
        rec = json.load(fh)
    trace = {"devices": {k: [tuple(e) for e in v]
                         for k, v in rec["devices"].items()},
             "host": [tuple(e) for e in rec["host"]]}
    r = tr.reduce(trace)
    want = rec["expected"]
    assert r["window_s"] == pytest.approx(want["window_s"])
    assert r["busy_s"] == pytest.approx(want["busy_s"])
    assert r["idle_share"] == pytest.approx(want["idle_share"])
    assert 0 < r["busy_s"] < r["window_s"]
    assert r["breakdown"]["idle_gaps"][0][0] == want["top_gap_owner"]
    assert r["breakdown"]["device_ops"][0][0] == want["top_op"]
