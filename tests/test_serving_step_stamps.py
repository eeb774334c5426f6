"""The launch and landing stamps of each device step (serving/engine.py
``_DeviceStep``), on the ``serve.iteration`` span that delivers it.

``launch_t0`` / ``launch_t1``: ``_launch`` entered and returned;
``landed_lo`` / ``landed_hi``: a bracket around the moment the step's
outputs became ready, narrowed by a look at each phase's end and closed by
the fetch's wait. Pinned here on the CPU, for engines that run ahead, in
order, land only, and speculate: the stamps are ordered, landings follow
launches, and no two steps' device intervals overlap."""

import time

import jax
import pytest
from test_serving_run_ahead import (  # noqa: F401  (lm, prompts: fixtures)
    in_flight_after,
    in_order,
    lm,
    make,
    prompts,
    serve,
)

from distributed_training_tpu.observability import trace as trace_lib

STAMPS = ("launch_t0", "launch_t1", "landed_lo", "landed_hi")


def iterations(since):
    return [s for s in trace_lib.host_spans(since, time.perf_counter())
            if s.name == "serve.iteration"]


def check(its):
    """The delivered steps' stamps, in the order the calls delivered them."""
    delivered = [s.attrs for s in its if s.attrs["program"] != "idle"]
    assert delivered
    for s in its:
        # a call that delivers nothing carries no stamps
        assert (s.attrs["program"] == "idle") == ("launch_t0" not in s.attrs)
    prev = None
    for a in delivered:
        t0, t1, lo, hi = (a[k] for k in STAMPS)
        assert t0 <= t1 <= lo <= hi
        landing = (lo + hi) / 2
        if prev is not None:
            # launched, and landed, in the order delivered
            assert prev["launch_t0"] < t0
            assert prev["landed_lo"] <= lo and prev["landed_hi"] <= hi
            # its device interval begins where the one before it ended
            start = max(t1, (prev["landed_lo"] + prev["landed_hi"]) / 2)
            assert start <= landing
        prev = a
    return delivered


@pytest.mark.parametrize("mode", ["run-ahead", "in-order", "speculative"])
def test_every_delivered_step_is_stamped_in_order(lm, prompts, mode):
    t0 = time.perf_counter()
    if mode == "in-order":
        with in_order():
            eng = make(lm)
            serve(eng, prompts)
    else:
        eng = make(lm, spec_k=2 if mode == "speculative" else 0)
        serve(eng, prompts)
    its = iterations(t0)
    delivered = check(its)
    ahead = [a["ahead"] for a in delivered]
    if mode == "run-ahead":
        assert sum(ahead) > len(ahead) / 2
    else:
        assert not any(ahead)


def test_a_call_that_only_lands_carries_the_stamps_of_what_it_lands(
        lm, prompts):
    eng = make(lm)
    a = eng.submit(prompts[0], max_new_tokens=8)
    eng.submit(prompts[1], max_new_tokens=8)
    t0 = time.perf_counter()
    in_flight_after(eng, 4)
    landing = eng._in_flight
    eng.cancel(a.uid)
    t_land = time.perf_counter()
    assert eng.step() == []                     # lands only
    (it,) = iterations(t_land)
    assert not [s for s in trace_lib.host_spans(t_land, time.perf_counter())
                if s.name == "serve.dispatch"]
    assert [it.attrs[k] for k in STAMPS] == [
        landing.launch_t0, landing.launch_t1, landing.landed_lo,
        landing.landed_hi]
    eng.run()
    check(iterations(t0))


def test_the_stamps_touch_no_device_data(lm, prompts):
    """The looks and the wait transfer nothing (the fetch is the
    iteration's own, allowed where it is made)."""
    eng = make(lm, max_batch=2)
    for p in prompts[:2]:
        eng.submit(p, max_new_tokens=8)
    while len(eng.queue) or any(s.prefilling
                                for s in eng.scheduler.active()):
        eng.step()
    step = eng._in_flight
    assert step is not None
    with jax.transfer_guard("disallow"):
        step.probe()
        step.land()
    assert step.launch_t1 <= step.landed_lo <= step.landed_hi
