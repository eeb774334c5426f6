"""The traffic generator: every seed offers the same work in another order."""

import json
import os

import numpy as np
import pytest

from benchmark import trafficgen

TRAFFIC = os.path.join(os.path.dirname(trafficgen.__file__), "traffic")
MIXES = sorted(f[:-5] for f in os.listdir(TRAFFIC)
               if json.load(open(os.path.join(TRAFFIC, f)))["driver"]
               == "serve")
BIG = 2 ** 31 + 12345     # the driver's seeds exceed 32 signed bits


def _cycle(name, seed):
    spec = trafficgen.load(name)
    s = trafficgen.RequestStream(spec, seed, 50257)
    return spec, [s.pop() for _ in range(int(spec["multiset"]))]


@pytest.mark.parametrize("name", MIXES)
def test_same_multiset_other_order(name):
    _, a = _cycle(name, 1)
    _, b = _cycle(name, BIG)
    pa, pb = [r.prompt.size for r in a], [r.prompt.size for r in b]
    oa, ob = [r.max_new_tokens for r in a], [r.max_new_tokens for r in b]
    assert sorted(pa) == sorted(pb) and sorted(oa) == sorted(ob)
    assert pa != pb and oa != ob
    assert any((x.prompt[:3] != y.prompt[:3]).any() for x, y in zip(a, b))


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_traffic(name):
    _, a = _cycle(name, BIG)
    _, b = _cycle(name, BIG)
    assert all((x.prompt == y.prompt).all()
               and x.max_new_tokens == y.max_new_tokens
               and x.due_s == y.due_s for x, y in zip(a, b))


@pytest.mark.parametrize("name", MIXES)
def test_lengths_keep_to_the_file(name):
    spec, a = _cycle(name, 3)
    p = np.array([r.prompt.size for r in a])
    o = np.array([r.max_new_tokens for r in a])
    for got, want in ((p, spec["prompt_tokens"]), (o, spec["output_tokens"])):
        assert got.min() >= want["min"] and got.max() <= want["max"]
        assert abs(np.median(got) - want["median"]) <= 1
    ids = np.concatenate([r.prompt for r in a])
    assert ids.min() >= 0 and ids.max() < 50257
    e = spec["engine"]
    assert want["max"] <= e["max_new_tokens"]
    assert spec["prompt_tokens"]["max"] + want["max"] <= e["max_len"]


def test_arrival_gaps_sum_to_the_schedule():
    g = trafficgen.stratified_exponential_gaps(2.5, 256)
    assert g.sum() == pytest.approx(256 / 2.5)
    assert (g > 0).all() and g.max() / g.min() > 50   # a real exponential
    spec, a = _cycle("serve-chat", 5)
    _, b = _cycle("serve-chat", 6)
    rate = spec["arrival_rate_per_s"]
    assert a[-1].due_s == pytest.approx(len(a) / rate)
    assert b[-1].due_s == pytest.approx(a[-1].due_s)
    assert [r.due_s for r in a] != [r.due_s for r in b]


def test_second_cycle_is_reshuffled_and_time_goes_on():
    spec = trafficgen.load("serve-chat")
    s = trafficgen.RequestStream(spec, 9, 50257)
    n = int(spec["multiset"])
    first = [s.pop() for _ in range(n)]
    second = [s.pop() for _ in range(n)]
    assert sorted(r.max_new_tokens for r in first) == \
        sorted(r.max_new_tokens for r in second)
    assert [r.max_new_tokens for r in first] != \
        [r.max_new_tokens for r in second]
    assert second[0].due_s > first[-1].due_s
    assert second[0].index == n


def test_training_rows_all_differ():
    spec = trafficgen.load("train-b16-t1024")
    rows = trafficgen.token_rows(spec, BIG, 50257)
    assert rows.shape == (spec["rows"], spec["seq_len"] + 1)
    assert rows.dtype == np.int32 and rows.max() < 50257
    assert len({r.tobytes() for r in rows}) == len(rows)
    assert (rows != trafficgen.token_rows(spec, BIG + 1, 50257)).any()
    assert (rows == trafficgen.token_rows(spec, BIG, 50257)).all()


@pytest.mark.parametrize("name", MIXES)
def test_balanced_order_every_block_spans_the_distribution(name):
    spec, a = _cycle(name, BIG)
    strata = spec["order_strata"]
    k = int(strata[-1] if isinstance(strata, list) else strata)
    n = int(spec["multiset"])
    outs = np.array([r.max_new_tokens for r in a])
    bands = np.sort(outs).reshape(k, n // k)
    for block in outs.reshape(n // k, k):
        # one value from each quantile band, whatever the order
        assert all(bands[b].min() <= v <= bands[b].max()
                   for b, v in enumerate(np.sort(block)))
    sums = outs.reshape(n // k, k).sum(axis=1)
    plain = trafficgen.shuffled(np.sort(outs), trafficgen.rng_for(1, 1))
    assert sums.std() < 0.5 * plain.reshape(n // k, k).sum(axis=1).std()


def test_balanced_shuffle_needs_strata_that_divide():
    with pytest.raises(ValueError):
        trafficgen.shuffled(np.arange(10), trafficgen.rng_for(1, 1), 3)


def test_nested_balance_holds_at_both_scales():
    values = np.arange(256)
    got = trafficgen.shuffled(values, trafficgen.rng_for(BIG, 3), [4, 32])
    assert sorted(got) == list(values)
    for block in got.reshape(8, 32):
        assert sorted(block // 8) == list(range(32))      # one of each band
        ranks = np.argsort(np.argsort(block)).reshape(8, 4)
        assert all(sorted(r // 8) == [0, 1, 2, 3] for r in ranks)
    assert (got != trafficgen.shuffled(values, trafficgen.rng_for(BIG + 1, 3),
                                       [4, 32])).any()
