"""The paged iteration one device step ahead of the host (serving/engine.py,
docs/SERVING.md "The iteration's order").

Entered with step k in flight, a call to ``Engine.step`` assembles and
launches k + 1 and only then fetches, commits and delivers k's tokens. What
that may never change, pinned here on the CPU:

1. **Tokens.** Every request's stream is the in-order engine's (the same
   code with nothing in flight) and, greedy, the sequential ``Generator``'s:
   over mixed prompt and output lengths, admissions, multi-chunk prefills,
   final chunks that land while a step is in flight, an EOS the host could
   not foresee (its wasted row is dropped, its pages go back after the step
   that wrote them).
2. **What changes the world lands the flight first**: a cancel, an expired
   deadline, a preemption, an armed swap; a drafter never runs ahead.
3. **What callers see**: ``idle`` is false while tokens are undelivered,
   ``run()`` and ``drain()`` deliver every token, ``ahead`` and
   ``stats()["run_ahead_share"]`` count what happened, and the programs
   are still two, one shape each, with the outputs they had.

Engines compile real XLA programs, so the model is tiny.
"""

import contextlib
import dataclasses
import time

import jax
import numpy as np
import pytest

from distributed_training_tpu.config import ServeConfig
from distributed_training_tpu.inference import Generator, SampleConfig
from distributed_training_tpu.models import get_model
from distributed_training_tpu.observability import trace as trace_lib
from distributed_training_tpu.serving import (
    FINISH_EOS,
    FINISH_TIMEOUT,
    Engine,
)
from distributed_training_tpu.serving import engine as engine_mod
from distributed_training_tpu.serving.request import FINISH_CANCELLED

VOCAB = 31
MAX_LEN = 48
# Mixed lengths: prompts of one to four chunks of 4, outputs from one token
# (finishes at its prefill) and two (its last token is the one in flight)
# up to eight.
PROMPT_LENS = (3, 9, 5, 13, 4, 9, 6)
NEW_TOKENS = (6, 1, 4, 8, 2, 5, 3)
SHAPES = {"two-slots-chunk4": dict(max_batch=2, prefill_chunk=4),
          "three-slots-one-chunk": dict(max_batch=3, prefill_chunk=16),
          "one-slot-chunk4": dict(max_batch=1, prefill_chunk=4)}


@pytest.fixture(scope="module")
def lm():
    model = get_model(
        "transformer_lm", num_classes=VOCAB, num_layers=1, num_heads=2,
        hidden_dim=16, max_len=MAX_LEN)
    params = model.init(jax.random.PRNGKey(0),
                        np.zeros((1, 8), np.int32))["params"]
    return model, params


@pytest.fixture(scope="module")
def prompts():
    rng = np.random.RandomState(11)
    return [rng.randint(0, VOCAB, size=n).astype(np.int32)
            for n in PROMPT_LENS]


@contextlib.contextmanager
def in_order():
    """Engines driven inside never have a step in flight."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(Engine, "_runs_in_order", lambda self, deadlines: True)
        yield


def make(lm, **kw):
    model, params = lm
    kw.setdefault("max_batch", 2)
    kw.setdefault("max_new_tokens", 8)
    kw.setdefault("prefill_chunk", 4)
    kw.setdefault("kv_page_size", 4)
    return Engine(model, params, ServeConfig(**kw))


def serve(eng, prompts, new_tokens=NEW_TOKENS):
    for p, n in zip(prompts, new_tokens):
        eng.submit(p, max_new_tokens=n)
    return {f.uid: f for f in eng.run()}


def streams(done):
    return {uid: f.tokens.tolist() for uid, f in done.items()}


def the_in_order_streams(lm, prompts, new_tokens=NEW_TOKENS, **kw):
    with in_order():
        eng = make(lm, **kw)
        out = serve(eng, prompts, new_tokens)
    assert eng.stats()["run_ahead_share"] == 0.0
    return out


# -- 1. tokens --------------------------------------------------------------
@pytest.mark.parametrize("temperature", [0.0, 0.8])
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_streams_are_the_in_order_engines(lm, prompts, shape,
                                          temperature):
    kw = dict(SHAPES[shape], temperature=temperature, top_k=8)
    want = the_in_order_streams(lm, prompts, **kw)
    eng = make(lm, **kw)
    got = serve(eng, prompts)
    assert streams(got) == streams(want)
    assert {u: f.finish_reason for u, f in got.items()} \
        == {u: f.finish_reason for u, f in want.items()}
    assert [got[u].tokens.size for u in sorted(got)] == list(NEW_TOKENS)
    eng.check_balanced()
    assert eng.idle and eng._in_flight is None
    # most steps had their successor launched before they were fetched
    assert eng.stats()["run_ahead_share"] > 0.6


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_greedy_streams_are_the_sequential_generators(lm, prompts, shape):
    model, params = lm
    got = serve(make(lm, temperature=0.0, **SHAPES[shape]), prompts)
    for uid, (p, n) in enumerate(zip(prompts, NEW_TOKENS)):
        gen = Generator(model, params, SampleConfig(
            max_new_tokens=n, temperature=0.0))
        np.testing.assert_array_equal(got[uid].tokens, gen(p)[0])


@pytest.mark.parametrize("prompt_len,chunks", [(6, 2), (11, 3), (4, 1)])
def test_a_final_chunk_in_flight_feeds_the_decode_lane(lm, prompt_len,
                                                        chunks):
    """The request's first token is on the device when the step after its
    final chunk is assembled: that step's lane reads the chunk's last valid
    row, and the stream is the Generator's all the same."""
    model, params = lm
    prompt = (np.arange(prompt_len, dtype=np.int32) * 5 + 2) % VOCAB
    eng = make(lm, max_batch=1, temperature=0.0)
    eng.submit(prompt, max_new_tokens=5)
    sources, done = [], []
    while not eng.idle:
        done.extend(eng.step())
        if eng._in_flight is not None:
            sources.append((eng._in_flight.program,
                            int(eng._in_flight.d_src[0])))
    last_rows = prompt_len - 4 * (chunks - 1)
    # in flight after each call: the later chunks, then the decode step fed
    # from the final chunk's last valid row, then decode steps fed from the
    # step before
    assert all(s == ("fused", engine_mod._SRC_HOST)
               for s in sources[:chunks - 1])
    assert sources[chunks - 1] == (
        "decode", engine_mod._SRC_CHUNK + last_rows - 1)
    assert sources[chunks:] == [("decode", engine_mod._SRC_NXT)] * 3
    gen = Generator(model, params, SampleConfig(max_new_tokens=5,
                                                temperature=0.0))
    np.testing.assert_array_equal(done[0].tokens, gen(prompt)[0])


@pytest.mark.parametrize("prefix_cache", [False, True])
@pytest.mark.parametrize("temperature", [0.0, 0.8])
def test_an_eos_inside_the_flight(lm, prompts, prefix_cache,
                                  temperature):
    """The host cannot foresee an EOS: the step ahead was assembled with
    the request in it. Its row is dropped, its pages are released after
    that step has landed, and nothing else moves."""
    kw = dict(max_batch=2, temperature=temperature, top_k=8,
              prefix_cache=prefix_cache)
    new = (8,) * len(prompts)
    free_run = the_in_order_streams(lm, prompts, new, **kw)
    eos = next(toks[at] for toks in streams(free_run).values()
               for at in (2, 3, 4) if toks[at] not in toks[:at])
    want = the_in_order_streams(lm, prompts, new,
                                eos_id=eos, **kw)
    assert any(f.finish_reason == FINISH_EOS for f in want.values())

    eng = make(lm, eos_id=eos, **kw)
    dropped, held = [], []
    commit = eng._commit_step

    def spy(step, toks, accepts, t):
        dropped.append(sum(eng._slot_seq[q.slot] is not q
                           for q in step.decoding))
        held.append(len(step.held))
        return commit(step, toks, accepts, t)

    eng._commit_step = spy
    got = serve(eng, prompts, new)
    assert streams(got) == streams(want)
    assert {u: f.finish_reason for u, f in got.items()} \
        == {u: f.finish_reason for u, f in want.items()}
    # an EOS was met mid-flight: a row nobody waits for, pages held back
    assert sum(dropped) >= 1 and sum(held) >= 1
    assert eng.stats()["tokens_emitted"] \
        == sum(f.tokens.size for f in got.values())
    eng.check_balanced()
    assert eng.idle


# -- 2. what changes the world lands the flight first ------------------------
def in_flight_after(eng, calls):
    for _ in range(calls):
        eng.step()
    assert eng._in_flight is not None
    return eng


def test_a_cancel_lands_the_flight_first(lm, prompts):
    want = the_in_order_streams(lm, prompts[:2], (8, 8))
    eng = make(lm)
    a = eng.submit(prompts[0], max_new_tokens=8)
    b = eng.submit(prompts[1], max_new_tokens=8)
    in_flight_after(eng, 4)
    seated = len(eng.scheduler.sequence(0).tokens)
    eng.cancel(a.uid)
    assert eng.step() == []                   # lands, cancels nothing yet
    assert eng._in_flight is None
    assert len(eng.scheduler.sequence(0).tokens) == seated + 1
    fins = eng.step()                         # nothing in flight: cancels
    assert [f.finish_reason for f in fins] == [FINISH_CANCELLED]
    assert fins[0].tokens.tolist() \
        == want[a.uid].tokens.tolist()[:seated + 1]
    rest = {f.uid: f for f in eng.run()}
    assert rest[b.uid].tokens.tolist() == want[b.uid].tokens.tolist()
    eng.check_balanced()


def test_an_expired_deadline_lands_the_flight_first(lm, prompts):
    eng = make(lm, deadline_ms=600000.0)
    a = eng.submit(prompts[0], max_new_tokens=8)
    eng.submit(prompts[1], max_new_tokens=8)
    in_flight_after(eng, 4)
    seq = eng.scheduler.sequence(0)
    assert seq.request.uid == a.uid
    emitted = len(seq.tokens)
    # what waiting out the deadline would do
    seq.request = dataclasses.replace(
        seq.request, deadline_t=time.perf_counter() - 1.0)
    fins = eng.step()          # lands the flight; the landing evicts it
    assert eng._in_flight is None
    assert [(f.uid, f.finish_reason) for f in fins] \
        == [(a.uid, FINISH_TIMEOUT)]
    assert fins[0].tokens.size == emitted + 1
    eng.run()
    eng.check_balanced()


@pytest.mark.parametrize("temperature", [0.0, 0.8])
def test_a_preemption_lands_the_flight_first(lm, prompts,
                                             temperature):
    kw = dict(max_batch=1, num_tiers=2, temperature=temperature, top_k=8)

    def drive(eng, calls_before):
        low = eng.submit(prompts[0], priority=1, max_new_tokens=8)
        for _ in range(calls_before):
            eng.step()
        high = eng.submit(prompts[1], priority=0, max_new_tokens=4)
        return low, high

    with in_order():
        oracle = make(lm, **kw)
        low, high = drive(oracle, 5)    # five steps delivered, then preempts
        oracle.step()
        assert oracle.stats()["requests_preempted"] == 1
        want = {f.uid: f for f in oracle.run()}

    eng = make(lm, **kw)
    low, high = drive(eng, 4)           # four delivered, the fifth in flight
    assert eng._in_flight is not None
    eng.step()                          # lands it; the preemption is due
    assert eng.stats()["requests_preempted"] == 0
    assert eng._in_flight is None and eng._preempt_due
    eng.step()                          # nothing in flight: preempts
    assert eng.stats()["requests_preempted"] == 1
    got = {f.uid: f for f in eng.run()}
    assert streams(got) == streams(want)
    assert eng.stats()["preempted_token_recompute"] \
        == oracle.stats()["preempted_token_recompute"]
    eng.check_balanced()


def test_an_armed_swap_lands_the_flight_first(lm, prompts):
    model, params = lm
    params2 = model.init(jax.random.PRNGKey(5),
                         np.zeros((1, 8), np.int32))["params"]

    def drive(eng, calls_before):
        eng.submit(prompts[3], max_new_tokens=8)
        for _ in range(calls_before):
            eng.step()
        eng.arm_swap(params2, epoch=1)

    with in_order():
        oracle = make(lm, max_batch=1)
        drive(oracle, 6)           # six steps on the old weights
        want = {f.uid: f for f in oracle.run()}

    eng = make(lm, max_batch=1)
    drive(eng, 5)                  # five delivered, the sixth in flight
    assert eng._in_flight is not None
    eng.step()                     # lands it on the old weights
    assert eng.weights_epoch == -1 and eng.phase == "swapping"
    assert eng._in_flight is None
    eng.step()                     # the barrier, then the new weights
    assert eng.weights_epoch == 1
    got = {f.uid: f for f in eng.run()}
    assert streams(got) == streams(want)


@pytest.mark.parametrize("temperature", [0.0, 0.8])
def test_a_drafter_never_runs_ahead(lm, prompts, temperature):
    kw = dict(max_batch=2, temperature=temperature, top_k=8)
    want = serve(make(lm, **kw), prompts)
    eng = make(lm, spec_k=2, **kw)
    for p, n in zip(prompts, NEW_TOKENS):
        eng.submit(p, max_new_tokens=n)
    got = {}
    while not eng.idle:
        got.update((f.uid, f) for f in eng.step())
        assert eng._in_flight is None
    assert streams(got) == streams(want)
    assert eng.stats()["run_ahead_share"] == 0.0


# -- 3. what callers see ------------------------------------------------------
def test_idle_is_false_while_a_step_is_undelivered(lm, prompts):
    """A lone request that meets an EOS: when that lands nothing is
    queued or seated, but the step launched ahead of it has not been
    delivered — ``run()`` goes on until it has."""
    kw = dict(max_batch=1, temperature=0.8, top_k=8)
    toks = streams(the_in_order_streams(
        lm, prompts[:1], (8,), **kw))[0]
    at = next(i for i in range(1, 8) if toks[i] not in toks[:i])
    eng = make(lm, eos_id=toks[at], **kw)
    eng.submit(prompts[0], max_new_tokens=8)
    fins = []
    while not fins:
        fins = eng.step()
    assert fins[0].finish_reason == FINISH_EOS
    assert fins[0].tokens.tolist() == toks[:at + 1]
    assert eng.scheduler.num_active == 0 and len(eng.queue) == 0
    assert eng._in_flight is not None and not eng.idle
    assert eng.run() == [] and eng.idle
    eng.check_balanced()


@pytest.mark.parametrize("calls_before", [0, 1, 3, 6])
def test_drain_delivers_every_token(lm, prompts, calls_before):
    want = the_in_order_streams(lm, prompts)
    eng = make(lm)
    for p, n in zip(prompts, NEW_TOKENS):
        eng.submit(p, max_new_tokens=n)
    done = []
    for _ in range(calls_before):
        done.extend(eng.step())
    done.extend(eng.drain())
    assert streams({f.uid: f for f in done}) == streams(want)
    assert eng.stats()["drained"] is True and eng.idle
    eng.check_balanced()


@pytest.mark.parametrize("n_new", [1, 2, 5])
def test_ahead_counts_what_happened(lm, prompts, n_new):
    """One request, one chunk: ``n_new`` calls deliver its tokens, and all
    but the last had the next step launched before their fetch."""
    eng = make(lm, max_batch=1, prefill_chunk=16)
    eng.submit(prompts[1], max_new_tokens=n_new)
    t0 = time.perf_counter()
    calls = 0
    while not eng.idle:
        eng.step()
        calls += 1
    assert calls == n_new
    its = [s for s in trace_lib.host_spans(t0, time.perf_counter())
           if s.name == "serve.iteration"
           and s.attrs.get("program") != "idle"]
    assert [s.attrs["ahead"] for s in its] == [1] * (n_new - 1) + [0]
    assert eng.stats()["run_ahead_share"] == (n_new - 1) / n_new
    dispatches = [s for s in trace_lib.host_spans(t0, time.perf_counter())
                  if s.name == "serve.dispatch"]
    # the first call launches two steps in its one dispatch span, the last
    # launches nothing
    assert len(dispatches) == max(n_new - 1, 1)
    eng.reset_stats()
    assert eng.stats()["run_ahead_share"] == 0.0


@pytest.mark.parametrize("spec_k", [0, 2])
def test_two_programs_of_one_shape_with_the_outputs_they_had(lm, prompts,
                                                             spec_k):
    eng = make(lm, spec_k=spec_k, temperature=0.0)
    decode, fused = eng._decode, eng._fused
    seen = {"decode": set(), "fused": set()}

    def wrapped_decode(*a):
        cache, nxt, acc = decode(*a)
        seen["decode"].add((len(a), nxt.shape, acc.shape))
        return cache, nxt, acc

    def wrapped_fused(*a):
        cache, nxt, acc, c_sampled = fused(*a)
        seen["fused"].add((len(a), nxt.shape, acc.shape, c_sampled.shape))
        return cache, nxt, acc, c_sampled

    eng._decode, eng._fused = wrapped_decode, wrapped_fused
    # the harness's warm-up request compiles both before anything else runs
    eng.submit(np.arange(1, 9, dtype=np.int32), max_new_tokens=3)
    eng.run()
    eng._decode, eng._fused = decode, fused
    assert eng.compiled_programs() == {"fused": 1, "decode": 1}
    eng._decode, eng._fused = wrapped_decode, wrapped_fused
    serve(eng, prompts)
    eng._decode, eng._fused = decode, fused
    assert eng.compiled_programs() == {"fused": 1, "decode": 1}
    w = spec_k + 1
    assert seen["decode"] == {(5, (2, w), (2,))}
    assert seen["fused"] == {(5, (2, w), (2,), (4,))}
