"""The Ling hybrid model (``models/ling_hybrid.py``) on the CPU at toy
widths: the KDA layer's chunk form against the token-by-token recurrence,
the decode kernel against the one-step form, prefill then decode through
both kinds of cache against the plain forward and the float32 reference,
and the engine with per-slot state: reseated slots, fused steps,
preemption and the typed refusals. The harness side:
tests/benchmark/test_bm_ling_hybrid.py."""

import json
import os
import time

import flax
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import weights
from benchmark.families import ling_hybrid as family
from benchmark.reference import ling_hybrid as ref
from distributed_training_tpu.config import ServeConfig
from distributed_training_tpu.models.gpt import init_decode_cache
from distributed_training_tpu.models.ling_hybrid import SUB_CHUNK, kda_chunk
from distributed_training_tpu.observability import trace as trace_lib
from distributed_training_tpu.ops.kda_decode import kda_decode, kda_step
from distributed_training_tpu.parallel.ring_attention import PagedKV
from distributed_training_tpu.serving.engine import (
    Engine,
    RecurrentStateError,
)

HERE = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(HERE, "benchmark", "data", "toy-ling.json")) as _fh:
    TOY = json.load(_fh)
FP32 = {"dtype": "fp32", "logits_dtype": "fp32"}


@pytest.fixture(autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


def recurrence(q, k, v, g, beta, state):
    """The gated delta rule token by token: [T, H, d] inputs."""
    outs = []
    for t in range(q.shape[0]):
        o, state = kda_step(q[t], k[t], v[t], g[t], beta[t], state)
        outs.append(o)
    return jnp.stack(outs), state


def kda_inputs(t, h=2, dk=16, dv=8, seed=0):
    ks = jax.random.split(jax.random.key(seed), 6)
    q = jax.random.normal(ks[0], (t, h, dk))
    k = jax.random.normal(ks[1], (t, h, dk))
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (t, h, dv))
    g = -5.0 * jax.nn.sigmoid(jax.random.normal(ks[3], (t, h, dk)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (t, h)))
    state = jax.random.normal(ks[5], (h, dk, dv))
    return q, k, v, g, beta, state


# -- the chunk form --------------------------------------------------------

@pytest.mark.parametrize("carried", [False, True], ids=["zero", "carried"])
@pytest.mark.parametrize("t", [1, 5, SUB_CHUNK, SUB_CHUNK + 1, 40])
def test_the_chunk_form_follows_the_recurrence(t, carried):
    """Across sub-chunk boundaries, from a zero state and from a carried
    one: outputs and the state at the end."""
    q, k, v, g, beta, state = kda_inputs(t, seed=t)
    if not carried:
        state = jnp.zeros_like(state)
    want_o, want_s = recurrence(q, k, v, g, beta, state)
    got_o, got_s = jax.jit(kda_chunk)(q, k, v, g, beta, state)
    np.testing.assert_allclose(got_o, want_o, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(got_s, want_s, rtol=1e-4, atol=1e-5)


def test_two_chunks_carry_the_state_as_one_and_padding_is_inert():
    q, k, v, g, beta, state = kda_inputs(40, seed=3)
    whole_o, whole_s = kda_chunk(q, k, v, g, beta, state)
    o1, s1 = kda_chunk(q[:23], k[:23], v[:23], g[:23], beta[:23], state)
    # the second part padded to 24 rows: g = 0 and beta = 0 change nothing
    pad = [(0, 7), (0, 0), (0, 0)]
    o2, s2 = kda_chunk(jnp.pad(q[23:], pad), jnp.pad(k[23:], pad),
                       jnp.pad(v[23:], pad), jnp.pad(g[23:], pad),
                       jnp.pad(beta[23:], pad[:2]), s1)
    np.testing.assert_allclose(jnp.concatenate([o1, o2[:17]]), whole_o,
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(s2, whole_s, rtol=1e-4, atol=1e-5)


# -- the decode kernel -----------------------------------------------------

@pytest.mark.parametrize("live", [
    [False, True, True, False, True], [False] * 5, [True] * 5],
    ids=["mixed", "none", "all"])
def test_the_decode_kernel_matches_one_step_and_leaves_idle_slots(live):
    """Interpret mode: the live rows' slots advance as ``kda_step`` does;
    every other slot's state is bit-for-bit as it was, and a row that is
    not live reads zero."""
    b, h, d, n_slots = 5, 2, 128, 7
    q, k, v, g, beta, _ = kda_inputs(b, h=h, dk=d, dv=d, seed=11)
    state = jax.random.normal(jax.random.key(12), (n_slots, h, d, d))
    slots = jnp.array([3, 0, 6, 1, 2], jnp.int32)
    live = jnp.array(live)
    o, got = kda_decode(q, k, v, g, beta, state, slots, live,
                        interpret=True)
    want_o, want_s = kda_step(q, k, v, g, beta, state[slots])
    want = state.at[slots].set(
        jnp.where(live[:, None, None, None], want_s, state[slots]))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    untouched = np.setdiff1d(np.arange(n_slots), np.asarray(slots)[live])
    np.testing.assert_array_equal(np.asarray(got)[untouched],
                                  np.asarray(state)[untouched])
    np.testing.assert_allclose(
        o, jnp.where(live[:, None, None], want_o, 0.0), rtol=1e-5, atol=1e-5)


# -- the model through both caches -----------------------------------------

@pytest.fixture(scope="module")
def toy():
    model = family.build_model(TOY, FP32)
    flat = weights.make(7, ref.param_shapes(TOY), jnp.float32)
    return model, weights.unflatten(flat), flat


def test_prefill_then_decode_logits_match_the_full_forward(toy):
    """A 13-token prompt as one chunk of 16 into slot 2 of 3, then five
    decoded tokens, against the plain forward and the reference over all
    18: the latent layer reads its pages, the KDA layers their slabs, and
    slots 0 and 1 are never written."""
    model, params, flat = toy
    seq = np.random.RandomState(4).randint(1, 64, size=18).astype(np.int32)
    m = model.clone(cache_len=32, kv_page_size=4, kv_pages=20, state_slots=3)
    cache = init_decode_cache(m, params, batch_size=1)
    table = np.zeros((3, 8), np.int32)
    table[2] = np.arange(1, 9)

    def apply(cache, toks, pos, valid, tables, slots):
        logits, v = m.apply(
            {"params": params, "cache": cache}, toks, positions=pos,
            decode=True, mutable=["cache", "counters"],
            pages=PagedKV(tables, pos, valid), slots=slots)
        return logits, v["cache"]

    apply = jax.jit(apply)
    c_tok = np.zeros((1, 16), np.int32)
    c_tok[0, :13] = seq[:13]
    logits, cache = apply(cache, c_tok, np.arange(16)[None],
                          (np.arange(16) < 13)[None], table[2:], np.array([2]))
    got = [logits[0, :13]]
    for i in range(13, 18):
        toks = np.zeros((3, 1), np.int32)
        toks[2, 0] = seq[i]
        pos = np.full((3, 1), i, np.int32)
        valid = np.array([[False], [False], [True]])
        logits, cache = apply(cache, toks, pos, valid, table,
                              np.arange(3, dtype=np.int32))
        got.append(logits[2])
    got = jnp.concatenate(got)
    plain = jax.jit(model.apply)({"params": params}, seq[None])[0]
    padded = np.zeros((1, 64), np.int32)
    padded[0, :18] = seq
    want = _ref_logits(flat, jnp.asarray(padded))[0, :18]
    np.testing.assert_allclose(got, plain, rtol=1e-4, atol=2e-5)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=2e-5)
    for leaf in jax.tree.leaves(cache):
        if leaf.shape[0] == 3:                     # a per-slot slab
            assert not np.asarray(leaf[:2]).any()
            assert np.asarray(leaf[2]).any()


# -- the engine --------------------------------------------------------------

def reference_argmax_gap(flat, prompt, served):
    """How far each served token's reference logit lies under the
    reference's best (0 where it is the reference's greedy token)."""
    seq = np.concatenate([prompt, np.asarray(served, np.int32)])
    padded = np.zeros((1, 64), np.int32)
    padded[0, :seq.size] = seq
    logits = _ref_logits(flat, jnp.asarray(padded))[0]
    rows = np.arange(prompt.size - 1, seq.size - 1)
    lg = np.asarray(logits)[rows]
    return lg.max(-1) - lg[np.arange(rows.size), seq[rows + 1]]


@jax.jit
def _ref_logits(flat, toks):
    return ref.forward(flat, toks, TOY)


def serve_cfg(**kw):
    base = dict(max_batch=3, max_len=48, kv_page_size=4, kv_pages=40,
                prefill_chunk=16, temperature=0.0, max_new_tokens=8)
    return ServeConfig(**{**base, **kw})


def prompts(n, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(1, 64, size=rng.randint(3, 30)).astype(np.int32)
            for _ in range(n)]


def test_the_engine_serves_the_references_greedy_tokens(toy):
    """Five requests over three slots: slots reseated (each new seat
    starts from zero state), prompts of one and two chunks, chunks fused
    with decoding slots; every served token is the reference's greedy
    one. Each delivered step names the slots whose state its decode lane
    carried."""
    model, params, flat = toy
    t0 = time.perf_counter()
    eng = Engine(model, params, serve_cfg())
    asked = prompts(5)
    for p in asked:
        eng.submit(p, max_new_tokens=7)
    done = {f.uid: f for f in eng.run()}
    eng.check_balanced()
    for uid, p in enumerate(asked):
        assert len(done[uid].tokens) == 7
        assert reference_argmax_gap(flat, p, done[uid].tokens).max() < 1e-4
    spans = [s for s in trace_lib.host_spans(t0, time.perf_counter())
             if s.name == "serve.iteration" and s.attrs["program"] != "idle"]
    assert {s.attrs["state_slots"] for s in spans} >= {1, 2, 3}
    assert any(s.attrs["program"] == "fused" and s.attrs["state_slots"] >= 1
               for s in spans)
    alloc = [s for s in trace_lib.host_spans(t0, time.perf_counter())
             if s.name == "setup.cache_alloc"][-1]
    # 3 slots x 2 KDA layers x (2 x 128 x 128 float32 + 3 x 768 float32)
    assert alloc.attrs["state_bytes"] == 3 * 2 * (2 * 128 * 128 + 3 * 768) * 4


def test_a_preempted_request_resumes_with_the_same_greedy_tokens(toy):
    """A low-tier request preempted mid-decode by a high-tier one
    re-prefills from position 0 when it is seated again (its state is not
    kept), and both serve the reference's greedy tokens."""
    model, params, flat = toy
    eng = Engine(model, params, serve_cfg(max_batch=1, num_tiers=2))
    low_p, high_p = prompts(2, seed=5)
    low = eng.submit(low_p, priority=1, max_new_tokens=8)
    for _ in range(4):
        eng.step()
    assert eng.scheduler.sequence(0).tokens
    high = eng.submit(high_p, priority=0, max_new_tokens=5)
    done = {f.uid: f for f in eng.run()}
    eng.check_balanced()
    assert eng.stats()["requests_preempted"] >= 1
    for req, p, n in ((low, low_p, 8), (high, high_p, 5)):
        assert len(done[req.uid].tokens) == n
        assert reference_argmax_gap(flat, p, done[req.uid].tokens).max() \
            < 1e-4


def test_the_served_state_stays_float32_under_bfloat16_weights():
    """The configuration states a float32 KDA state beside bfloat16
    weights and activations. The gap comparison that decides a cell's
    ``correct`` cannot see a state kept in bfloat16 (the seeded weights'
    gates forget a rounding within a few tokens), so the dtype is held
    here: the engine's slabs of state are float32, its convolution tails
    the compute dtype."""
    model = family.build_model(TOY, {"dtype": "bf16", "logits_dtype": "bf16"})
    params = weights.unflatten(
        weights.make(7, ref.param_shapes(TOY), jnp.bfloat16))
    eng = Engine(model, params, serve_cfg())
    flat = flax.traverse_util.flatten_dict(flax.core.unfreeze(eng._cache))
    kinds = {k[-1]: v.dtype for k, v in flat.items()}
    assert kinds["kda_state"] == jnp.float32
    assert kinds["kda_conv"] == jnp.bfloat16


@pytest.mark.parametrize("kw,message", [
    ({"prefix_cache": True}, "prefix cache"),
    ({"spec_k": 2}, "speculative")])
def test_a_model_with_recurrent_state_refuses_what_its_state_cannot_give(
        toy, kw, message):
    model, params, _ = toy
    with pytest.raises(RecurrentStateError, match=message):
        Engine(model, params, serve_cfg(**kw))
    assert issubclass(RecurrentStateError, ValueError)
