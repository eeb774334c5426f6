"""One packed host buffer a launch (serving/engine.py ``_StepLayout``,
docs/SERVING.md "The iteration's order").

Everything the host hands a device step — the decode lane's tokens,
positions, valid rows and sources, the slots' RNG keys and page tables, and
for a fused step the chunk's three rows and its slot — travels as one int32
vector and one transfer, and is taken apart inside the compiled program.
Pinned here on the CPU:

1. **No field changes value on the way**: key words with their top bit set,
   pad ids, all-invalid rows, a ``_SRC_CHUNK + row`` source, the chunk's
   slot at either end.
2. **One transfer a launch**, and ``serve.dispatch``'s ``uploads`` counts
   the transfers really made.
3. **The step in flight reads a buffer the host never writes again**: a
   seat or a page crossing after the launch does not reach it (the CPU
   backend may alias host memory, so it would show here).
4. **Tokens**: greedy and sampled streams are those of the sequential
   ``Generator``'s decode (contiguous cache, one token a step) under the
   engine's ``fold_in(fold_in(seed, uid), position)`` keys; greedy against
   ``Generator`` itself is ``test_serving_run_ahead.py``'s, on this path.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from test_serving_run_ahead import (  # noqa: F401  (lm, prompts: fixtures)
    NEW_TOKENS,
    SHAPES,
    lm,
    make,
    prompts,
    serve,
    streams,
)

from distributed_training_tpu.inference.sampler import sample_token
from distributed_training_tpu.observability import trace as trace_lib
from distributed_training_tpu.serving import engine as engine_mod
from distributed_training_tpu.serving.engine import _StepLayout

PAD = 7


def fields(layout, rng, *, all_invalid):
    """Host values for every field, awkward ones among them."""
    b, w = layout.slots, layout.width
    d_tok = rng.randint(0, 50000, (b, w)).astype(np.int32)
    d_tok[0] = PAD
    d_pos = rng.randint(0, layout.pages * 16, (b, w)).astype(np.int32)
    d_valid = np.zeros((b, w), bool) if all_invalid \
        else rng.rand(b, w) < 0.5
    d_src = rng.randint(0, 3, (b,)).astype(np.int32)
    d_src[-1] = engine_mod._SRC_CHUNK + layout.chunk - 1
    keys = rng.randint(0, 2**32, (b, layout.key_words),
                       dtype=np.uint64).astype(np.uint32)
    keys[0] = 0xFFFFFFFF
    keys[-1, 0] = 2**31
    tables = rng.randint(0, 2**20, (b, layout.pages)).astype(np.int32)
    c_tok = rng.randint(0, 50000, (layout.chunk,)).astype(np.int32)
    c_tok[-2:] = PAD
    c_pos = np.arange(100, 100 + layout.chunk, dtype=np.int32)
    c_valid = np.arange(layout.chunk) < layout.chunk - 2
    return (d_tok, d_pos, d_valid, d_src, keys, tables), \
        (c_tok, c_pos, c_valid)


# -- 1. no field changes value ------------------------------------------------
@pytest.mark.parametrize("chunk_slot", [None, 0, -1],
                         ids=["decode", "chunk-slot-0", "chunk-slot-last"])
@pytest.mark.parametrize("all_invalid", [False, True],
                         ids=["mixed", "all-invalid"])
@pytest.mark.parametrize("width", [1, 3])
def test_pack_then_unpack_under_jit_is_bit_for_bit(width, all_invalid,
                                                   chunk_slot):
    layout = _StepLayout(slots=5, width=width, key_words=2, pages=6,
                         chunk=8)
    lane, chunk = fields(layout, np.random.RandomState(width),
                         all_invalid=all_invalid)
    d_tok, d_pos, d_valid, d_src, keys, tables = lane
    if chunk_slot is None:
        packed = layout.pack(*lane)
    else:
        chunk_slot %= layout.slots
        packed = layout.pack(*lane, chunk, chunk_slot)
    assert packed.dtype == np.int32
    assert packed.shape == (layout.size(chunk_slot is not None),)
    got_lane, got_chunk = jax.jit(layout.unpack)(jnp.asarray(packed))
    # as Engine._decode_step takes them: tok, pos, valid, rngs, tables, src
    for got, want in zip(got_lane,
                         (d_tok, d_pos, d_valid, keys, tables, d_src)):
        assert got.dtype == want.dtype and got.shape == want.shape
        np.testing.assert_array_equal(np.asarray(got), want)
    if chunk_slot is None:
        assert got_chunk is None
        return
    want_chunk = (*chunk, tables[chunk_slot][None], keys[chunk_slot])
    for got, want in zip(got_chunk, want_chunk, strict=True):
        assert got.dtype == want.dtype and got.shape == want.shape
        np.testing.assert_array_equal(np.asarray(got), want)


def test_a_buffer_of_another_size_is_refused():
    layout = _StepLayout(slots=2, width=1, key_words=2, pages=3, chunk=4)
    with pytest.raises(ValueError, match="fits neither program"):
        jax.jit(layout.unpack)(jnp.zeros((layout.size(False) + 1,),
                                         jnp.int32))


def test_the_engines_layout_follows_its_shapes(lm):
    eng = make(lm, spec_k=2, max_batch=3, prefill_chunk=4)
    assert eng._layout == _StepLayout(
        slots=3, width=3, key_words=eng._slot_rng.shape[1],
        pages=eng.pages_per_slot, chunk=4)


# -- 2. one transfer a launch -------------------------------------------------
@pytest.mark.parametrize("spec_k", [0, 2])
def test_uploads_counts_the_transfers_of_each_call(lm, prompts, spec_k):
    """A launch takes the parameters, the pool and the step before's own
    outputs as they lie on the device, and ONE array from the host."""
    eng = make(lm, spec_k=spec_k, temperature=0.0)
    decode, fused = eng._decode, eng._fused
    launched = []

    def watch(program, name):
        def call(params, cache, packed, prev_nxt, prev_sampled):
            assert packed.shape == (eng._layout.size(name == "fused"),)
            assert packed.dtype == np.int32
            # the rest lies on the device already
            assert isinstance(prev_nxt, jax.Array)
            assert isinstance(prev_sampled, jax.Array)
            launched.append(name)
            return program(params, cache, packed, prev_nxt, prev_sampled)
        return call

    eng._decode, eng._fused = watch(decode, "decode"), watch(fused, "fused")
    for p, n in zip(prompts, NEW_TOKENS):
        eng.submit(p, max_new_tokens=n)
    per_call = []
    while not eng.idle:
        before = len(launched)
        t0 = time.perf_counter()
        eng.step()
        spans = [s for s in trace_lib.host_spans(t0, time.perf_counter())
                 if s.name == "serve.dispatch"]
        made = launched[before:]
        # no launch, no span; else one span whose counter is the launches'
        assert len(spans) == bool(made)
        if made:
            assert spans[0].attrs["uploads"] == len(made)
        per_call.append(tuple(made))
    kinds = {name for call in per_call for name in call}
    assert kinds == {"decode", "fused"}
    # one launch a call, two where the call entered with nothing in flight
    assert set(map(len, per_call)) <= {0, 1, 2}
    if not spec_k:
        assert 2 in set(map(len, per_call))


# -- 3. the step in flight and the host's later writes -------------------------
@pytest.mark.parametrize("temperature", [0.0, 0.8])
def test_host_writes_after_the_launch_do_not_reach_the_step(lm, prompts,
                                                            temperature):
    kw = dict(max_batch=2, temperature=temperature, top_k=8)
    want = serve(make(lm, **kw), prompts)
    eng = make(lm, **kw)
    launch = eng._launch
    scribbled = []

    def launch_then_scribble(step, prev):
        made = launch(step, prev)
        # what a seat or a page crossing does, at its worst, while the
        # step is on its way; put back before the next launch reads it
        tables, keys = eng._tables.copy(), eng._slot_rng.copy()
        eng._tables[:] = 0
        eng._slot_rng[:] = 0xDEADBEEF
        jax.block_until_ready(step.nxt)
        eng._tables[:], eng._slot_rng[:] = tables, keys
        scribbled.append(step.program)
        return made

    eng._launch = launch_then_scribble
    got = serve(eng, prompts)
    assert {"decode", "fused"} <= set(scribbled)
    assert streams(got) == streams(want)
    eng.check_balanced()


# -- 4. tokens ----------------------------------------------------------------
def sequential_decode(model, params, prompt, n_new, key, cfg):
    """``Generator``'s decode — contiguous cache, one prefill, one token
    a step — under the engine's keys: ``fold_in(key, position)`` of the
    row that is sampled."""
    model = model.clone(cache_len=prompt.size + n_new)
    t = prompt.size
    logits, vars_out = model.apply(
        {"params": params}, prompt[None], positions=jnp.arange(t)[None],
        train=False, decode=True, mutable=["cache"])
    out = []
    for pos in range(t - 1, t - 1 + n_new):
        tok = sample_token(jax.random.fold_in(key, pos), logits[:, -1], cfg)
        out.append(int(tok[0]))
        logits, vars_out = model.apply(
            {"params": params, "cache": vars_out["cache"]}, tok[:, None],
            positions=jnp.full((1, 1), pos + 1), train=False, decode=True,
            mutable=["cache"])
    return out


@pytest.mark.parametrize("temperature", [0.0, 0.8])
@pytest.mark.parametrize("spec_k", [0, 2])
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_streams_are_the_sequential_decodes(lm, prompts, shape, spec_k,
                                            temperature):
    """Greedy and sampled. A key that lost a bit on its way through the
    buffer, or the wrong slot's key for a chunk, would sample another
    stream; a wrong table row or position another token."""
    model, params = lm
    seed = 2**31 - 5
    eng = make(lm, temperature=temperature, top_k=8, seed=seed,
               spec_k=spec_k, **SHAPES[shape])
    got = streams(serve(eng, prompts))
    base = jax.random.PRNGKey(seed)
    for uid, (p, n) in enumerate(zip(prompts, NEW_TOKENS)):
        assert got[uid] == sequential_decode(
            model, params, p, n, jax.random.fold_in(base, uid),
            eng.sample_cfg)
