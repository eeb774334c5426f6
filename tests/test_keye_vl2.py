"""The Keye-VL-2.0 family's language model at a toy size on the CPU: the
program (the plain forward; chunked prefill, then decode through the paged
key/value and index pools; through ``Engine``) against the plain reference
of the benchmark; the three position streams; the shares of an expert layer
against the uncut layer. The router's cases: tests/test_moe.py; the grouped
kernel form: tests/test_masked_attention_kernel.py; the family's counts and
the toy cell through the harness: tests/benchmark/test_bm_keye_vl2.py.

Tolerances: both sides compute in float32 at ``highest``; they differ by
the order of their sums (blocks of keys with an online softmax against one
softmax, experts by sorted pairs against a scan), a few 1e-7 on logits of
~0.5 — 3e-6 holds that with room and is 1e4 under what a wrong key, expert
or rotary section moves (>= 1e-2)."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_deepseek_v32 import paged_logits   # chunks, then a row at a time

from benchmark import weights
from benchmark.families import keye_vl2 as family
from benchmark.reference import keye_vl2 as ref
from distributed_training_tpu.config import ServeConfig
from distributed_training_tpu.models import keye_vl2, moe
from distributed_training_tpu.models.gpt import init_decode_cache
from distributed_training_tpu.observability import trace
from distributed_training_tpu.serving.engine import Engine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "tests", "benchmark", "data",
                       "toy-keye.json")) as _fh:
    TOY = json.load(_fh)       # 2 layers, 4 heads over 2 of 16, top 8 keys
FP32 = {"dtype": "fp32", "logits_dtype": "fp32"}
LENGTH = 48     # every sequence here is this long: one compile a program


@pytest.fixture(autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


def toy_model(**kw):
    return family.build_model(TOY, FP32).clone(**kw)


def toy_params(seed, cfg=TOY):
    flat = weights.make(seed, ref.param_shapes(cfg), jnp.float32)
    return flat, weights.unflatten(flat)


@jax.jit
def ref_logits(flat, seq):
    """The reference's logits ``[LENGTH, rows]`` of one sequence."""
    return ref.forward(flat, seq[None], TOY)[0]


# -- the position streams ----------------------------------------------------

def test_rotary_angles_honour_the_sections():
    """Sections (2, 3, 3) of a 16-wide head: frequencies 0-1 turn by the
    temporal stream, 2-4 by the height, 5-7 by the width."""
    streams = jnp.asarray([[[3, 9]], [[5, 1]], [[7, 2]]])     # [3, 1, 2]
    got = np.asarray(keye_vl2.rotary_angles(streams, 16, 100.0, (2, 3, 3)))
    freq = 100.0 ** (-np.arange(8) / 8)
    by = np.array([[3, 3, 5, 5, 5, 7, 7, 7], [9, 9, 1, 1, 1, 2, 2, 2]])
    np.testing.assert_allclose(got[0], by * freq, rtol=1e-6)
    # the index head turns by the temporal stream alone; one stream is 1-D
    np.testing.assert_allclose(
        keye_vl2.rotary_angles(streams, 16, 100.0),
        keye_vl2.rotary_angles(streams[0], 16, 100.0))
    with pytest.raises(ValueError, match="sum"):
        keye_vl2.rotary_angles(streams, 16, 100.0, (2, 3, 4))


@pytest.fixture(scope="module")
def forwards():
    """One sequence through the plain forward under text positions, three
    equal streams and three unequal ones; the reference under the same."""
    with jax.default_matmul_precision("highest"):
        flat, params = toy_params(3)
        toks = jnp.asarray(np.random.default_rng(3).integers(
            0, 64, (2, LENGTH)), jnp.int32)
        text = jnp.broadcast_to(jnp.arange(LENGTH), (2, LENGTH))
        # an image's patches: the temporal stream stands, height and width
        # walk a 6-wide grid
        unequal = jnp.stack([jnp.minimum(text, 10), text // 6, text % 6])
        apply = jax.jit(toy_model().apply)
        got = {name: np.asarray(apply({"params": params}, toks, pos))
               for name, pos in (("text", text),
                                 ("equal", jnp.stack([text] * 3)),
                                 ("unequal", unequal))}
        want = {name: np.asarray(jax.jit(
            lambda f, t, p: ref.forward(f, t, TOY, positions=p))(
                flat, toks, pos))
            for name, pos in (("text", None), ("unequal", unequal))}
    return got, want


def test_the_plain_forward_gives_the_references_logits(forwards):
    got, want = forwards
    assert np.abs(want["text"]).max() > 0.1
    np.testing.assert_allclose(got["text"], want["text"], atol=3e-6)


def test_three_equal_streams_are_one_dimensional_rotary(forwards):
    got, _ = forwards
    np.testing.assert_array_equal(got["equal"], got["text"])


def test_three_unequal_streams_give_the_references_logits(forwards):
    got, want = forwards
    np.testing.assert_allclose(got["unequal"], want["unequal"], atol=3e-6)
    # and the streams matter: far more than rounding
    assert np.abs(got["unequal"] - got["text"]).max() > 1e-2


# -- the program against the reference ---------------------------------------

@pytest.mark.parametrize("key_block,expert_rows,page,chunk,prompt", [
    (8, 4, 4, 16, 29),        # several key blocks; experts overflow
    (1024, 128, 8, 12, 39)])  # the cell's own block sizes; pages of 8
def test_chunked_prefill_then_paged_decode_gives_the_references_logits(
        key_block, expert_rows, page, chunk, prompt):
    """Contexts run to 48 keys where a query may attend 8: the selection
    bites in the chunk lane (from the chunk's ninth row on) and at every
    decoded token."""
    flat, params = toy_params(11 + key_block)
    seq = np.random.default_rng(prompt).integers(0, 64, LENGTH).astype(
        np.int32)
    model = toy_model(key_block=key_block, expert_block_rows=expert_rows)
    assert model.paged_lane(chunk) == "masked-blocks"
    assert model.paged_lane(1) == "sparse-gather"
    got = paged_logits(model, params, seq, page, chunk, prompt)
    want = np.asarray(ref_logits(flat, jnp.asarray(seq)))
    assert np.abs(want).max() > 0.1
    np.testing.assert_allclose(got, want, atol=3e-6)


def test_the_cache_is_two_pools_a_layer_of_whole_lane_tiles():
    _, params = toy_params(0)
    paged = toy_model(kv_page_size=4, kv_pages=9)
    cache = init_decode_cache(paged, params, batch_size=1)
    assert {k: {n: a.shape for n, a in v["attn"].items()}
            for k, v in cache.items()} == {
        f"layer{i}": {"kv_pages": (36, 2 * 2 * 16),
                      "index_pages": (36, 128)} for i in range(2)}
    with pytest.raises(ValueError, match="compute dtype"):
        toy_model(kv_dtype="int8").apply({"params": params},
                                         jnp.zeros((1, 4), jnp.int32))


def run_engine(seed, lengths, *, max_new=8, **clone):
    flat, params = toy_params(seed)
    engine = Engine(toy_model(**clone), params, ServeConfig(
        max_batch=3, max_len=48, max_new_tokens=max_new, kv_page_size=4,
        kv_pages=36, prefill_chunk=16, temperature=0.0, spec_k=0,
        prefix_cache=False))
    rng = np.random.default_rng(seed)
    prompts = {}
    for n in lengths:
        p = rng.integers(0, 64, n).astype(np.int32)
        prompts[engine.submit(p, max_new_tokens=max_new).uid] = p
    t0 = trace.host_spans()[-1].t1 if trace.host_spans() else 0.0
    done = engine.run()
    engine.pool.check_balanced()
    spans = [s for s in trace.host_spans(t0) if s.name == "serve.iteration"]
    return engine, flat, prompts, done, spans


@pytest.fixture(scope="module")
def served():
    with jax.default_matmul_precision("highest"):
        return run_engine(5, (24, 31, 40), key_block=8,
                          expert_block_rows=4)


def test_the_engine_serves_what_the_reference_puts_first(served):
    """Requests of mixed length through ``Engine`` (continuous batching,
    fused chunk + decode steps): every served token is the reference's
    first choice at its position, to rounding."""
    engine, flat, prompts, done, _ = served
    assert engine.lane_formulation == {"decode": "sparse-gather",
                                       "chunk": "masked-blocks"}
    assert len(done) == 3 and all(len(f.tokens) == 8 for f in done)
    for f in done:
        seq = np.concatenate([prompts[f.uid], np.asarray(f.tokens,
                                                         np.int32)])
        padded = np.zeros(LENGTH, np.int32)
        padded[:seq.size] = seq
        logits = np.asarray(ref_logits(flat, jnp.asarray(padded)))
        at = np.arange(seq.size - 9, seq.size - 1)
        assert (logits[at].max(-1) - logits[at, seq[-8:]]).max() < 1e-5


def test_the_iterations_counters(served):
    """Host arithmetic over the decoding slots: a query reads 8 of its live
    rows, and the lane scores the index keys of each decoding slot's whole
    page budget (12 pages of 4 rows)."""
    engine = served[0]
    spans = [s.attrs for s in served[4]]
    assert engine.model.index_rows_scored(30, 48) == 48
    assert engine.model.attended_rows(30) == 8
    decoding = [a for a in spans if a["kv_rows_live"]]
    assert decoding
    for a in decoding:
        slots = a["kv_rows_selected"] // 8
        assert a["index_rows_scored"] == 48 * slots
        assert a["index_rows_scored"] > a["kv_rows_live"] >= 24 * slots
    assert all(a["index_rows_scored"] == 0 for a in spans
               if not a["kv_rows_live"])
    # the expert layer's three counters come back with the tokens
    assert all(a["expert_rows"] >= a["expert_rows_max"] >= 1
               and 1 <= a["experts_hit"] for a in spans
               if a["program"] != "idle")


# -- the shares add up -------------------------------------------------------

WIDE = {**TOY, "num_experts": 128, "num_local_experts": 128,
        "n_routed_experts": 128, "num_experts_per_tok": 8,
        "published": {**TOY["published"], "num_experts": 128}}


def share(flat, first, count):
    """Layer 1's expert leaves cut to ``first .. first + count`` of the 128,
    and the configuration of a chip that holds them."""
    p = {k[len("layer1/ffn/"):]: v for k, v in flat.items()
         if k.startswith("layer1/ffn/")}
    for w in ("w1", "w3", "w2"):
        p[w] = p[w][first:first + count]
    held = {"num_experts": count, "num_local_experts": count,
            "n_routed_experts": count}
    return p, {**WIDE, **held,
               "assumed": {**WIDE["assumed"], "first_held_expert": first}}


def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """Four chips hold 32 of the 128 experts each: the reference's partial
    sums are its uncut layer (there is no shared expert to count once), and
    the program's share of one chip is the reference's share of it."""
    flat, _ = toy_params(21, WIDE)
    x = jnp.asarray(np.random.default_rng(2).normal(size=(19, 64)),
                    jnp.float32)
    whole_p, whole_cfg = share(flat, 0, 128)
    family.validate(whole_cfg)
    whole = ref.expert_layer(x, whole_p, whole_cfg)
    parts = {}
    for first in (0, 32, 64, 96):
        p, cfg = share(flat, first, 32)
        family.validate(cfg)
        parts[first] = ref.expert_layer(x, p, cfg)
    assert float(jnp.abs(whole).max()) > 1e-3
    np.testing.assert_allclose(sum(parts.values()), whole, atol=2e-6)
    layer = moe.HeldExpertsMlp(
        num_experts=128, held=(64, 32), hidden_dim=32, top_k=8,
        scoring="softmax", shared_experts=0, block_rows=3)
    got = jax.jit(layer.apply)(
        {"params": weights.unflatten(share(flat, 64, 32)[0])}, x)
    np.testing.assert_allclose(got, parts[64], atol=2e-6)
