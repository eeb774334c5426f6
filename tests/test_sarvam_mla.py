"""The ``sarvam_mla`` family's model (sarvam-105b: latent attention with no
query latent and no selection, an ungrouped sigmoid router) at a toy size on
the CPU: the program (chunked prefill, then decode through the paged latent
pool, through ``Engine``) against the plain reference of the benchmark; the
decode lane's shared-row kernel (interpret mode) against the gather lane and
through the model; one group of the router against a plain top-k; the shares
of an expert layer against the uncut layer; the DeepSeek model's tree
unchanged. The family's counts and the toy cell through the harness:
tests/benchmark/test_bm_sarvam_mla.py.

Tolerances: both sides compute in float32 at ``highest``; they differ in the
order of sums (per-head against absorbed, blocks of keys, an online softmax),
which at these widths moves a logit of size ~0.3 by 1e-6: ``atol`` 3e-6
where logits are compared, 2e-6 for one layer's output. A missing term (a
dropped rotary part, a key too many) moves them by 1e-2 and more."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_deepseek_v32 import paged_logits   # chunks, then a row at a time

from benchmark import weights
from benchmark.families import deepseek_v32 as dsv32_family
from benchmark.families import sarvam_mla as family
from benchmark.reference import sarvam_mla as ref
from distributed_training_tpu.config import ServeConfig
from distributed_training_tpu.models import deepseek_v32 as dsv32
from distributed_training_tpu.models import moe
from distributed_training_tpu.models.gpt import init_decode_cache
from distributed_training_tpu.observability import trace
from distributed_training_tpu.ops import paged_attention as pa
from distributed_training_tpu.serving.engine import Engine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(*parts):
    with open(os.path.join(ROOT, *parts)) as fh:
        return json.load(fh)


TOY = _load("tests", "benchmark", "data", "toy-sarvam.json")
REAL = _load("benchmark", "configs", "sarvam-105b-ep4.json")
DSV32_TOY = _load("tests", "benchmark", "data", "toy-dsv32.json")
# the toy at widths the shared-row kernel serves: a pool row of 128 + 16 ->
# 256 lanes whose first 128 are the value, 8 heads, pages of 8 float32 rows
TILE = {**TOY, "kv_lora_rank": 128, "qk_rope_head_dim": 16, "head_dim": 144,
        "q_head_dim": 32, "num_attention_heads": 8}
FP32 = {"dtype": "fp32", "logits_dtype": "fp32"}
LENGTH = 48     # every sequence here is this long: one compile a program


@pytest.fixture(autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


def toy_model(cfg=TOY, **kw):
    return family.build_model(cfg, FP32).clone(**kw)


def toy_params(seed, cfg=TOY):
    flat = weights.make(seed, ref.param_shapes(cfg), jnp.float32)
    return flat, weights.unflatten(flat)


def ref_logits(flat, seq, cfg=TOY):
    return np.asarray(jax.jit(
        lambda f, s: ref.forward(f, s[None], cfg)[0])(flat, jnp.asarray(seq)))


# -- the model's tree --------------------------------------------------------

def tree_of(model):
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))
    return {k: tuple(v.shape)
            for k, v in weights.flatten(shapes["params"]).items()}


def test_the_tree_has_no_query_latent_and_no_indexer():
    tree = tree_of(toy_model())
    assert tree == {k: tuple(v) for k, v in ref.param_shapes(TOY).items()}
    assert tree["layer0/attn/wq"] == (64, 4, 24)
    assert not [k for k in tree if "index" in k or "wq_" in k
                or "q_norm" in k]


def test_the_deepseek_models_tree_is_unchanged():
    """The same module with the query latent and the indexer: every leaf
    the DeepSeek reference names, and its two pools a layer."""
    from benchmark.reference import deepseek_v32 as dsv32_ref

    model = dsv32_family.build_model(DSV32_TOY, FP32)
    assert tree_of(model) == {
        k: tuple(v) for k, v in dsv32_ref.param_shapes(DSV32_TOY).items()}
    flat = weights.make(1, dsv32_ref.param_shapes(DSV32_TOY), jnp.float32)
    cache = init_decode_cache(model.clone(kv_page_size=4, kv_pages=3),
                              weights.unflatten(flat), batch_size=1)
    assert sorted(cache["layer0"]["attn"]) == ["index_pages", "latent_pages"]
    assert model.step_counters[:2] == ("expert_rows", "expert_rows_max")
    assert model.attended_rows(5) == 5 and model.attended_rows(100) == 8


def test_one_pool_a_layer_and_every_live_row_attended():
    model = toy_model(kv_page_size=4, kv_pages=3)
    _, params = toy_params(1)
    cache = init_decode_cache(model, params, batch_size=1)
    assert sorted(cache["layer0"]["attn"]) == ["latent_pages"]
    assert cache["layer0"]["attn"]["latent_pages"].shape == (12, 128)
    assert model.attended_rows(5) == 5 and model.attended_rows(9000) == 9000


@pytest.mark.parametrize("cfg,page,dtype,t_in,lane", [
    (TOY, 8, "fp32", 1, "dense-latent-gather"),      # rows under a tile
    (TOY, 8, "fp32", 16, "masked-blocks"),
    (TILE, 8, "fp32", 1, "dense-latent-kernel"),
    (TILE, 8, "fp32", 8, "dense-latent-kernel"),     # the widest window
    (TILE, 8, "fp32", 9, "masked-blocks"),           # wider is a chunk
    (TILE, 4, "fp32", 1, "dense-latent-gather"),     # a page under a tile
    (REAL, 16, "bf16", 1, "dense-latent-kernel"),    # the cell's lanes
    (REAL, 16, "bf16", 2, "dense-latent-kernel"),
    (REAL, 16, "bf16", 3, "dense-latent-gather"),    # 192 query rows
    (REAL, 8, "bf16", 1, "dense-latent-gather"),
    (REAL, 16, "bf16", 1024, "masked-blocks-kernel")])
def test_the_lane_a_calls_width_selects(cfg, page, dtype, t_in, lane):
    model = family.build_model(cfg, {"dtype": dtype, "logits_dtype": dtype})
    assert model.paged_lane(t_in, page, None) == lane


# -- routing -----------------------------------------------------------------

@pytest.mark.parametrize("seed,e,k,bias_std", [
    (0, 16, 4, 0.02), (1, 128, 8, 0.02), (2, 128, 8, 0.4), (3, 8, 8, 0.1)])
def test_one_group_of_the_router_is_a_plain_top_k(seed, e, k, bias_std):
    rng = np.random.default_rng(seed)
    logits = rng.normal(0, 1.7, (24, e)).astype(np.float32)
    if seed == 0:
        logits[:, 5] = logits[:, 2]          # ties go to the lower index
    bias = rng.normal(0, bias_std, e).astype(np.float32)
    experts, w = moe.grouped_sigmoid_route(
        jnp.asarray(logits), jnp.asarray(bias), n_group=1, topk_group=1,
        top_k=k, scale=2.5)
    s = 1.0 / (1.0 + np.exp(-logits.astype(np.float64)))
    want_e = np.array([sorted(range(e), key=lambda i: (-(row + bias)[i], i))
                       [:k] for row in s.astype(np.float32)])
    want_w = np.take_along_axis(s, want_e, -1)
    want_w = want_w / want_w.sum(-1, keepdims=True) * 2.5
    np.testing.assert_array_equal(np.asarray(experts), want_e)
    np.testing.assert_allclose(np.asarray(w), want_w, rtol=2e-6)
    if bias_std >= 0.4:   # the bias moved the choice, not the weights
        plain = np.argsort(-s, axis=-1, kind="stable")[:, :k]
        assert (np.sort(plain) != np.sort(want_e)).any()
    # and the reference's own routing is the same one
    cfg = {**TOY, "num_experts_per_tok": k,
           "published": {**TOY["published"], "num_experts": e}}
    p = {"router": jnp.eye(e, dtype=jnp.float32),
         "router_bias": jnp.asarray(bias)}
    ref_e, ref_w = ref.route(jnp.asarray(logits), p, cfg)
    np.testing.assert_array_equal(np.asarray(ref_e), want_e)
    np.testing.assert_allclose(np.asarray(ref_w), want_w, rtol=2e-6)


# -- the shares add up -------------------------------------------------------

UNCUT = {**TOY, "num_experts": 16, "n_routed_experts": 16,
         "assumed": {**TOY["assumed"], "first_held_expert": 0}}


def share_of(flat, first, count):
    """Expert layer 1's leaves cut to the share ``first .. first + count``
    of an uncut layer's."""
    p = ref.layer_leaves(flat, 1, "ffn/")
    for w in ("w1", "w3", "w2"):
        p[w] = p[w][first:first + count]
    return p, {**UNCUT, "num_experts": count, "n_routed_experts": count,
               "assumed": {**UNCUT["assumed"], "first_held_expert": first}}


def test_the_four_shares_and_the_shared_expert_once_are_the_uncut_layer():
    """Four chips hold four of the sixteen experts each: their routed
    parts, the shared expert counted once, are the uncut reference's whole
    layer."""
    flat, _ = toy_params(21, UNCUT)
    x = jnp.asarray(np.random.default_rng(2).normal(size=(19, 64)),
                    jnp.float32)
    whole_p, whole_cfg = share_of(flat, 0, 16)
    whole = jax.jit(lambda x, p: ref.expert_layer(x, p, whole_cfg))(
        x, whole_p)
    routed = 0
    for first in (0, 4, 8, 12):
        p, cfg = share_of(flat, first, 4)
        routed += jax.jit(lambda x, p: ref.expert_layer(
            x, p, cfg, shared=False))(x, p)
    shared = ref._ffn(x, whole_p["shared/w1"], whole_p["shared/w3"],
                      whole_p["shared/w2"], None)
    assert float(jnp.abs(routed).max()) > 1e-3
    np.testing.assert_allclose(routed + shared, whole, atol=2e-6)


@pytest.mark.parametrize("first,count,block_rows", [
    (4, 4, 3), (12, 4, 128), (0, 16, 2)])
def test_the_programs_share_is_the_references_share(first, count,
                                                    block_rows):
    flat, _ = toy_params(21, UNCUT)
    rng = np.random.default_rng(first)
    x = jnp.asarray(rng.normal(size=(19, 64)), jnp.float32)
    valid = jnp.asarray(rng.random(19) < 0.8)
    p, cfg = share_of(flat, first, count)
    layer = moe.HeldExpertsMlp(
        num_experts=16, held=(first, count), hidden_dim=32, top_k=4,
        n_group=1, topk_group=1, routed_scale=2.5, block_rows=block_rows)
    got, sown = jax.jit(lambda v, x, valid: layer.apply(
        v, x, valid, mutable=["counters"]))(
        {"params": weights.unflatten(p)}, x, valid)
    want = jax.jit(lambda x, p: ref.expert_layer(x, p, cfg))(x, p)
    np.testing.assert_allclose(got[valid], want[valid], atol=2e-6)
    experts = np.asarray(ref.route(x, p, cfg)[0])[np.asarray(valid)]
    per_expert = [(experts == e).sum() for e in range(first, first + count)]
    assert int(sown["counters"]["expert_rows"]) == sum(per_expert)
    assert int(sown["counters"]["expert_rows_max"]) == max(per_expert)
    assert int(sown["counters"]["experts_hit"]) == sum(
        n > 0 for n in per_expert)


# -- the two forms of the attention ------------------------------------------

def test_the_absorbed_form_is_the_references_per_head_form(seed=0):
    """One layer's attention of the reference (per head, keys and values
    expanded) against the program's absorbed form over the same rows."""
    flat, _ = toy_params(30 + seed)
    s = ref.sizes(TOY)
    p = ref.layer_leaves(flat, 0, "attn/")
    x = jnp.asarray(np.random.default_rng(seed).normal(size=(13, 64)),
                    jnp.float32)
    want = ref.attention(x, p, TOY)
    # the absorbed form by hand from the same leaves
    angles = jnp.arange(13, dtype=jnp.float32)[:, None] \
        * jnp.asarray(ref.yarn_frequencies(TOY))
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    q = jnp.einsum("td,dhe->the", x, p["wq"])
    q_nope = q[..., :s["nope"]]
    q_rope = dsv32.rotate_interleaved(q[..., s["nope"]:], cos[:, None],
                                      sin[:, None])
    kv = x @ p["wkv_a"]
    c_kv = ref._rms(kv[:, :16], p["kv_norm/scale"], s["eps"])
    rows = jnp.concatenate(
        [c_kv, dsv32.rotate_interleaved(kv[:, 16:], cos, sin)], -1)
    keep = jnp.tril(jnp.ones((13, 13), bool))
    out = dsv32.attend_absorbed(
        q_nope[None], q_rope[None],
        jnp.broadcast_to(rows[None, None], (1, 13, 13, 24)), keep[None],
        p["wkv_b"], ref.softmax_scale(TOY))
    got = jnp.einsum("thv,hvd->td", out[0], p["wo"])
    assert float(jnp.abs(want).max()) > 1e-3
    np.testing.assert_allclose(got, want, atol=2e-6)


# -- the shared-row kernel against the gather lane ---------------------------

def ragged_slots(t_in, page, pages_per_slot, rng):
    """Page tables as the allocator leaves them, with every ragged case: an
    empty slot, a slot of one row, a position on a page boundary, the last
    row of a kernel block, the budget's end, one invalid row in a window."""
    budget = page * pages_per_slot
    first = [None, 0, 3 * page, pa.LATENT_BLOCK_ROWS - 1, budget - t_in, 77]
    b = len(first)
    table = np.zeros((b, pages_per_slot), np.int32)
    positions = np.zeros((b, t_in), np.int32)
    valid = np.zeros((b, t_in), bool)
    ids = 1 + rng.permutation(b * pages_per_slot)
    for i, f in enumerate(first):
        if f is None:
            continue
        positions[i] = f + np.arange(t_in)
        valid[i] = True
        n = -(-(int(positions[i, -1]) + 1) // page)
        table[i, :n] = ids[i * pages_per_slot:i * pages_per_slot + n]
    if t_in > 1:
        valid[5, -1] = False
    return table, positions, valid


@pytest.mark.parametrize("dtype,t_in,tol", [
    ("float32", 1, 2e-6), ("float32", 2, 2e-6), ("bfloat16", 1, 2e-2)])
def test_the_kernel_is_the_gather_lane(dtype, t_in, tol):
    """``paged_latent_attention`` (interpret mode) against
    ``attend_absorbed`` over a gather of each slot's page budget, with NaN
    in every row no valid query may see. bfloat16: the probabilities meet
    the values rounded to 8 bits on both sides, in another order."""
    rng = np.random.default_rng(3)
    heads, rank, rope, width, page, per_slot = 16, 128, 16, 256, 16, 20
    dt = jnp.dtype(dtype)
    table, positions, valid = ragged_slots(t_in, page, per_slot, rng)
    b = table.shape[0]
    rows = (b * per_slot + 1) * page
    pool = rng.normal(size=(rows, width)).astype(np.float32)
    pool[:, rank + rope:] = 0.0
    seen = np.zeros(rows, bool)
    for i in range(b):
        if valid[i].any():
            n = positions[i][valid[i]].max() + 1
            at = np.arange(n)
            seen[table[i, at // page] * page + at % page] = True
    pool[~seen] = np.nan
    pool = jnp.asarray(pool, dt)
    q_nope = jnp.asarray(rng.normal(size=(b, t_in, heads, 32)), dt)
    q_rope = jnp.asarray(rng.normal(size=(b, t_in, heads, rope)), dt)
    wkv_b = jnp.asarray(rng.normal(size=(rank, heads, 32 + 24)) * 0.1, dt)
    assert pa.kernel_fits(t_in, heads, width, page, dt, value_lanes=rank)

    q_abs = jnp.einsum("bthd,chd->bthc", q_nope, wkv_b[..., :32])
    q = jnp.concatenate([q_abs, q_rope, jnp.zeros(
        (b, t_in, heads, width - rank - rope), dt)], -1)
    got = pa.paged_latent_attention(
        q, pool, jnp.asarray(table), jnp.asarray(positions),
        jnp.asarray(valid), value_lanes=rank, page_size=page, scale=0.17)
    got = jnp.einsum("bthc,chv->bthv", got, wkv_b[..., 32:])

    gathered = jnp.nan_to_num(dsv32.by_page(pool, page)[table].reshape(
        b, 1, per_slot * page, width))
    keep = (np.arange(per_slot * page) <= positions[..., None]) \
        & valid[..., None]
    want = dsv32.attend_absorbed(
        q_nope, q_rope, jnp.broadcast_to(
            gathered, (b, t_in, per_slot * page, width)),
        jnp.asarray(keep | ~valid[..., None]), wkv_b, 0.17)
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got[valid], want[valid], atol=tol, rtol=tol)
    assert (got[~valid] == 0).all()        # the empty slot, the invalid row


# -- the program against the reference ---------------------------------------

@pytest.mark.parametrize("cfg,lane,key_block,expert_rows,page,chunk,prompt", [
    (TOY, "dense-latent-gather", 8, 4, 4, 12, 29),   # several key blocks
    (TILE, "dense-latent-kernel", 16, 2, 8, 16, 30)])  # the kernel decodes
def test_chunked_prefill_then_paged_decode_gives_the_references_logits(
        cfg, lane, key_block, expert_rows, page, chunk, prompt):
    flat, params = toy_params(11 + key_block, cfg)
    seq = np.random.default_rng(prompt).integers(0, 64, LENGTH).astype(
        np.int32)
    model = toy_model(cfg, key_block=key_block,
                      expert_block_rows=expert_rows)
    assert model.paged_lane(1, page, None) == lane
    got = paged_logits(model, params, seq, page, chunk, prompt)
    want = ref_logits(flat, seq, cfg)
    assert np.abs(want).max() > 0.1
    np.testing.assert_allclose(got, want, atol=3e-6)
    plain = jax.jit(model.apply)({"params": params}, jnp.asarray(seq[None]))
    np.testing.assert_allclose(plain[0], want, atol=3e-6)


def run_engine(seed, lengths, *, cfg=TOY, page=4, max_new=8, **clone):
    flat, params = toy_params(seed, cfg)
    engine = Engine(toy_model(cfg, **clone), params, ServeConfig(
        max_batch=3, max_len=48, max_new_tokens=max_new, kv_page_size=page,
        kv_pages=144 // page, prefill_chunk=16, temperature=0.0, spec_k=0,
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


@pytest.mark.parametrize("seed,cfg,page,lanes", [
    (5, TOY, 4, {"decode": "dense-latent-gather", "chunk": "masked-blocks"}),
    (6, TILE, 8, {"decode": "dense-latent-kernel",
                  "chunk": "masked-blocks"})])
def test_the_engine_serves_what_the_reference_puts_first(seed, cfg, page,
                                                         lanes):
    """Requests of mixed length through ``Engine`` (continuous batching,
    fused chunk + decode steps, slots that empty and fill): every served
    token is the reference's first choice at its position, to rounding."""
    engine, flat, prompts, done, _ = run_engine(
        seed, (24, 31, 40, 27, 36, 25), cfg=cfg, page=page)
    assert engine.lane_formulation == lanes
    assert len(done) == 6 and all(len(f.tokens) == 8 for f in done)
    for f in done:
        seq = np.concatenate([prompts[f.uid], np.asarray(f.tokens,
                                                         np.int32)])
        padded = np.zeros(LENGTH, np.int32)
        padded[:seq.size] = seq
        logits = ref_logits(flat, padded, cfg)
        at = np.arange(seq.size - 9, seq.size - 1)
        assert (logits[at].max(-1) - logits[at, seq[-8:]]).max() < 1e-5


def test_the_iterations_counters():
    """Dense attention reads every live row (``kv_rows_selected`` is
    ``kv_rows_live``); ``experts_hit`` comes back with the tokens: against
    the reference's routing of one lone request's decode steps."""
    engine, flat, prompts, done, spans = run_engine(9, (26,), max_new=5)
    (prompt,) = prompts.values()
    seq = np.concatenate([prompt, np.asarray(done[0].tokens, np.int32)])
    decode = [s.attrs for s in spans if s.attrs["program"] == "decode"]
    fused = [s.attrs for s in spans if s.attrs["program"] == "fused"]
    assert len(fused) == 2 and len(decode) == 4
    assert [a["kv_rows_live"] for a in decode] == [27, 28, 29, 30]
    assert [a["kv_rows_selected"] for a in decode] == [27, 28, 29, 30]
    routed = routed_experts(flat, jnp.asarray(seq))    # [layers, T, k]
    held = (routed >= 4) & (routed < 8)
    for a, t in zip(decode, range(26, 30)):
        assert a["expert_rows"] == int(held[:, t].sum())
        # one token: each of its held experts got one row
        assert a["experts_hit"] == int(held[:, t].sum())
        assert a["expert_rows_max"] == int(held[:, t].any(-1).sum())
    # the first chunk's 16 tokens: the held experts some token chose
    assert fused[0]["experts_hit"] == sum(
        len(set(routed[i, :16][held[i, :16]].tolist())) for i in range(2))
    assert fused[0]["expert_rows"] == int(held[:, :16].sum())


@jax.jit
def _routed(flat, seq):
    s = ref.sizes(TOY)
    x = flat["tok_embed"][seq].astype(jnp.float32)
    out = []
    for i in range(s["layers"]):
        p = ref.layer_leaves(flat, i)
        x = x + ref.attention(ref._rms(x, p["attn_norm/scale"], s["eps"]),
                              ref.layer_leaves(flat, i, "attn/"), TOY)
        y = ref._rms(x, p["ffn_norm/scale"], s["eps"])
        ffn = ref.layer_leaves(flat, i, "ffn/")
        if i < s["dense_layers"]:
            x = x + ref._ffn(y, ffn["w1"], ffn["w3"], ffn["w2"], None)
            continue
        out.append(ref.route(y, ffn, TOY)[0])
        x = x + ref.expert_layer(y, ffn, TOY)
    return jnp.stack(out)


def routed_experts(flat, seq):
    return np.asarray(_routed(flat, seq))
