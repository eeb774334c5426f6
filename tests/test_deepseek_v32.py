"""The DeepSeek-V3.2 family's model at a toy size on the CPU: the program
(chunked prefill, then decode through the paged latent and index pools,
through ``Engine``) against the plain reference of the benchmark; the pieces
(rotary, router, selection, the two attention forms) against brute force;
the shares of an expert layer against the uncut layer. The family's counts
and the toy cell through the harness: tests/benchmark/test_bm_deepseek_v32.py."""

import json
import math
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import weights
from benchmark.families import deepseek_v32 as family
from benchmark.reference import deepseek_v32 as ref
from distributed_training_tpu.config import ServeConfig
from distributed_training_tpu.models import deepseek_v32 as dsv32
from distributed_training_tpu.models import moe
from distributed_training_tpu.models.gpt import init_decode_cache
from distributed_training_tpu.observability import trace
from distributed_training_tpu.parallel.ring_attention import PagedKV
from distributed_training_tpu.serving.engine import Engine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "tests", "benchmark", "data",
                       "toy-dsv32.json")) as _fh:
    TOY = json.load(_fh)
with open(os.path.join(ROOT, "benchmark", "configs",
                       "deepseek-v3.2-exp-ep16.json")) as _fh:
    REAL = json.load(_fh)
FP32 = {"dtype": "fp32", "logits_dtype": "fp32"}


@pytest.fixture(autouse=True)
def highest():
    with jax.default_matmul_precision("highest"):
        yield


def toy_model(**kw):
    return family.build_model(TOY, FP32).clone(**kw)


def toy_params(seed, cfg=TOY):
    flat = weights.make(seed, ref.param_shapes(cfg), jnp.float32)
    return flat, weights.unflatten(flat)


LENGTH = 48     # every sequence here is this long: one compile a program


@jax.jit
def ref_logits(flat, seq):
    """The reference's logits ``[LENGTH, rows]`` of one sequence."""
    return ref.forward(flat, seq[None], TOY)[0]


# -- rotary ------------------------------------------------------------------

# dim 64, base 10000, factor 40, original 4096, beta 32 / 1:
# corr(32) = 64 ln(4096 / 64 pi) / (2 ln 10000) = 10.47 -> low 10;
# corr(1) = 64 ln(4096 / 2 pi) / (2 ln 10000) = 22.51 -> high 23
HAND_FREQUENCIES = {
    0: 1.0,
    10: 10000 ** (-20 / 64),
    16: 10000 ** (-32 / 64) * (7 / 13 + 6 / 13 / 40),
    23: 10000 ** (-46 / 64) / 40,
    31: 10000 ** (-62 / 64) / 40,
}


@pytest.mark.parametrize("i", sorted(HAND_FREQUENCIES))
@pytest.mark.parametrize("side", ["program", "reference"])
def test_yarn_frequencies_against_hand_values(i, side):
    got = (dsv32.yarn_frequencies(64, 10000.0, 40.0, 4096, 32.0, 1.0)
           if side == "program" else ref.yarn_frequencies(REAL))
    assert got.shape == (32,)
    assert got[i] == pytest.approx(HAND_FREQUENCIES[i], rel=1e-6)


def test_softmax_scale_carries_yarns_factor_squared():
    m = 0.1 * math.log(40) + 1
    assert m == pytest.approx(1.3689, abs=1e-4)
    assert ref.softmax_scale(REAL) == pytest.approx(192 ** -0.5 * m * m)
    assert dsv32.yarn_softmax_scale(192, 40.0, 1.0) == pytest.approx(
        ref.softmax_scale(REAL))
    short = {**REAL, "max_position_embeddings": 4096}
    assert ref.softmax_scale(short) == pytest.approx(192 ** -0.5)
    assert ref.yarn_frequencies(short)[31] == pytest.approx(
        10000 ** (-62 / 64))


def test_the_two_rotary_layouts():
    x = jnp.arange(8.0)
    cos, sin = jnp.zeros(4), jnp.ones(4)          # a quarter turn
    np.testing.assert_allclose(dsv32.rotate_interleaved(x, cos, sin),
                               [-1, 0, -3, 2, -5, 4, -7, 6])
    np.testing.assert_allclose(dsv32.rotate_half_split(x, cos, sin),
                               [-4, -5, -6, -7, 0, 1, 2, 3])


# -- routing -----------------------------------------------------------------

def brute_route(logits, bias, groups, kept, k, scale):
    """Row by row in numpy, as ISSUE 27 writes the routing out."""
    s = 1.0 / (1.0 + np.exp(-logits.astype(np.float64)))
    e = s.shape[1]
    size = e // groups
    experts, wts = [], []
    for row in s:
        biased = row + bias
        score = [np.sort(biased[g * size:(g + 1) * size])[-2:].sum()
                 for g in range(groups)]
        best = sorted(range(groups), key=lambda g: (-score[g], g))[:kept]
        allowed = [i for i in range(e) if i // size in best]
        chosen = sorted(allowed, key=lambda i: (-biased[i], i))[:k]
        w = row[chosen]
        experts.append(chosen)
        wts.append(w / w.sum() * scale)
    return np.array(experts), np.array(wts)


@pytest.mark.parametrize("seed,e,groups,kept,k,bias_std", [
    (0, 16, 4, 2, 4, 0.02), (1, 16, 4, 2, 4, 0.5), (2, 256, 8, 4, 8, 0.02),
    (3, 256, 8, 4, 8, 0.3), (4, 32, 8, 3, 2, 0.1)])
def test_the_router_against_a_brute_force_routing(seed, e, groups, kept, k,
                                                  bias_std):
    rng = np.random.default_rng(seed)
    logits = rng.normal(0, 1.7, (24, e)).astype(np.float32)
    bias = rng.normal(0, bias_std, e).astype(np.float32)
    experts, w = moe.grouped_sigmoid_route(
        jnp.asarray(logits), jnp.asarray(bias), n_group=groups,
        topk_group=kept, top_k=k, scale=2.5)
    want_e, want_w = brute_route(logits, bias, groups, kept, k, 2.5)
    np.testing.assert_array_equal(np.asarray(experts), want_e)
    np.testing.assert_allclose(np.asarray(w), want_w, rtol=2e-6)
    np.testing.assert_allclose(np.asarray(w).sum(-1), 2.5, rtol=1e-6)
    if bias_std >= 0.3:   # the bias moved the choice, not the weights
        plain = brute_route(logits, 0 * bias, groups, kept, k, 2.5)[0]
        assert (plain != want_e).any()
    # and the reference's own routing is the same one
    cfg = {**TOY, "n_group": groups, "topk_group": kept,
           "num_experts_per_tok": k,
           "published": {**TOY["published"], "n_routed_experts": e}}
    p = {"router": jnp.eye(e, dtype=jnp.float32),
         "router_bias": jnp.asarray(bias)}
    ref_e, ref_w = ref.route(jnp.asarray(logits), p, cfg)
    np.testing.assert_array_equal(np.asarray(ref_e), want_e)
    np.testing.assert_allclose(np.asarray(ref_w), want_w, rtol=2e-6)


# -- the selection -----------------------------------------------------------

def brute_topk_mask(scores, k):
    out = np.zeros(scores.shape, bool)
    for r, row in enumerate(scores):
        order = sorted(range(len(row)), key=lambda i: (-row[i], i))
        out[r, order[:k]] = True
    return out


SELECTION_CASES = ["random", "ties", "negative_zero", "short_rows",
                   "k_covers_all", "wide"]


def selection_case(case, rows=6, n=None):
    """Index scores ``[rows, n]`` of one case, and its ``k``."""
    rng = np.random.default_rng(7)
    wide_n, k = (4096, 2048) if case == "wide" else (40, 8)
    n = n or wide_n
    s = rng.normal(0, 1, (rows, n)).astype(np.float32)
    if case in ("ties", "idle_slot"):
        s = np.round(s * 2) / 2               # few values, many equal
    if case == "negative_zero":
        s = np.where(rng.random(s.shape) < 0.5, 0.0, -0.0).astype(np.float32)
        s[:, :3] = 1.0
    if case == "short_rows":                  # causal rows with < k keys
        last = np.resize(np.array([0, 2, 6, 7, 8, 20]), rows)
        s = np.where(np.arange(n)[None] <= last[:, None], s,
                     -np.inf).astype(np.float32)
    if case == "idle_slot":     # one key, beside rows crowded with ties
        s[0, 1:] = -np.inf
    if case == "k_covers_all":
        k = n
    return s, k


@pytest.mark.parametrize("case", SELECTION_CASES + ["idle_slot"])
def test_the_selected_set_against_a_brute_force_top_k(case):
    s, k = selection_case(case)
    want = brute_topk_mask(s, k)
    # the selection never takes a -inf entry: a row with fewer than k keys
    # (the idle slot has one) keeps them all, whatever its neighbours need
    got = np.asarray(dsv32.exact_topk_mask(jnp.asarray(s), k))
    np.testing.assert_array_equal(got, want & (s > -np.inf))
    # lax.top_k (the reference's) picks the same set, once a negative zero
    # is a zero (both add 0.0 first)
    top = np.zeros_like(want)
    np.put_along_axis(
        top, np.asarray(jax.lax.top_k(jnp.asarray(s) + 0.0, k)[1]), True,
        axis=1)
    np.testing.assert_array_equal(top, want)
    keep = np.asarray(ref.select_keys(jnp.asarray(s), k))
    np.testing.assert_array_equal(keep, got)


@pytest.mark.parametrize("t", [1, 4])
@pytest.mark.parametrize("case", SELECTION_CASES)
def test_the_selected_positions_against_a_brute_force_list(case, t):
    """The decode lane's compaction: the set as positions in ascending
    order, padded behind each row's count."""
    s, k = selection_case(case, rows=3 * t,
                          n=16896 if case == "wide" else None)
    n = s.shape[-1]
    mask = dsv32.exact_topk_mask(jnp.asarray(s.reshape(3, t, n)), k)
    chosen, keep = (np.asarray(a).reshape(3 * t, k) for a in
                    jax.jit(dsv32.mask_positions, static_argnums=1)(mask, k))
    finite = (s > -np.inf).sum(-1)
    brute = brute_topk_mask(s, k)
    for row in range(3 * t):
        want = [i for i in range(n) if brute[row, i] and s[row, i] > -np.inf]
        assert keep[row].sum() == min(k, finite[row]) == len(want)
        assert keep[row, :len(want)].all()
        assert chosen[row][keep[row]].tolist() == want
    assert ((chosen >= 0) & (chosen < n)).all()


@pytest.fixture(scope="module")
def sparse_and_dense():
    """One sequence through the model as it selects (``index_topk`` 8) and
    as it cannot (4096), and through the reference that cannot."""
    with jax.default_matmul_precision("highest"):
        flat, params = toy_params(4)
        toks = jnp.asarray(np.random.default_rng(0).integers(
            0, 64, (1, LENGTH)), jnp.int32)
        sparse, full = (np.asarray(jax.jit(toy_model(**kw).apply)(
            {"params": params}, toks)[0])
            for kw in ({}, {"index_topk": 4096}))
        want = np.asarray(jax.jit(lambda f, t: ref.forward(
            f, t, {**TOY, "index_topk": 4096}))(flat, toks)[0])
    return sparse, full, want


@pytest.mark.parametrize("position,dense", [(5, True), (7, True), (8, False),
                                            (29, False)])
def test_contexts_within_top_k_are_dense_mla(sparse_and_dense, position,
                                             dense):
    """Up to ``index_topk`` = 8 keys a query attends all of them: the
    model that selects gives what the one that cannot gives; from the
    ninth key on the selection bites."""
    sparse, full, want = sparse_and_dense
    same = np.allclose(sparse[position], full[position], atol=1e-6)
    assert same is dense
    np.testing.assert_allclose(full[position], want[position], atol=2e-6)


@pytest.mark.parametrize("seed", [0, 1])
def test_the_absorbed_form_is_the_per_head_form(seed):
    rng = np.random.default_rng(seed)
    b, t, s, h, rank, nope, rope, v = 2, 3, 11, 4, 16, 16, 8, 16
    q_nope = jnp.asarray(rng.normal(size=(b, t, h, nope)), jnp.float32)
    q_rope = jnp.asarray(rng.normal(size=(b, t, h, rope)), jnp.float32)
    latent = jnp.asarray(rng.normal(size=(b, s, rank + rope)), jnp.float32)
    wkv_b = jnp.asarray(rng.normal(size=(rank, h, nope + v)), jnp.float32)
    keep = jnp.asarray(rng.random((b, t, s)) < 0.6).at[:, :, 0].set(True)
    scores, values = dsv32.per_head_block(q_nope, q_rope, latent, wkv_b, 0.2)
    p = jax.nn.softmax(jnp.where(keep[:, None], scores, -jnp.inf), -1)
    per_head = jnp.einsum("bhts,bshv->bthv", p, values)
    absorbed = dsv32.attend_absorbed(
        q_nope, q_rope, jnp.broadcast_to(latent[:, None], (b, t, s, rank
                                                           + rope)),
        keep, wkv_b, 0.2)
    np.testing.assert_allclose(absorbed, per_head, rtol=2e-5, atol=2e-5)


# -- the program against the reference ---------------------------------------

def paged_logits(model, params, seq, page: int, chunk: int, prompt: int):
    """Logits of every position of ``seq`` as the engine computes them: the
    first ``prompt`` tokens in chunks of ``chunk`` through the masked-blocks
    lane, the rest one at a time through the sparse-gather lane, one slot's
    pages in a pool with other slots' (here: scrambled page ids)."""
    pages_per_slot = -(-seq.size // page)
    paged = model.clone(kv_page_size=page, kv_pages=2 * pages_per_slot + 1)
    table = 1 + np.random.default_rng(1).permutation(2 * pages_per_slot)[
        :pages_per_slot].astype(np.int32)
    cache = init_decode_cache(paged, params, batch_size=1)
    out = []

    @jax.jit
    def step(cache, toks, pos, valid):
        routing = PagedKV(table=jnp.asarray(table[None]), positions=pos,
                          valid=valid)
        logits, new = paged.apply(
            {"params": params, "cache": cache}, toks, positions=pos,
            decode=True, mutable=["cache"], pages=routing)
        return new["cache"], logits[0]

    def call(cache, toks, pos, valid):
        cache, logits = step(cache, jnp.asarray(toks[None]),
                             jnp.asarray(pos[None]), jnp.asarray(valid[None]))
        return cache, np.asarray(logits)

    for start in range(0, prompt, chunk):
        n = min(chunk, prompt - start)
        toks = np.zeros(chunk, np.int32)
        pos = np.zeros(chunk, np.int32)
        toks[:n] = seq[start:start + n]
        pos[:n] = np.arange(start, start + n)
        cache, logits = call(cache, toks, pos, np.arange(chunk) < n)
        out.append(logits[:n])
    for i in range(prompt, seq.size):
        cache, logits = call(cache, seq[i:i + 1],
                             np.array([i], np.int32), np.array([True]))
        out.append(logits)
    return np.concatenate(out)


@pytest.mark.parametrize("key_block,expert_rows,page,chunk,prompt", [
    (8, 4, 4, 16, 29),        # several key blocks; experts overflow
    (1024, 128, 4, 16, 32),   # the cell's own block sizes; whole chunks
    (16, 2, 8, 12, 39)])      # pages of 8; one decoded token
def test_chunked_prefill_then_paged_decode_gives_the_references_logits(
        key_block, expert_rows, page, chunk, prompt):
    flat, params = toy_params(11 + key_block)
    seq = np.random.default_rng(prompt).integers(0, 64, LENGTH).astype(
        np.int32)
    model = toy_model(key_block=key_block, expert_block_rows=expert_rows)
    got = paged_logits(model, params, seq, page, chunk, prompt)
    want = np.asarray(ref_logits(flat, jnp.asarray(seq)))
    assert np.abs(want).max() > 0.1
    np.testing.assert_allclose(got, want, atol=3e-6)
    plain = jax.jit(model.apply)({"params": params}, jnp.asarray(seq[None]))
    np.testing.assert_allclose(plain[0], want, atol=3e-6)


def sorted_sparse_gather(self, q_nope, q_rope, q_i, w_i, positions, table,
                         lat_all, idx_all, wkv_b, scale):
    """The decode lane as it was before PR 33: the keys picked by a sort of
    the slot's whole page budget, attended in score order."""
    b = table.shape[0]
    ps = int(self.kv_page_size)
    l_all = table.shape[1] * ps
    keys = dsv32.by_page(idx_all, ps)[table].reshape(b, l_all, -1)
    s = dsv32.index_scores(q_i, w_i, keys)
    s = jnp.where(jnp.arange(l_all) <= positions[..., None], s + 0.0,
                  -jnp.inf)
    top, chosen = jax.lax.top_k(s, min(self.index_topk, l_all))
    chosen_rows = jnp.take_along_axis(
        table[:, None, :], chosen // ps, axis=2) * ps + chosen % ps
    return dsv32.attend_absorbed(q_nope, q_rope, lat_all[chosen_rows],
                                 top > -jnp.inf, wkv_b, scale)


@pytest.fixture(scope="module")
def lane_and_sorted_lane():
    """Positions 4 .. 47 of one sequence decoded a token at a time through
    the sparse-gather lane, and through the sorted formulation."""
    with jax.default_matmul_precision("highest"):
        _, params = toy_params(3)
        seq = np.random.default_rng(3).integers(0, 64, LENGTH).astype(
            np.int32)
        lane = paged_logits(toy_model(), params, seq, 4, 4, 4)
        with pytest.MonkeyPatch.context() as patch:
            patch.setattr(dsv32.SparseLatentAttention, "_sparse_gather",
                          sorted_sparse_gather)
            by_sort = paged_logits(toy_model(), params, seq, 4, 4, 4)
    return lane, by_sort


@pytest.mark.parametrize("contexts,first,last", [
    ("below_top_k", 4, 6), ("at_top_k", 7, 7), ("above_top_k", 8, 47)])
def test_the_lane_against_the_sort_it_replaced(lane_and_sorted_lane,
                                               contexts, first, last):
    """``index_topk`` = 8: a query at position 7 has exactly 8 keys."""
    lane, by_sort = lane_and_sorted_lane
    assert np.abs(by_sort[first:last + 1]).max() > 0.1
    np.testing.assert_allclose(lane[first:last + 1],
                               by_sort[first:last + 1], atol=1e-6)


# the operand types of every ordering operation of a lowered program
ORDERINGS = re.compile(r'stablehlo\.sort"\(.*?\}\) : \(([^)]*)\)'
                       r"|chlo\.top_k\([^)]*\) : (\S+)", re.S)


def test_the_decode_program_sorts_nothing_as_wide_as_the_page_budget():
    """The toy's paged decode step as lowered: what is still put in order
    (the routers' ``top_k`` over the experts, an expert layer's pairs by
    expert) is narrower than the index scores ``[2, 1, 12 x 4]``."""
    _, params = toy_params(3)
    paged = toy_model().clone(kv_page_size=4, kv_pages=25)
    cache = init_decode_cache(paged, params, batch_size=2)

    def ordered():
        def step(cache, toks, pos, table):      # traced anew at each call
            routing = PagedKV(table=table, positions=pos,
                              valid=jnp.ones_like(pos, bool))
            return paged.apply({"params": params, "cache": cache}, toks,
                               positions=pos, decode=True, mutable=["cache"],
                               pages=routing)

        text = jax.jit(step).lower(
            cache, jnp.zeros((2, 1), jnp.int32), jnp.zeros((2, 1), jnp.int32),
            jnp.zeros((2, 12), jnp.int32)).as_text()
        return [a or b for a, b in ORDERINGS.findall(text)]

    scores = "tensor<2x1x48xf32>"
    now = ordered()
    assert now and not any(scores in types for types in now)
    with pytest.MonkeyPatch.context() as patch:     # the check can tell
        patch.setattr(dsv32.SparseLatentAttention, "_sparse_gather",
                      sorted_sparse_gather)
        assert any(scores in types for types in ordered())


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


@pytest.mark.parametrize("seed,clone", [
    (5, {"key_block": 8, "expert_block_rows": 4}), (6, {})])
def test_the_engine_serves_what_the_reference_puts_first(seed, clone):
    """Requests of mixed length through ``Engine`` (continuous batching,
    fused chunk + decode steps): every served token is the reference's
    first choice at its position, to rounding."""
    engine, flat, prompts, done, _ = run_engine(
        seed, (24, 31, 40, 27, 36, 25), **clone)
    assert engine.lane_formulation == {"decode": "sparse-gather",
                                       "chunk": "masked-blocks"}
    assert len(done) == 6 and all(len(f.tokens) == 8 for f in done)
    for f in done:
        seq = np.concatenate([prompts[f.uid], np.asarray(f.tokens,
                                                         np.int32)])
        padded = np.zeros(LENGTH, np.int32)
        padded[:seq.size] = seq
        logits = np.asarray(ref_logits(flat, jnp.asarray(padded)))
        at = np.arange(seq.size - 9, seq.size - 1)
        assert (logits[at].max(-1) - logits[at, seq[-8:]]).max() < 1e-5


def test_the_iterations_counters():
    """``kv_rows_*`` are host arithmetic over the decoding slots' contexts;
    ``expert_rows*`` come back with the tokens: against a brute-force count
    of one lone request's decode steps."""
    engine, flat, prompts, done, spans = run_engine(9, (26,), max_new=5)
    (prompt,) = prompts.values()
    seq = np.concatenate([prompt, np.asarray(done[0].tokens, np.int32)])
    decode = [s.attrs for s in spans if s.attrs["program"] == "decode"]
    fused = [s.attrs for s in spans if s.attrs["program"] == "fused"]
    assert len(fused) == 2 and len(decode) == 4
    assert [a["kv_rows_live"] for a in decode] == [27, 28, 29, 30]
    assert all(a["kv_rows_selected"] == 8 for a in decode)
    assert all(a["kv_rows_live"] == 0 for a in fused)
    # routed rows of the token each decode step fed, over the two expert
    # layers, on the held experts 4..7
    rows, busiest = expert_rows_of(flat, seq)
    assert [a["expert_rows"] for a in decode] == [
        int(rows[:, t].sum()) for t in range(26, 30)]
    assert [a["expert_rows_max"] for a in decode] == [
        int(busiest[:, t:t + 1].sum()) for t in range(26, 30)]
    # the chunks' rows: 16 tokens, then 10 (padding rows are not counted)
    assert fused[0]["expert_rows"] == int(rows[:, :16].sum())
    assert fused[1]["expert_rows"] == int(rows[:, 16:26].sum())


@jax.jit
def expert_rows_of(flat, seq):
    """Per expert layer and position: how many of the token's experts are
    held here, and (``busiest``) whether any is — for one token the
    busiest held expert has one row or none."""
    s = ref.sizes(TOY)
    x = flat["tok_embed"][seq].astype(jnp.float32)
    rows = []
    for i in range(s["layers"]):
        p = ref._layer(flat, i)
        attn = {k[5:]: v for k, v in p.items() if k.startswith("attn/")}
        ffn = {k[4:]: v for k, v in p.items() if k.startswith("ffn/")}
        x = x + ref.attention(ref._rms(x, p["attn_norm/scale"], s["eps"]),
                              attn, TOY)
        y = ref._rms(x, p["ffn_norm/scale"], s["eps"])
        if i < s["dense_layers"]:
            x = x + ref._ffn(y, ffn["w1"], ffn["w3"], ffn["w2"], None)
            continue
        experts = ref.route(y, ffn, TOY)[0]
        rows.append(((experts >= 4) & (experts < 8)).sum(-1))
        x = x + ref.expert_layer(y, ffn, TOY)
    rows = jnp.stack(rows)
    return rows, (rows > 0).astype(int)


# -- the shares add up -------------------------------------------------------

def layer_leaves(flat, cfg, first, count):
    """Expert layer 1's leaves cut to the share ``first .. first + count``
    of an uncut layer's."""
    p = {k[len("layer1/ffn/"):]: v for k, v in flat.items()
         if k.startswith("layer1/ffn/")}
    for w in ("w1", "w3", "w2"):
        p[w] = p[w][first:first + count]
    return p, {**cfg, "n_routed_experts": count,
               "assumed": {**cfg["assumed"], "first_held_expert": first}}


UNCUT = {**TOY, "n_routed_experts": 16,
         "assumed": {**TOY["assumed"], "first_held_expert": 0}}


def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """Four chips hold four experts each: their partial sums, the shared
    expert counted once, are the uncut reference's whole layer."""
    flat, _ = toy_params(21, UNCUT)
    x = jnp.asarray(np.random.default_rng(2).normal(size=(19, 64)),
                    jnp.float32)
    whole_p, whole_cfg = layer_leaves(flat, UNCUT, 0, 16)
    whole = jax.jit(lambda x, p: ref.expert_layer(x, p, whole_cfg))(
        x, whole_p)
    routed = 0
    for first in (0, 4, 8, 12):
        p, cfg = layer_leaves(flat, UNCUT, first, 4)
        routed += jax.jit(lambda x, p: ref.expert_layer(
            x, p, cfg, shared=False))(x, p)
    shared = ref._ffn(x, whole_p["shared/w1"], whole_p["shared/w3"],
                      whole_p["shared/w2"], None)
    assert float(jnp.abs(routed).max()) > 1e-3
    np.testing.assert_allclose(routed + shared, whole, atol=2e-6)


@pytest.mark.parametrize("first,count,block_rows", [
    (0, 4, 128), (4, 4, 3), (8, 4, 1), (12, 4, 128), (0, 16, 2), (6, 1, 128)])
def test_the_programs_share_is_the_references_share(first, count,
                                                    block_rows):
    flat, _ = toy_params(21, UNCUT)
    rng = np.random.default_rng(first)
    x = jnp.asarray(rng.normal(size=(19, 64)), jnp.float32)
    valid = jnp.asarray(rng.random(19) < 0.8)
    p, cfg = layer_leaves(flat, UNCUT, first, count)
    layer = moe.HeldExpertsMlp(
        num_experts=16, held=(first, count), hidden_dim=32, top_k=4,
        n_group=4, topk_group=2, routed_scale=2.5, block_rows=block_rows)
    got, sown = jax.jit(lambda v, x, valid: layer.apply(
        v, x, valid, mutable=["counters"]))(
        {"params": weights.unflatten(p)}, x, valid)
    want = jax.jit(lambda x, p: ref.expert_layer(x, p, cfg))(x, p)
    np.testing.assert_allclose(got[valid], want[valid], atol=2e-6)
    experts = np.asarray(ref.route(x, p, cfg)[0])[np.asarray(valid)]
    per_expert = [(experts == e).sum() for e in range(first, first + count)]
    assert int(sown["counters"]["expert_rows"]) == sum(per_expert)
    assert int(sown["counters"]["expert_rows_max"]) == max(per_expert)
