"""The paged pools' one writer (``parallel/ring_attention.py::paged_write``).

1. **The page form against the row form**: a chunk's run written a page at
   a time leaves every page but the null page bit-equal to a row-by-row
   write, over where the run starts (page-aligned, mid-page, ending on the
   table's last entry) × how many rows it holds (none, one, a page less
   one, a page, a page and one, the chunk less one, the whole chunk) × the
   pool widths the serving families hold in bfloat16 (GPT-2 large's K or
   V, the latent row, Keye's ``[K | V]``, an index key) — jitted with the
   pool donated, and with the table's entries past the run's pages stale
   (pointing at other slots' pages, which must not change).
2. **Several runs in one call** (a verify window's shape): each row of the
   call is its own run, an inactive row among them.
3. **Where the row form stays**: a window of 1 or 3 rows, pages of 8
   bfloat16 rows (half a tile), the int8 pool with its scales.
4. **Through the GPT layer**: a chunk's call through ``_paged_decode_attend``
   leaves the pools as the row form would.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_training_tpu.parallel.ring_attention import (
    PagedKV,
    RingSelfAttention,
    _QKVProj,
    paged_write,
    paged_write_form,
)

PS = 16
C = 40                     # chunk rows: two pages and a half
PER_SLOT = 8               # a slot's table: 128 positions
POOL_PAGES = 48            # page 0 is the null page
WIDTHS = {"gpt2_large_kv": 1280, "latent": 640, "keye_kv": 1024,
          "index_key": 128}
STARTS = {"aligned": lambda c: 2 * PS, "mid_page": lambda c: 2 * PS + 5,
          "table_end": lambda c: PER_SLOT * PS - c}
COUNTS = [0, 1, PS - 1, PS, PS + 1, C - 1, C]


def _run(start, c, t=C, b_rows=1, seed=0):
    """One slot's chunk as the engine hands it over: positions start ..
    start + c - 1 in the valid prefix, padding at position 0. The table's
    pages are distinct; entries past the run's last page are stale (they
    name pages other slots hold)."""
    rng = np.random.default_rng(seed)
    ids = rng.permutation(np.arange(1, POOL_PAGES))
    table = ids[:b_rows * PER_SLOT].reshape(b_rows, PER_SLOT).astype(np.int32)
    positions = np.zeros((b_rows, t), np.int32)
    valid = np.zeros((b_rows, t), bool)
    positions[0, :c] = start + np.arange(c)
    valid[0, :c] = True
    return table, positions, valid


def _rows_reference(pool, rows, table, positions, valid):
    """Row by row, in numpy: what the row form writes."""
    out = np.array(pool)
    flat = np.asarray(rows).reshape(-1, pool.shape[-1])
    for i, (p, ok) in enumerate(zip(positions.reshape(-1),
                                    valid.reshape(-1))):
        b = i // positions.shape[1]
        if ok:
            out[table[b, p // PS] * PS + p % PS] = flat[i]
    return out


@jax.jit
def _plain_rows(pool, rows, table, positions, valid):
    """The row form's scatter as the parent wrote it (null page included)."""
    phys = jnp.take_along_axis(table, positions // PS, axis=1) * PS \
        + positions % PS
    idx = jnp.where(valid, phys, 0).reshape(-1)
    return pool.at[idx].set(rows.reshape(idx.shape[0], -1))


_write = jax.jit(lambda pool, rows, pages: paged_write([pool], [rows], pages,
                                                       PS)[0],
                 donate_argnums=0)


def _inputs(width, start_kind, c, dtype=jnp.bfloat16, seed=0):
    table, positions, valid = _run(STARTS[start_kind](c), c, seed=seed)
    rng = np.random.default_rng(seed + 1)
    pool = np.asarray(jnp.asarray(
        rng.standard_normal((POOL_PAGES * PS, width)), dtype))
    rows = jnp.asarray(rng.standard_normal((1, C, width)), dtype)
    return pool, rows, table, positions, valid


@pytest.mark.parametrize("c", COUNTS)
@pytest.mark.parametrize("start_kind", sorted(STARTS))
@pytest.mark.parametrize("width", sorted(WIDTHS))
def test_a_chunk_written_by_page_is_the_row_form(width, start_kind, c):
    pool, rows, table, positions, valid = _inputs(WIDTHS[width], start_kind,
                                                  c)
    assert paged_write_form(C, PS, [pool.dtype]) == "page"
    want = _rows_reference(pool, rows, table, positions, valid)
    source = jnp.asarray(pool)
    got = _write(source, rows, PagedKV(jnp.asarray(table),
                                       jnp.asarray(positions),
                                       jnp.asarray(valid)))
    assert source.is_deleted()          # donated: written in place
    got = np.asarray(got)
    assert got.shape == pool.shape
    np.testing.assert_array_equal(got[PS:], want[PS:])
    # and the parent's scatter agrees with the reference
    plain = np.asarray(_plain_rows(jnp.asarray(pool), rows, table,
                                   positions, valid))
    np.testing.assert_array_equal(plain[PS:], want[PS:])


@pytest.mark.parametrize("c", [0, 1, PS])
def test_pages_without_a_row_of_the_run_are_not_written(c):
    """Only the run's own pages change (and the null page may): the stale
    entries past the allocation name pages that stay as they were."""
    pool, rows, table, positions, valid = _inputs(1280, "mid_page", c)
    got = np.asarray(_write(jnp.asarray(pool), rows, PagedKV(
        jnp.asarray(table), jnp.asarray(positions), jnp.asarray(valid))))
    changed = {r // PS for r in np.flatnonzero(
        np.any(got != pool, axis=1)) if r >= PS}
    start = STARTS["mid_page"](c)
    own = {int(table[0, p // PS]) for p in range(start, start + c)}
    assert changed <= own
    assert len(changed) == len(own)


def test_each_row_of_a_wide_call_is_its_own_run():
    """Three slots of a 9-row window over float32 pages of 8 (whole
    tiles): runs that start mid-page, on a page, and an inactive slot."""
    ps, t, width = 8, 9, 128
    rng = np.random.default_rng(3)
    table = rng.permutation(np.arange(1, 40))[:3 * 6].reshape(3, 6).astype(
        np.int32)
    positions = np.zeros((3, t), np.int32)
    valid = np.zeros((3, t), bool)
    for b, (start, c) in enumerate([(13, 9), (16, 4), (0, 0)]):
        positions[b, :c] = start + np.arange(c)
        # verify-window padding clamps its positions, never rewinds them
        positions[b, c:] = min(start + c, 6 * ps - 1) if c else 0
        valid[b, :c] = True
    pool = rng.standard_normal((40 * ps, width)).astype(np.float32)
    rows = rng.standard_normal((3, t, width)).astype(np.float32)
    assert paged_write_form(t, ps, [np.float32]) == "page"
    got = np.asarray(paged_write([jnp.asarray(pool)], [jnp.asarray(rows)],
                                 PagedKV(jnp.asarray(table),
                                         jnp.asarray(positions),
                                         jnp.asarray(valid)), ps)[0])
    want = np.array(pool)
    for b in range(3):
        for i in np.flatnonzero(valid[b]):
            p = positions[b, i]
            want[table[b, p // ps] * ps + p % ps] = rows[b, i]
    np.testing.assert_array_equal(got[ps:], want[ps:])


def _scatter_operands(fn, *args):
    """The ranks of the pools every scatter of ``fn`` writes into."""
    jaxpr = jax.make_jaxpr(fn)(*args)
    return [e.invars[0].aval.ndim for e in jaxpr.eqns
            if e.primitive.name == "scatter"]


@pytest.mark.parametrize("t, ps, dtypes", [
    (1, 16, (jnp.bfloat16,)),                      # the decode lane
    (3, 16, (jnp.bfloat16,)),                      # a verify window
    (C, 8, (jnp.bfloat16,)),                       # half-tile pages
    (C, 16, (jnp.int8, jnp.int8, jnp.float32, jnp.float32)),  # int8 + scales
], ids=["window_1", "window_3", "bf16_pages_of_8", "int8_pool"])
def test_the_row_form_stays_where_a_page_is_not_worth_it(t, ps, dtypes):
    assert paged_write_form(t, ps, dtypes) == "row"
    pools = [jnp.zeros((8 * ps, 2 if d == jnp.float32 else 128), d)
             for d in dtypes]
    rows = [jnp.ones((2, t, p.shape[1]), p.dtype) for p in pools]
    pages = PagedKV(jnp.ones((2, 4), jnp.int32),
                    jnp.zeros((2, t), jnp.int32), jnp.ones((2, t), bool))
    ranks = _scatter_operands(
        lambda pl, rw, pg: paged_write(pl, rw, pg, ps), pools, rows, pages)
    assert ranks == [2] * len(dtypes)          # rows of the flat pool


def test_the_page_form_writes_pages():
    pools = [jnp.zeros((8 * PS, 128), jnp.bfloat16)]
    rows = [jnp.ones((1, C, 128), jnp.bfloat16)]
    pages = PagedKV(jnp.ones((1, 4), jnp.int32),
                    jnp.zeros((1, C), jnp.int32), jnp.ones((1, C), bool))
    assert _scatter_operands(
        lambda pl, rw, pg: paged_write(pl, rw, pg, PS), pools, rows,
        pages) == [3]                           # [pages, page, width]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("start", [0, 21])
def test_a_chunk_through_the_gpt_layer_writes_as_the_row_form(dtype, start):
    heads, hd, c = 2, 64, 27
    attn = RingSelfAttention(num_heads=heads, dtype=dtype, param_dtype=dtype,
                             causal=True, kv_page_size=PS,
                             kv_pages=POOL_PAGES)
    table, positions, valid = _run(start, c, seed=5)
    rng = np.random.default_rng(6)
    x = jnp.asarray(rng.standard_normal((1, C, heads * hd)), dtype)
    params = attn.init(jax.random.PRNGKey(0), x)["params"]
    before = {name: jnp.asarray(rng.standard_normal(
        (POOL_PAGES * PS, heads * hd)), dtype)
        for name in ("key_pages", "value_pages")}
    pages = PagedKV(jnp.asarray(table), jnp.asarray(positions),
                    jnp.asarray(valid))
    _, state = attn.apply({"params": params, "cache": before}, x,
                          decode=True, pages=pages, mutable=["cache"])
    # the K and V rows the call computed, from the layer's projection
    _, k, v = _QKVProj(num_heads=heads, head_dim=hd, dtype=dtype,
                       param_dtype=dtype).apply({"params": params["qkv"]}, x)
    for name, rows in (("key_pages", k), ("value_pages", v)):
        rows = jnp.swapaxes(rows, 1, 2)           # [B, T, H, hd]
        want = _rows_reference(np.asarray(before[name]), rows, table,
                               positions, valid)
        np.testing.assert_array_equal(
            np.asarray(state["cache"][name])[PS:], want[PS:])


# -- 5. through the engine ----------------------------------------------------

VOCAB, MAX_LEN, EPS, ECHUNK = 31, 64, 8, 12   # float32 pages of 8: whole tiles
ENGINE_PROMPTS = (3, 13, 26, 9, 30, 17)
ENGINE_NEW = (6, 3, 5, 8, 2, 4)


@pytest.fixture(scope="module")
def lm():
    from distributed_training_tpu.models import get_model

    model = get_model("transformer_lm", num_classes=VOCAB, num_layers=1,
                      num_heads=2, hidden_dim=16, max_len=MAX_LEN)
    params = model.init(jax.random.PRNGKey(0),
                        np.zeros((1, 8), np.int32))["params"]
    return model, params


def _engine(lm, **kw):
    from distributed_training_tpu.config import ServeConfig
    from distributed_training_tpu.serving import Engine

    model, params = lm
    kw.setdefault("max_batch", 2)
    eng = Engine(model, params, ServeConfig(
        kv_page_size=EPS, prefill_chunk=ECHUNK, max_new_tokens=8, top_k=8,
        **kw))
    assert eng.chunk_write == "page"
    return eng


def _sequential(lm, prompt, n_new, seed, uid, eng):
    from test_serving_packed_inputs import sequential_decode

    model, params = lm
    return sequential_decode(
        model, params, prompt, n_new,
        jax.random.fold_in(jax.random.PRNGKey(seed), uid), eng.sample_cfg)


def _prompts(lens, seed=7):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, VOCAB, size=n).astype(np.int32) for n in lens]


@pytest.mark.parametrize("temperature", [0.0, 0.8])
def test_engine_streams_from_chunks_that_start_mid_page(lm, temperature):
    """Chunks of 12 rows over pages of 8: every second chunk of a prompt
    starts mid-page. Greedy and sampled streams are the sequential
    decode's, and ``kv_pages_written`` counts the pages each chunk wrote."""
    from distributed_training_tpu.observability import trace as trace_lib
    from distributed_training_tpu.serving import pages_for

    seed = 2**31 - 9
    eng = _engine(lm, temperature=temperature, seed=seed)
    prompts = _prompts(ENGINE_PROMPTS)
    t0 = time.perf_counter()
    for p, n in zip(prompts, ENGINE_NEW):
        eng.submit(p, max_new_tokens=n)
    done = {f.uid: f.tokens.tolist() for f in eng.run()}
    spans = trace_lib.host_spans(t0, time.perf_counter())
    for uid, (p, n) in enumerate(zip(prompts, ENGINE_NEW)):
        assert done[uid] == _sequential(lm, p, n, seed, uid, eng), uid
    written = sorted(s.attrs["kv_pages_written"] for s in spans
                     if s.name == "serve.iteration"
                     and s.attrs.get("program") == "fused")
    want = sorted(pages_for(start % EPS + min(ECHUNK, p.size - start), EPS)
                  for p in prompts for start in range(0, p.size, ECHUNK))
    assert written == want
    assert not any(s.attrs.get("kv_pages_written")
                   for s in spans if s.name == "serve.iteration"
                   and s.attrs.get("program") != "fused")
    eng.check_balanced()


def test_a_prefix_hit_then_chunks_from_mid_page(lm):
    """The hit's chain is page-aligned (2 pages); the tail's chunks of 12
    start at 16 and 28, the second mid-page. The warm engine's streams are
    the cold engine's and the sequential decode's."""
    seed = 11
    preamble = _prompts((16,), seed=3)[0]
    prompts = [np.concatenate([preamble, t]) for t in _prompts((5, 21))]
    out = {}
    for warm in (False, True):
        eng = _engine(lm, temperature=0.8, seed=seed, prefix_cache=warm,
                      max_batch=1)
        for p in prompts:
            eng.submit(p, max_new_tokens=6)
        out[warm] = {f.uid: f.tokens.tolist() for f in eng.run()}
        eng.check_balanced()
    assert eng.stats()["prefix_cache_hit_tokens"] == 16
    assert out[True] == out[False]
    for uid, p in enumerate(prompts):
        assert out[True][uid] == _sequential(lm, p, 6, seed, uid, eng)


@pytest.mark.parametrize("temperature", [0.0, 0.8])
def test_a_resumption_re_prefills_from_mid_page(lm, temperature):
    """A 26-token request preempted mid-decode re-prefills prompt and
    emitted tokens in chunks of 12 (the second from mid-page) and ends as
    the sequential decode does; so does the request that preempted it."""
    seed = 5
    low_p, high_p = _prompts((26, 7), seed=9)
    eng = _engine(lm, temperature=temperature, seed=seed, max_batch=1,
                  num_tiers=2)
    low = eng.submit(low_p, priority=1, max_new_tokens=8)
    for _ in range(6):
        eng.step()
    assert len(eng.scheduler.sequence(0).tokens) >= 1
    high = eng.submit(high_p, priority=0, max_new_tokens=4)
    done = {f.uid: f.tokens.tolist() for f in eng.run()}
    assert eng.stats()["requests_preempted"] >= 1
    eng.check_balanced()
    assert done[low.uid] == _sequential(lm, low_p, 8, seed, low.uid, eng)
    assert done[high.uid] == _sequential(lm, high_p, 4, seed, high.uid, eng)
