"""The paged decode-attention kernel (ops/paged_attention.py), off the
TPU in Pallas interpret mode.

1. **Kernel against the gather formulation** on the same pool, over
   dtype × window width, with a table set that holds every ragged case
   the engine produces: an inactive slot, position 0, a position on a
   page boundary, the last position of the budget, two tables aliasing
   the same leading pages, unallocated entries pointing at the null
   page.
2. **Dead pages are not read into results**: NaN in every pool row no
   valid query may see leaves the outputs bitwise unchanged.
3. **Through ``_paged_decode_attend``**: the per-row overflow poison
   (that row NaN, its neighbours intact), and the write — after the call
   the pool differs from its input in exactly the written rows.
4. **Through the engine** at tile-sized shapes (the tiny models of the
   other serving tests have pages smaller than a tile and keep the
   gather): tokens against the sequential ``Generator``, greedy and
   sampled, speculation on and off; the ``kv_pages_live`` counter.
5. **For the chip**: the kernel compiles at GPT-2-large's widths for a
   described v5e (no chip needed; skipped where it cannot be described),
   and its shared-row form at sarvam-105b's (the form itself against the
   gather lane: tests/test_sarvam_mla.py).
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_training_tpu.config import ServeConfig
from distributed_training_tpu.inference import Generator, SampleConfig
from distributed_training_tpu.models import get_model
from distributed_training_tpu.observability import trace as trace_lib
from distributed_training_tpu.ops.paged_attention import (
    BLOCK_ROWS,
    kernel_fits,
    paged_attention,
)
from distributed_training_tpu.parallel.ring_attention import (
    PagedKV,
    RingSelfAttention,
    paged_formulation,
    paged_gather_attention,
)
from distributed_training_tpu.serving import Engine, pages_for

H, HD, PS = 2, 64, 16
D = H * HD
PAGES_PER_SLOT = 20            # 320 rows: three kernel blocks, the last partial
BUDGET = PAGES_PER_SLOT * PS
DTYPES = {"fp32": jnp.float32, "bf16": jnp.bfloat16}
TOL = {"fp32": 2e-6, "bf16": 2e-2}

# slot -> position of its window's first row (None: the slot is inactive)
SLOTS = {
    "inactive": None,
    "position_0": 0,
    "page_boundary": 3 * PS,                 # first row opens a page
    "block_boundary": BLOCK_ROWS - 1,        # last row of a kernel block
    "budget_end": None,                      # set from t_in: BUDGET - t_in
    "alias_a": 5 * PS + 3,
    "alias_b": 4 * PS + 9,                   # shares alias_a's first 3 pages
}


def _tables(t_in):
    """Page tables as the allocator would leave them: pages only as far
    as each slot's positions reach, the rest at the null page."""
    first = dict(SLOTS, budget_end=BUDGET - t_in)
    names = list(first)
    table = np.zeros((len(names), PAGES_PER_SLOT), np.int32)
    positions = np.zeros((len(names), t_in), np.int32)
    valid = np.zeros((len(names), t_in), bool)
    next_page = 1
    for b, name in enumerate(names):
        if first[name] is None:
            continue
        positions[b] = first[name] + np.arange(t_in)
        valid[b] = True
        n = pages_for(int(positions[b, -1]) + 1, PS)
        table[b, :n] = np.arange(next_page, next_page + n)
        next_page += n
    a, b = names.index("alias_a"), names.index("alias_b")
    table[b, :3] = table[a, :3]
    return table, positions, valid, next_page


def _inputs(dtype, t_in, seed=0):
    table, positions, valid, n_pages = _tables(t_in)
    rng = np.random.default_rng(seed)
    rows = n_pages * PS
    k_pool = jnp.asarray(rng.standard_normal((rows, D)), dtype)
    v_pool = jnp.asarray(rng.standard_normal((rows, D)), dtype)
    q = jnp.asarray(rng.standard_normal((len(table), t_in, D)), dtype)
    return q, k_pool, v_pool, table, positions, valid


def _visible_rows(table, positions, valid):
    """Pool rows some valid query attends."""
    seen = set()
    for b in range(len(table)):
        if valid[b].any():
            for p in range(int(positions[b][valid[b]].max()) + 1):
                seen.add(int(table[b, p // PS]) * PS + p % PS)
    return seen


@jax.jit
def _kernel(q, k_pool, v_pool, table, positions, valid):
    return paged_attention(q, k_pool, v_pool, table, positions, valid,
                           num_heads=H, page_size=PS)


@jax.jit
def _gather(q, k_pool, v_pool, table, positions):
    b, t_in, _ = q.shape
    out = paged_gather_attention(
        q.reshape(b, t_in, H, HD), k_pool, v_pool, table, positions,
        page_size=PS)
    return out.reshape(b, t_in, D)


@pytest.mark.parametrize("t_in", [1, 3])
@pytest.mark.parametrize("dtype", sorted(DTYPES))
class TestKernelAgainstGather:
    def test_every_ragged_case_matches_the_gather(self, dtype, t_in):
        args = _inputs(DTYPES[dtype], t_in)
        valid = args[-1]
        assert kernel_fits(t_in, H, HD, PS, DTYPES[dtype])
        got = np.asarray(_kernel(*args), np.float32)
        want = np.asarray(_gather(*args[:-1]), np.float32)
        for b, name in enumerate(SLOTS):
            if valid[b].any():
                np.testing.assert_allclose(
                    got[b], want[b], atol=TOL[dtype], rtol=TOL[dtype],
                    err_msg=name)
            else:
                assert not got[b].any(), name    # an invalid row reads 0

    def test_nan_in_every_unseen_row_changes_nothing(self, dtype, t_in):
        q, k_pool, v_pool, table, positions, valid = _inputs(
            DTYPES[dtype], t_in)
        clean = np.asarray(_kernel(q, k_pool, v_pool, table, positions,
                                   valid), np.float32)
        unseen = np.ones(k_pool.shape[0], bool)
        unseen[sorted(_visible_rows(table, positions, valid))] = False
        assert unseen[:PS].all() and unseen.sum() > PS   # null page + tails
        k_bad = jnp.where(unseen[:, None], jnp.nan, k_pool)
        v_bad = jnp.where(unseen[:, None], jnp.nan, v_pool)
        dirty = np.asarray(_kernel(q, k_bad, v_bad, table, positions,
                                   valid), np.float32)
        np.testing.assert_array_equal(dirty, clean)

    def test_a_slots_rows_do_not_depend_on_its_neighbours(self, dtype,
                                                          t_in):
        q, k_pool, v_pool, table, positions, valid = _inputs(
            DTYPES[dtype], t_in)
        together = np.asarray(_kernel(q, k_pool, v_pool, table, positions,
                                      valid), np.float32)
        b = list(SLOTS).index("alias_b")
        alone = np.zeros_like(valid)
        alone[b] = True
        solo = np.asarray(_kernel(q, k_pool, v_pool, table, positions,
                                  alone), np.float32)
        np.testing.assert_array_equal(solo[b], together[b])
        assert not np.delete(solo, b, axis=0).any()


def _attend(dtype, t_in, positions, valid, table, *, seed=0):
    """One ``_paged_decode_attend`` call through the module on a pool of
    random rows; returns (output [B, T_in, D], pool before, pool after)."""
    attn = RingSelfAttention(
        num_heads=H, dtype=dtype, param_dtype=dtype, causal=True,
        kv_page_size=PS, kv_pages=int(table.max()) + 1)
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((len(table), t_in, D)), dtype)
    params = attn.init(jax.random.PRNGKey(0), x)["params"]
    rows = (int(table.max()) + 1) * PS
    before = {"key_pages": jnp.asarray(rng.standard_normal((rows, D)), dtype),
              "value_pages": jnp.asarray(rng.standard_normal((rows, D)),
                                         dtype)}
    pages = PagedKV(table=jnp.asarray(table), positions=jnp.asarray(positions),
                    valid=jnp.asarray(valid))
    out, state = attn.apply({"params": params, "cache": before}, x,
                            decode=True, pages=pages, mutable=["cache"])
    return np.asarray(out, np.float32), before, state["cache"]


@pytest.mark.parametrize("t_in", [1, 3])
@pytest.mark.parametrize("dtype", sorted(DTYPES))
class TestThroughTheModule:
    def test_the_pool_changes_in_exactly_the_written_rows(self, dtype,
                                                          t_in):
        table, positions, valid, _ = _tables(t_in)
        assert paged_formulation(t_in, H, HD, PS, DTYPES[dtype],
                                 None) == "kernel"
        _, before, after = _attend(DTYPES[dtype], t_in, positions, valid,
                                   table)
        written = {int(table[b, p // PS]) * PS + p % PS
                   for b in range(len(table)) for p in positions[b]
                   if valid[b].any()}
        written.add(0)          # the inactive slot's rows: the null page
        for name in ("key_pages", "value_pages"):
            assert after[name].shape == before[name].shape
            changed = np.flatnonzero(np.any(
                np.asarray(after[name], np.float32)
                != np.asarray(before[name], np.float32), axis=1))
            assert set(changed.tolist()) == written, name

    def test_a_position_past_the_table_poisons_that_row_alone(self, dtype,
                                                              t_in):
        table, positions, valid, _ = _tables(t_in)
        b = list(SLOTS).index("page_boundary")
        clean, _, _ = _attend(DTYPES[dtype], t_in, positions,
                              np.where(np.arange(len(table))[:, None] == b,
                                       False, valid), table)
        over = positions.copy()
        over[b, -1] = BUDGET + 5
        out, _, _ = _attend(DTYPES[dtype], t_in, over, valid, table)
        assert np.isnan(out[b, -1]).all()
        assert not np.isnan(out[b, :-1]).any()
        others = np.arange(len(table)) != b
        np.testing.assert_array_equal(out[others], clean[others])


# -- through the engine -------------------------------------------------------

VOCAB, MAX_LEN, N_NEW = 61, 64, 6
PROMPT_LENS = [3, 9, 17, 5]


@pytest.fixture(scope="module")
def lm():
    # 2 heads × 64 = 128 lanes, fp32 pages of 8 rows: whole tiles, so the
    # decode lane takes the kernel.
    model = get_model(
        "transformer_lm", num_classes=VOCAB, num_layers=2, num_heads=2,
        hidden_dim=128, max_len=MAX_LEN)
    params = model.init(jax.random.PRNGKey(0),
                        np.zeros((2, 16), np.int32))["params"]
    return model, params


@pytest.fixture(scope="module")
def prompts():
    rng = np.random.RandomState(1)
    return [rng.randint(0, VOCAB, size=n).astype(np.int32)
            for n in PROMPT_LENS]


def _serve(model, params, prompts, **cfg_kw):
    eng = Engine(model, params, ServeConfig(
        max_new_tokens=N_NEW, kv_page_size=8, **cfg_kw))
    for p in prompts:
        eng.submit(p)
    done = eng.run()
    assert len(done) == len(prompts)
    eng.pool.check_balanced()
    return eng, {f.uid: f.tokens for f in done}


ENGINES = {
    # name: (config, formulation of the decode lane and of the chunk lane)
    "chunk_gathers": (dict(max_batch=2, prefill_chunk=32),
                      {"decode": "kernel", "chunk": "gather"}),
    "chunk_in_kernel": (dict(max_batch=3, prefill_chunk=8),
                        {"decode": "kernel", "chunk": "kernel"}),
    "verify_window": (dict(max_batch=2, prefill_chunk=32, spec_k=2),
                      {"decode": "kernel", "chunk": "gather"}),
}


class TestEngineThroughTheKernel:
    @pytest.mark.parametrize("name", sorted(ENGINES))
    def test_greedy_tokens_match_the_sequential_generator(
            self, lm, prompts, name):
        model, params = lm
        cfg, lanes = ENGINES[name]
        eng, by_uid = _serve(model, params, prompts, temperature=0.0, **cfg)
        assert eng.lane_formulation == lanes
        assert eng.compiled_programs() == {"fused": 1, "decode": 1}
        gen = Generator(model, params, SampleConfig(
            max_new_tokens=N_NEW, temperature=0.0))
        for uid, p in enumerate(prompts):
            np.testing.assert_array_equal(by_uid[uid], gen(p)[0],
                                          err_msg=f"{name}: request {uid}")

    def test_sampled_tokens_do_not_depend_on_the_batch(self, lm, prompts):
        model, params = lm
        kw = dict(temperature=1.0, top_k=10, prefill_chunk=32)
        _, batched = _serve(model, params, prompts, max_batch=3, **kw)
        _, solo = _serve(model, params, prompts, max_batch=1, **kw)
        for uid in batched:
            np.testing.assert_array_equal(batched[uid], solo[uid])

    def test_tiny_pages_and_the_int8_pool_keep_the_gather(self):
        assert paged_formulation(1, 2, 16, 8, jnp.float32, None) == "gather"
        assert paged_formulation(1, 2, 64, 4, jnp.float32, None) == "gather"
        assert paged_formulation(1, 2, 64, 8, jnp.bfloat16, None) == "gather"
        assert paged_formulation(1, 20, 64, 16, jnp.bfloat16,
                                 "int8") == "gather"
        assert paged_formulation(256, 20, 64, 16, jnp.bfloat16,
                                 None) == "gather"
        assert paged_formulation(5, 20, 64, 16, jnp.bfloat16,
                                 None) == "kernel"

    def test_kv_pages_live_is_what_the_live_positions_cover(self, lm,
                                                            prompts):
        model, params = lm
        eng = Engine(model, params, ServeConfig(
            max_batch=3, max_new_tokens=N_NEW, kv_page_size=8,
            prefill_chunk=8, temperature=0.0))
        for p in prompts[:3]:
            eng.submit(p)
        budget = 3 * eng.pages_per_slot
        seen = []
        while not eng.idle:
            t0 = time.perf_counter()
            eng.step()
            it, = [s for s in trace_lib.host_spans(t0, time.perf_counter())
                   if s.name == "serve.iteration"]
            if it.attrs["program"] == "idle":
                continue
            assert it.attrs["kv_pages_budget"] == budget
            seen.append(it.attrs["kv_pages_live"])
        # The first iteration holds only the oldest request's chunk: its 3
        # prompt rows, one page. Later ones add each decoding slot's rows.
        assert seen[0] == pages_for(3, 8)
        assert max(seen) > 3 and all(0 < n <= budget for n in seen)
        stats = eng.stats()
        assert stats["kv_pages_live_iters"] == sum(seen)
        assert stats["kv_read_share"] == sum(seen) / (budget * len(seen))

    def test_kv_pages_live_of_one_decoding_slot(self, lm):
        model, params = lm
        eng = Engine(model, params, ServeConfig(
            max_batch=2, max_new_tokens=12, kv_page_size=8,
            prefill_chunk=32, temperature=0.0))
        eng.submit(np.arange(1, 14, dtype=np.int32))      # 13 tokens
        live = []
        while not eng.idle:
            t0 = time.perf_counter()
            eng.step()
            live += [s.attrs["kv_pages_live"]
                     for s in trace_lib.host_spans(t0, time.perf_counter())
                     if s.name == "serve.iteration"
                     and s.attrs["program"] != "idle"]
        # The chunk covers rows 0..12; decode step i reads rows 0..13+i.
        want = [pages_for(13, 8)] + [pages_for(14 + i, 8) for i in range(11)]
        assert live == want


# -- for the chip -------------------------------------------------------------

@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("t_in", [1, 5])
def test_the_kernel_compiles_for_a_v5e_at_gpt2_large_widths(one_chip, t_in):
    heads, head_dim, ps, slots, per_slot, rows = 20, 64, 16, 32, 64, 24592
    width = heads * head_dim

    def shape(s, d):
        return jax.ShapeDtypeStruct(s, d, sharding=one_chip)

    def call(q, k, v, table, positions, valid):
        return paged_attention(q, k, v, table, positions, valid,
                               num_heads=heads, page_size=ps,
                               interpret=False)

    compiled = jax.jit(call).lower(
        shape((slots, t_in, width), jnp.bfloat16),
        shape((rows, width), jnp.bfloat16),
        shape((rows, width), jnp.bfloat16),
        shape((slots, per_slot), jnp.int32),
        shape((slots, t_in), jnp.int32),
        shape((slots, t_in), jnp.bool_)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # Nothing of the pool's size is made: the kernel reads it in place.
    assert compiled.memory_analysis().temp_size_in_bytes < rows * width


@pytest.mark.parametrize("t_in", [1, 2])
def test_the_shared_row_kernel_compiles_for_a_v5e_at_sarvam_widths(one_chip,
                                                                   t_in):
    """``paged_latent_attention`` at the reasoning cell's shapes: 64 slots
    of 384 pages, 64 heads against a 640-wide latent row whose first 512
    lanes are the value."""
    from distributed_training_tpu.ops.paged_attention import (
        paged_latent_attention,
    )

    heads, width, value, ps, slots, per_slot, rows = (
        64, 640, 512, 16, 64, 384, 393232)
    assert kernel_fits(t_in, heads, width, ps, jnp.bfloat16,
                       value_lanes=value)

    def shape(s, d):
        return jax.ShapeDtypeStruct(s, d, sharding=one_chip)

    def call(q, pool, table, positions, valid):
        return paged_latent_attention(
            q, pool, table, positions, valid, value_lanes=value,
            page_size=ps, scale=0.1353, interpret=False)

    compiled = jax.jit(call).lower(
        shape((slots, t_in, heads, width), jnp.bfloat16),
        shape((rows, width), jnp.bfloat16),
        shape((slots, per_slot), jnp.int32),
        shape((slots, t_in), jnp.int32),
        shape((slots, t_in), jnp.bool_)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # Nothing of the pool's size is made: the kernel reads it in place.
    assert compiled.memory_analysis().temp_size_in_bytes < rows * width // 8


def test_the_masked_attention_kernel_compiles_for_a_v5e_at_deepseek_widths(
        one_chip):
    """``ops/masked_attention.py`` at the long-context cell's shapes: a
    1024-row chunk against a 1024-key block, 128 heads of 128 + 64 / 128."""
    from distributed_training_tpu.ops import masked_attention as ma

    heads, t, s, nope, rope, v = 128, 1024, 1024, 128, 64, 128

    def shape(dims, d):
        return jax.ShapeDtypeStruct(dims, d, sharding=one_chip)

    def call(*args):
        return ma.masked_attention_block(*args, scale=0.1, interpret=False)

    state = jax.eval_shape(lambda: ma.init_state(t, heads, v))
    compiled = jax.jit(call, donate_argnums=6).lower(
        shape((heads, t, nope), jnp.bfloat16),
        shape((heads, t, rope), jnp.bfloat16),
        shape((s, heads * nope), jnp.bfloat16),
        shape((s, rope), jnp.bfloat16),
        shape((s, heads * v), jnp.bfloat16),
        shape((t, s), jnp.int8),
        tuple(shape(a.shape, a.dtype) for a in state)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # the state is advanced in place and no score reaches memory
    assert compiled.memory_analysis().temp_size_in_bytes < t * s * 4


def test_the_grouped_masked_attention_kernel_compiles_for_a_v5e_at_keye_widths(
        one_chip):
    """The grouped form at ``keyevl2-serve-longctx``'s shapes: a 1024-row
    chunk of 32 query heads against a 1024-key block of 4 key/value heads
    of 128, as the pool's pages hold them."""
    from distributed_training_tpu.ops import masked_attention as ma

    heads, kv_heads, t, s, dim = 32, 4, 1024, 1024, 128
    assert ma.kernel_fits(1, t, s, dim, 0, dim, jnp.bfloat16)

    def shape(dims, d):
        return jax.ShapeDtypeStruct(dims, d, sharding=one_chip)

    def call(q, k, v, keep, state):
        return ma.masked_attention_block(q, None, k, None, v, keep, state,
                                         scale=0.1, interpret=False)

    state = jax.eval_shape(lambda: ma.init_state(t, heads, dim))
    compiled = jax.jit(call, donate_argnums=4).lower(
        shape((heads, t, dim), jnp.bfloat16),
        shape((s, kv_heads * dim), jnp.bfloat16),
        shape((s, kv_heads * dim), jnp.bfloat16),
        shape((t, s), jnp.int8),
        tuple(shape(a.shape, a.dtype) for a in state)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # the state is advanced in place, no score reaches memory and no key
    # head is expanded to its query heads
    assert compiled.memory_analysis().temp_size_in_bytes < t * s * 4
