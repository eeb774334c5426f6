"""``ops/masked_attention.py`` in interpret mode against plain ``jax.numpy``,
and the chunk lane of the DeepSeek model and of the Keye-VL-2.0 model (the
kernel's grouped form) through the kernel against the same lane in XLA. (The
compile for the chip: tests/test_paged_attention_kernel.py.)"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_training_tpu.models import deepseek_v32 as dsv32
from distributed_training_tpu.models import keye_vl2
from distributed_training_tpu.ops import masked_attention as ma

TOL = {"float32": 2e-6, "bfloat16": 6e-3}


def plain(q_nope, q_rope, k_nope, k_rope, v, keep, scale):
    """Softmax attention over every block's selected keys at once."""
    h, _, nope = q_nope.shape
    keys = k_rope.shape[0] * k_rope.shape[1]
    kn = k_nope.reshape(keys, h, nope)
    vv = v.reshape(keys, h, -1)
    s = (jnp.einsum("htd,shd->hts", q_nope, kn,
                    preferred_element_type=jnp.float32)
         + jnp.einsum("htd,sd->hts", q_rope, k_rope.reshape(keys, -1),
                      preferred_element_type=jnp.float32)) * scale
    keep = jnp.concatenate(list(keep), axis=-1)
    p = jax.nn.softmax(jnp.where(keep[None], s, -jnp.inf), axis=-1)
    return jnp.einsum("hts,shv->thv", p.astype(v.dtype).astype(jnp.float32),
                      vv.astype(jnp.float32))


@pytest.mark.parametrize("dtype", sorted(TOL))
@pytest.mark.parametrize("t", [64, 1024])      # one query block, and two
def test_blocks_through_the_kernel_are_one_softmax(dtype, t):
    h, s, nope, rope, vd, blocks = 2, 128, 128, 64, 128, 3
    dt = jnp.dtype(dtype)
    ks = jax.random.split(jax.random.key(t), 6)
    q_nope = jax.random.normal(ks[0], (h, t, nope), dt)
    q_rope = jax.random.normal(ks[1], (h, t, rope), dt)
    k_nope = jax.random.normal(ks[2], (blocks, s, h * nope), dt)
    k_rope = jax.random.normal(ks[3], (blocks, s, rope), dt)
    v = jax.random.normal(ks[4], (blocks, s, h * vd), dt)
    keep = jax.random.bernoulli(ks[5], 0.3, (blocks, t, s))
    keep = keep.at[:, :5].set(False)       # rows that select nothing at all
    keep = keep.at[0, 5:20].set(False)     # and nothing in the first block
    state = ma.init_state(t, h, vd)
    for j in range(blocks):
        state = ma.masked_attention_block(
            q_nope, q_rope, k_nope[j], k_rope[j], v[j],
            keep[j].astype(jnp.int8), tuple(state), scale=0.11)
    got = np.asarray(ma.finish(state, h))
    want = np.asarray(plain(q_nope, q_rope, k_nope, k_rope, v, keep, 0.11))
    assert np.isnan(got[:5]).all() and np.isnan(want[:5]).all()
    np.testing.assert_allclose(got[5:], want[5:], atol=TOL[dtype])


@pytest.mark.parametrize("dtype", sorted(TOL))
def test_the_grouped_form_is_one_softmax_over_each_key_heads_group(dtype):
    """4 query heads over 2 key/value heads, no shared rotated key: against
    the same keys and values expanded to the query heads in plain
    ``jax.numpy``. One query block (a tile of each operand)."""
    h, kvh, t, s, dim, blocks = 4, 2, 64, 128, 128, 2
    dt = jnp.dtype(dtype)
    ks = jax.random.split(jax.random.key(7), 4)
    q = jax.random.normal(ks[0], (h, t, dim), dt)
    k = jax.random.normal(ks[1], (blocks, s, kvh * dim), dt)
    v = jax.random.normal(ks[2], (blocks, s, kvh * dim), dt)
    keep = jax.random.bernoulli(ks[3], 0.3, (blocks, t, s))
    keep = keep.at[:, :3].set(False)       # rows that select nothing at all
    state = ma.init_state(t, h, dim)
    for j in range(blocks):
        state = ma.masked_attention_block(
            q, None, k[j], None, v[j], keep[j].astype(jnp.int8),
            tuple(state), scale=0.09)
    got = np.asarray(ma.finish(state, h))

    def expanded(a):       # query head hh reads key head hh // 2
        return jnp.repeat(a.reshape(blocks, s, kvh, dim), h // kvh,
                          axis=2).reshape(blocks, s, h * dim)

    want = np.asarray(plain(q, jnp.zeros((h, t, 8), dt), expanded(k),
                            jnp.zeros((blocks, s, 8), dt), expanded(v),
                            keep, 0.09))
    assert np.isnan(got[:3]).all() and np.isnan(want[:3]).all()
    np.testing.assert_allclose(got[3:], want[3:], atol=TOL[dtype])


@pytest.mark.parametrize("batch,t,kb,nope,rope,v,dtype,fits", [
    (1, 1024, 1024, 128, 64, 128, "bfloat16", True),
    (1, 1024, 1024, 128, 0, 128, "bfloat16", True),     # no shared key part
    (1, 64, 128, 128, 16, 128, "float32", True),
    (2, 1024, 1024, 128, 64, 128, "bfloat16", False),   # one sequence only
    (1, 1024, 1024, 16, 8, 16, "float32", False),       # the toy's heads
    (1, 1000, 1024, 128, 64, 128, "bfloat16", False),
    (1, 1024, 16, 128, 64, 128, "bfloat16", False),
])
def test_which_shapes_the_kernel_serves(batch, t, kb, nope, rope, v, dtype,
                                        fits):
    assert ma.kernel_fits(batch, t, kb, nope, rope, v, dtype) is fits


@pytest.fixture(scope="module")
def attention():
    """A two-head attention layer whose heads are lane tiles wide, a
    sequence of 3 key blocks, and its parameters."""
    layer = dsv32.SparseLatentAttention(
        num_heads=2, q_rank=32, kv_rank=48, nope_dim=128, rope_dim=16,
        v_dim=128, index_heads=2, index_dim=32, index_topk=40,
        rope=(10000.0, 1.0, 4096, 32, 1, 1.0), rope_scaled=False,
        key_block=128)
    x = jax.random.normal(jax.random.key(1), (1, 320, 64), jnp.float32)
    positions = jnp.arange(320)[None]
    params = layer.init(jax.random.key(2), x, positions)
    return layer, params, x, positions


def test_the_chunk_lane_through_the_kernel_is_the_lane_in_xla(attention,
                                                              monkeypatch):
    layer, params, x, positions = attention
    with jax.default_matmul_precision("highest"):
        assert layer.chunk_kernel(1, 320, 128)
        got = layer.apply(params, x, positions)
        monkeypatch.setattr(ma, "kernel_fits", lambda *a: False)
        assert not layer.chunk_kernel(1, 320, 128)
        want = layer.apply(params, x, positions)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_the_grouped_chunk_lane_through_the_kernel_is_the_lane_in_xla(
        monkeypatch):
    """A grouped-query layer whose heads are lane tiles wide (4 query heads
    over 2 key heads of 128), 2 key blocks, the selection biting (40 of up
    to 256 keys): the kernel's grouped form against ``attend_block`` in
    XLA."""
    layer = keye_vl2.SparseGroupedAttention(
        num_heads=4, num_kv_heads=2, head_dim=128, index_heads=2,
        index_dim=32, index_topk=40, rope_theta=1e4,
        mrope_section=(16, 24, 24), key_block=128)
    x = jax.random.normal(jax.random.key(1), (1, 256, 64), jnp.float32)
    positions = jnp.arange(256)[None]
    params = layer.init(jax.random.key(2), x, positions)
    with jax.default_matmul_precision("highest"):
        assert layer.chunk_kernel(1, 256, 128)
        got = jax.jit(layer.apply)(params, x, positions)
        monkeypatch.setattr(ma, "kernel_fits", lambda *a: False)
        assert not layer.chunk_kernel(1, 256, 128)
        want = jax.jit(layer.apply)(params, x, positions)
    assert float(jnp.abs(want).max()) > 1e-2
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


@pytest.mark.parametrize("n_live", [1, 2, 3, 5, 6])
def test_a_live_prefix_selects_the_same_finite_set(n_live, monkeypatch):
    monkeypatch.setattr(dsv32, "LIVE_RUNS", (2, 4))     # prefixes 2, 4, 6
    rng = np.random.default_rng(n_live)
    width, runs, k = 16, 6, 24
    s = rng.normal(0, 1, (5, runs * width)).astype(np.float32)
    s = np.round(s * 2) / 2 + 0.0                    # ties, no negative zero
    s[:, n_live * width:] = -np.inf                  # nothing lives behind
    s[0, 3:] = -np.inf                               # a row with 3 keys
    whole = np.asarray(dsv32.exact_topk_mask(jnp.asarray(s), k))
    live = np.asarray(jax.jit(
        lambda a, n: dsv32.exact_topk_mask(a, k, (n, width)))(
            jnp.asarray(s), n_live))
    finite = s > -np.inf
    np.testing.assert_array_equal(live & finite, whole & finite)
    covering = next(r for r in (2, 4, 6) if r >= n_live)
    assert not live[:, covering * width:].any()


def test_the_model_names_the_lane_its_shapes_take():
    import json
    import os

    from benchmark.families import deepseek_v32 as family

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    lanes = {}
    for name in ("benchmark/configs/deepseek-v3.2-exp-ep16.json",
                 "tests/benchmark/data/toy-dsv32.json"):
        with open(os.path.join(root, name)) as fh:
            model = family.build_model(json.load(fh), {
                "dtype": "bf16", "logits_dtype": "bf16"})
        lanes[name] = [model.paged_lane(t) for t in (1, 1024)]
    published, toy = lanes.values()
    assert published == ["sparse-gather", dsv32.KERNEL_LANE]
    assert toy == ["sparse-gather", "masked-blocks"]    # heads 16 + 8 wide


def test_the_grouped_model_names_the_lane_its_shapes_take():
    import json
    import os

    from benchmark.families import keye_vl2 as family

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    lanes = {}
    for name in ("benchmark/configs/keye-vl-2.0-30b-a3b-pp8.json",
                 "tests/benchmark/data/toy-keye.json"):
        with open(os.path.join(root, name)) as fh:
            model = family.build_model(json.load(fh), {
                "dtype": "bf16", "logits_dtype": "bf16"})
        lanes[name] = [model.paged_lane(t) for t in (1, 8, 1024)]
    published, toy = lanes.values()
    assert published == ["sparse-gather"] * 2 + [dsv32.KERNEL_LANE]
    assert toy == ["sparse-gather"] * 2 + ["masked-blocks"]   # heads of 16
