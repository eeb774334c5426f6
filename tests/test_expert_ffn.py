"""The held experts' grouped SwiGLU kernel (ops/expert_ffn.py), off the TPU
in Pallas interpret mode, against the walk it replaced (``expert_walk``)
and a dense float32 sum over every held expert; and the layer
(``models/moe.py::HeldExpertsMlp``) that calls it. Toy widths: the
kernel's compile for the chip at the cells' widths lives in
tests/test_paged_attention_kernel.py."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_training_tpu.models import moe
from distributed_training_tpu.ops import expert_ffn as ef

D, H = 32, 24


def weights(held: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    w1, w3 = (jnp.asarray(rng.normal(0, 0.2, (held, D, H)), jnp.float32)
              for _ in range(2))
    w2 = jnp.asarray(rng.normal(0, 0.2, (held, H, D)), jnp.float32)
    return w1, w3, w2


def sorted_pairs(experts, wts, held: int):
    """The layer's sort: pairs by held expert, the rest after them."""
    top_k = experts.shape[1]
    local = np.where((experts >= 0) & (experts < held), experts, held)
    local = local.reshape(-1)
    order = np.argsort(local, kind="stable")
    rows = np.array([(local == e).sum() for e in range(held)], np.int32)
    return (jnp.asarray(order // top_k, jnp.int32),
            jnp.asarray(wts.reshape(-1)[order], jnp.float32),
            jnp.asarray(rows), jnp.asarray(np.cumsum(rows) - rows))


def dense(x, w1, w3, w2, experts, wts):
    """Every held expert over every token, weighted by the token's pairs
    on it (zero where none), in float32 at the highest precision."""
    held = w1.shape[0]
    comb = np.zeros((x.shape[0], held), np.float32)
    for t, (es, ws) in enumerate(zip(experts, wts)):
        for e, w in zip(es, ws):
            if 0 <= e < held:
                comb[t, e] += w
    with jax.default_matmul_precision("highest"):
        a = jnp.einsum("td,edh->eth", x, w1)
        b = jnp.einsum("td,edh->eth", x, w3)
        y = jnp.einsum("eth,ehd->etd", jax.nn.silu(a) * b, w2)
        return jnp.einsum("te,etd->td", jnp.asarray(comb), y)


def check(x, experts, wts, held, row_tile, hidden=None):
    w1, w3, w2 = weights(held)
    pairs = sorted_pairs(np.asarray(experts), np.asarray(wts), held)
    got = ef.expert_ffn(x, w1, w3, w2, *pairs, row_tile=row_tile,
                        hidden=hidden)
    walk = jax.jit(ef.expert_walk, static_argnames="block_rows")(
        x, w1, w3, w2, *pairs, block_rows=row_tile)
    want = dense(x, w1, w3, w2, experts, wts)
    np.testing.assert_allclose(got, want, atol=2e-5)
    np.testing.assert_allclose(got, walk, atol=2e-5)
    return got


def routing(t, top_k, router, seed):
    rng = np.random.default_rng(seed)
    experts = np.stack([rng.choice(router, top_k, replace=False)
                        for _ in range(t)])
    return experts, rng.random((t, top_k)).astype(np.float32)


@pytest.mark.parametrize("t,top_k,router,held,row_tile,hidden", [
    (19, 4, 16, 4, 16, None),   # t not a multiple of the tile
    (19, 4, 16, 16, 3, None),   # every expert held, several tiles
    (40, 2, 8, 8, 8, 8),        # hidden in tiles of 8
    (7, 8, 64, 6, 16, None),    # several experts get no pair
])
def test_the_kernel_is_the_walk_and_the_dense_sum(t, top_k, router, held,
                                                  row_tile, hidden):
    experts, wts = routing(t, top_k, router, seed=t + held)
    x = jnp.asarray(np.random.default_rng(t).normal(size=(t, D)),
                    jnp.float32)
    rows = sorted_pairs(experts, wts, held)[2]
    got = check(x, experts, wts, held, row_tile, hidden)
    # a token with no pair on a held expert gets nothing
    none = ~((experts >= 0) & (experts < held)).any(1)
    assert (np.asarray(got)[none] == 0).all()
    if t == 7:
        assert (np.asarray(rows) == 0).sum() >= 2


def test_one_expert_takes_every_pair_across_several_tiles():
    t, held = 37, 5
    experts = np.stack([[3, 7, 9]] * t)
    wts = np.random.default_rng(1).random((t, 3)).astype(np.float32)
    x = jnp.asarray(np.random.default_rng(2).normal(size=(t, D)),
                    jnp.float32)
    assert list(np.asarray(sorted_pairs(experts, wts, held)[2])) == [
        0, 0, 0, t, 0]
    check(x, experts, wts, held, row_tile=8)


def test_pairs_all_on_other_chips_give_zero():
    t, held = 12, 4
    experts = np.random.default_rng(3).integers(4, 16, (t, 1))
    experts = np.concatenate([experts, experts + 16], 1)
    wts = np.ones((t, 2), np.float32)
    x = jnp.asarray(np.random.default_rng(4).normal(size=(t, D)),
                    jnp.float32)
    assert (check(x, experts, wts, held, row_tile=16) == 0).all()


def test_a_tokens_pairs_split_over_several_held_experts():
    """Each token's top_k land on different held experts, whose runs then
    add into the same accumulator row."""
    t, held = 9, 8
    experts = np.stack([(np.arange(4) * 2 + i) % held for i in range(t)])
    wts = np.random.default_rng(5).random((t, 4)).astype(np.float32)
    x = jnp.asarray(np.random.default_rng(6).normal(size=(t, D)),
                    jnp.float32)
    check(x, experts, wts, held, row_tile=2)


def test_the_row_tile_follows_the_pass():
    # about t * top_k / router rows an expert, rounded up to 16
    assert ef.row_tile(128, 8, 512) == 16      # Ling, decode
    assert ef.row_tile(1024, 8, 128) == 64     # Keye and sarvam, chunk
    assert ef.row_tile(1024, 8, 256) == 32     # DeepSeek, chunk
    assert ef.row_tile(3, 2, 64) == 16
    # the three weight tiles of a step stay under 12 MiB
    assert ef.hidden_tile(2560, 768, 2) == 768
    assert ef.hidden_tile(4096, 2048, 2) == 512
    assert ef.hidden_tile(7168, 2048, 2) == 256
    assert ef.hidden_tile(64, 24, 4) == 24


def test_the_visit_table_lists_the_hit_experts_first():
    visits = jax.jit(ef.visits)
    expert, count = visits(jnp.asarray([0, 3, 0, 0, 5, 1, 0], jnp.int32))
    assert list(np.asarray(expert)) == [1, 4, 5, 5, 5, 5, 5]
    assert list(np.asarray(count)) == [3, 5, 1, 0, 0, 0, 0]
    expert, count = visits(jnp.zeros(3, jnp.int32))
    assert list(np.asarray(count)) == [0, 0, 0]


def layer(**kw):
    return moe.HeldExpertsMlp(num_experts=16, held=(4, 8), hidden_dim=H,
                              top_k=4, n_group=4, topk_group=2,
                              routed_scale=2.5, **kw)


def test_the_layer_masks_padding_rows_and_holds_nothing_elsewhere():
    # one row tile throughout, so that the kernel compiles for two shapes
    held = layer(row_tile=3)
    x = jnp.asarray(np.random.default_rng(7).normal(size=(2, 11, D)),
                    jnp.float32)
    valid = jnp.asarray(np.random.default_rng(8).random((2, 11)) < 0.7)
    params = {"params": jax.jit(held.init)(jax.random.key(0), x)["params"]}
    counted = jax.jit(lambda p, x, valid: held.apply(p, x, valid,
                                                     mutable=["counters"]))
    got, sown = counted(params, x, valid)
    # the same tokens without their padding neighbours
    keep = np.asarray(valid).reshape(-1)
    alone = jax.jit(held.apply)(params, x.reshape(-1, D)[keep])
    np.testing.assert_allclose(got.reshape(-1, D)[keep], alone, atol=2e-5)
    # a padding row is routed nowhere: the shared expert alone
    shared = moe.GatedMlp(H, name="shared").apply(
        {"params": params["params"]["shared"]}, x.reshape(-1, D))
    np.testing.assert_allclose(got.reshape(-1, D)[~keep], shared[~keep],
                               atol=2e-6)
    assert int(sown["counters"]["expert_rows"]) <= 4 * keep.sum()
    # held experts the router never picks: the shared expert alone
    bias = params["params"]["router_bias"].at[4:12].set(-1e9)
    p = {"params": {**params["params"], "router_bias": bias}}
    got, sown = counted(p, x, valid)
    assert int(sown["counters"]["experts_hit"]) == 0
    np.testing.assert_allclose(got.reshape(-1, D), shared, atol=2e-6)


def test_the_lane_follows_the_shapes():
    # every pass of the four serving cells fits the kernel's VMEM
    for t, d, h, router in ((128, 2560, 768, 512), (1024, 2560, 768, 512),
                            (16, 2048, 768, 128), (1024, 2048, 768, 128),
                            (64, 4096, 2048, 128), (1024, 4096, 2048, 128),
                            (16, 7168, 2048, 256), (1024, 7168, 2048, 256)):
        assert moe.expert_lane(t, d, h, 8, router, jnp.bfloat16) == "kernel"
    # a pass whose [t, d] float32 does not fit is refused, by the layer too
    with pytest.raises(ValueError, match="prefill_chunk"):
        moe.expert_lane(4096, 7168, 2048, 8, 256, jnp.bfloat16)
    wide = moe.HeldExpertsMlp(num_experts=8, held=(0, 8), hidden_dim=128,
                              top_k=2, dtype=jnp.bfloat16)
    x = jax.ShapeDtypeStruct((64 * 1024, 512), jnp.bfloat16)
    with pytest.raises(ValueError, match="does not fit"):
        jax.eval_shape(lambda x: wide.init(jax.random.key(0), x), x)
