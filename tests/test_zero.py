"""ZeRO correctness (SURVEY.md §4): sharded optimizer update == unsharded.

Stage mapping under test (see ``parallel/sharding.py``):
- stage 1: optimizer state sharded over `data` → same params as stage 0.
- stage 3: params + optimizer state sharded (FSDP) → same params as stage 0.
- fsdp mesh axis: same property on a 2×4 data×fsdp mesh.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from distributed_training_tpu.config import PrecisionConfig
from distributed_training_tpu.models import get_model
from distributed_training_tpu.parallel.sharding import (
    state_shardings,
    zero_leaf_sharding,
)
from distributed_training_tpu.train.precision import LossScaleState
from distributed_training_tpu.train.step import make_train_step
from distributed_training_tpu.train.train_state import init_train_state


def _make_state(opt="sgd"):
    # SGD+momentum for strict 1e-5 equivalence (linear in grads — see
    # test_dp_equivalence for why Adam needs a looser bound).
    model = get_model("resnet_micro", num_classes=10, stem="cifar")
    if opt == "adam":
        tx = optax.chain(optax.clip_by_global_norm(1.0), optax.adam(1e-2))
    else:
        tx = optax.chain(
            optax.clip_by_global_norm(1.0), optax.sgd(1e-2, momentum=0.9))
    return init_train_state(
        model, jax.random.PRNGKey(0), (8, 8, 8, 3), tx,
        loss_scale=LossScaleState.create(PrecisionConfig(dtype="fp32")))


def _batch(seed=0):
    rng = np.random.RandomState(seed)
    return {
        "image": rng.rand(16, 8, 8, 3).astype(np.float32),
        "label": rng.randint(0, 10, 16).astype(np.int32),
    }


def _maxdiff(a, b):
    return max(
        float(jnp.max(jnp.abs(x - y)))
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


@pytest.mark.parametrize("stage", [1, 3])
def test_zero_stage_matches_dp(mesh, stage):
    batch = _batch()
    rng = jax.random.PRNGKey(5)

    s_dp = _make_state()
    dp_step = make_train_step(mesh, zero_stage=0, donate=False)
    dp_out, _ = dp_step(s_dp, batch, rng)

    s_z = _make_state()
    z_step = make_train_step(mesh, zero_stage=stage, donate=False)
    z_out, _ = z_step(s_z, batch, rng)

    assert _maxdiff(dp_out.params, z_out.params) < 1e-5
    assert _maxdiff(dp_out.batch_stats, z_out.batch_stats) < 1e-5


def test_zero1_sharded_adam_matches_unsharded_adam(mesh):
    """SURVEY.md §4: 'sharded-Adam update == unsharded-Adam update'.

    Tolerance: Adam's step-1 normalization amplifies ~1e-6 reduction-order
    grad noise to O(lr) on near-zero grads (see test_dp_equivalence);
    2e-2 = 2·lr bounds that amplification.
    """
    batch = _batch()
    rng = jax.random.PRNGKey(5)
    dp_out, _ = make_train_step(mesh, zero_stage=0, donate=False)(
        _make_state("adam"), batch, rng)
    z_out, _ = make_train_step(mesh, zero_stage=1, donate=False)(
        _make_state("adam"), batch, rng)
    assert _maxdiff(dp_out.params, z_out.params) < 2e-2


def test_zero1_opt_state_is_actually_sharded(mesh):
    state = _make_state()
    step = make_train_step(mesh, zero_stage=1, donate=False)
    out, _ = step(state, _batch(), jax.random.PRNGKey(0))
    # The Adam moments for large params must be sharded over `data`, and
    # consume ~1/8 the per-device memory of the replicated layout.
    shardings = state_shardings(state, mesh, 1)
    adam_mu = None
    for leaf_sh, leaf in zip(
            jax.tree.leaves(shardings.opt_state), jax.tree.leaves(out.opt_state)):
        if hasattr(leaf, "shape") and leaf.ndim == 4 and leaf.size > 8:
            adam_mu = (leaf_sh, leaf)
            break
    assert adam_mu is not None
    sh, leaf = adam_mu
    assert not sh.is_fully_replicated, "large moment tensors must be sharded"
    # The realized array must carry that sharding.
    assert not leaf.sharding.is_fully_replicated


def test_zero3_params_sharded(mesh):
    state = _make_state()
    step = make_train_step(mesh, zero_stage=3, donate=False)
    out, _ = step(state, _batch(), jax.random.PRNGKey(0))
    big = [p for p in jax.tree.leaves(out.params) if p.size > 10000]
    assert big and all(not p.sharding.is_fully_replicated for p in big)


def test_fsdp_mesh_axis_matches_dp(mesh, mesh2x4):
    batch = _batch(seed=2)
    rng = jax.random.PRNGKey(9)

    s_dp = _make_state()
    dp_out, _ = make_train_step(mesh, zero_stage=0, donate=False)(
        s_dp, batch, rng)

    s_f = _make_state()
    f_out, _ = make_train_step(mesh2x4, zero_stage=0, donate=False)(
        s_f, batch, rng)

    assert _maxdiff(dp_out.params, f_out.params) < 1e-5


def test_zero_stage_footprints_shrink(mesh):
    """The memory accounting ZeRO exists for (VERDICT r2 #5): per-device
    persistent state bytes must satisfy stage3 < stage1 < stage0 on the
    8-device mesh, with each stage's reduction matching its placement —
    stage 1 shards the optimizer moments, stage 3 additionally shards the
    params (small/indivisible leaves legitimately stay replicated)."""
    from distributed_training_tpu.parallel.sharding import place_state

    def device0_bytes(tree):
        dev = jax.devices()[0]
        total = 0
        for leaf in jax.tree.leaves(tree):
            for shard in leaf.addressable_shards:
                if shard.device == dev:
                    total += shard.data.size * shard.data.dtype.itemsize
        return total

    footprint = {}
    for stage in (0, 1, 3):
        state = _make_state(opt="adam")
        placed = place_state(state, state_shardings(state, mesh, stage))
        footprint[stage] = {
            "params": device0_bytes(placed.params),
            "opt": device0_bytes(placed.opt_state),
        }

    full_p = footprint[0]["params"]
    full_o = footprint[0]["opt"]
    # Stage 1: params still replicated; moments shed most of their bytes
    # (8-way on every divisible leaf).
    assert footprint[1]["params"] == full_p
    assert footprint[1]["opt"] < 0.5 * full_o
    # Stage 3: params shed too; opt no larger than stage 1's.
    assert footprint[3]["params"] < 0.5 * full_p
    assert footprint[3]["opt"] <= footprint[1]["opt"]
    # Strict total ordering.
    total = {s: v["params"] + v["opt"] for s, v in footprint.items()}
    assert total[3] < total[1] < total[0]


def test_zero_leaf_sharding_rules(mesh):
    # Large divisible tensor → sharded on its largest divisible dim.
    w = jnp.zeros((64, 3, 3, 128))
    sh = zero_leaf_sharding(w, mesh, ("data",))
    assert not sh.is_fully_replicated
    # Tiny/indivisible tensor → replicated.
    b = jnp.zeros((10,))
    assert zero_leaf_sharding(b, mesh, ("data",)).is_fully_replicated
    scalar = jnp.float32(1.0)
    assert zero_leaf_sharding(scalar, mesh, ("data",)).is_fully_replicated


class TestCpuOffload:
    """ZeRO-Offload: sharded optimizer state placed in pinned host memory.

    The CPU backend accepts pinned_host PLACEMENT (device_put) but cannot
    execute a jitted step with host-memory out_shardings ("side-effect ops
    cannot be replicated"), so the executing-step validation lives on the
    real chip (round 4: 2408 img/s offloaded vs 2528 on-device at
    zero-1); these tests pin the placement metadata and the refusal
    contract.
    """

    def test_offload_requires_zero_stage(self, mesh):
        state = _make_state("adam")
        with pytest.raises(ValueError, match="cpu_offload requires"):
            state_shardings(state, mesh, 0, cpu_offload=True)

    def test_opt_state_placed_in_pinned_host(self, mesh):
        state = _make_state("adam")
        sh = state_shardings(state, mesh, 1, cpu_offload=True)
        opt_kinds = {s.memory_kind for s in jax.tree.leaves(sh.opt_state)}
        assert opt_kinds == {"pinned_host"}
        # params stay on device
        param_kinds = {s.memory_kind for s in jax.tree.leaves(sh.params)}
        assert "pinned_host" not in param_kinds

    def test_tp_opt_state_placed_in_pinned_host(self, mesh):
        from distributed_training_tpu.parallel.tensor_parallel import (
            tp_state_shardings,
        )

        state = _make_state("adam")
        sh = tp_state_shardings(state, mesh, 1, cpu_offload=True)
        opt_kinds = {s.memory_kind for s in jax.tree.leaves(sh.opt_state)}
        assert opt_kinds == {"pinned_host"}
        with pytest.raises(ValueError, match="cpu_offload requires"):
            tp_state_shardings(state, mesh, 0, cpu_offload=True)

    def test_host_placement_works_on_cpu_backend(self, mesh):
        """device_put of a host-built state onto the offload shardings
        succeeds (arrays land addressable with the host memory kind)."""
        from distributed_training_tpu.parallel.sharding import place_state

        state = _make_state("adam")
        placed = place_state(state, state_shardings(
            state, mesh, 1, cpu_offload=True))
        kinds = {x.sharding.memory_kind
                 for x in jax.tree.leaves(placed.opt_state)}
        assert kinds == {"pinned_host"}
