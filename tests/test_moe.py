"""MoE layer tests: gating invariants, expert parallelism, DS flag parity."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_training_tpu.models import get_model
from distributed_training_tpu.models.moe import MoEMlp, TopKGate
from distributed_training_tpu.parallel.sharding import replicated
from distributed_training_tpu.runtime.mesh import MeshConfig, create_mesh


def _tokens(t=64, d=16, seed=0):
    return jnp.asarray(np.random.RandomState(seed).randn(t, d).astype(np.float32))


def test_gate_dispatch_invariants():
    gate = TopKGate(num_experts=4, top_k=1, capacity_factor=2.0)
    x = _tokens()
    (combine, dispatch, aux), _ = gate.init_with_output(
        {"params": jax.random.PRNGKey(0)}, x, train=False)
    t, e, c = combine.shape
    assert (e, t) == (4, 64)
    # Each token goes to at most top_k expert-slots.
    assert int(dispatch.sum()) <= t
    # No slot double-booked: at most one token per (expert, slot).
    assert np.asarray(dispatch.sum(axis=0)).max() <= 1
    # top-1 (Switch semantics): combine weight is the router probability of
    # the selected expert — in (1/E, 1] after softmax, NOT renormalized to 1
    # (that scaling is the router's gradient path).
    per_token = np.asarray(combine.sum(axis=(1, 2)))
    routed = np.asarray(dispatch.any(axis=(1, 2)))
    assert (per_token[routed] > 1.0 / 4).all()
    assert (per_token[routed] <= 1.0 + 1e-5).all()
    assert float(aux) > 0


def test_gate_top2_combine_weights_renormalized():
    gate = TopKGate(num_experts=4, top_k=2, capacity_factor=2.0)
    x = _tokens()
    (combine, dispatch, _), _ = gate.init_with_output(
        {"params": jax.random.PRNGKey(0)}, x, train=False)
    per_token = np.asarray(combine.sum(axis=(1, 2)))
    both_kept = np.asarray(dispatch.sum(axis=(1, 2))) == 2
    np.testing.assert_allclose(per_token[both_kept], 1.0, atol=1e-5)


def test_gate_top2_routes_two_experts():
    gate = TopKGate(num_experts=4, top_k=2, capacity_factor=2.0)
    x = _tokens(t=32)
    (combine, dispatch, _), _ = gate.init_with_output(
        {"params": jax.random.PRNGKey(0)}, x, train=False)
    per_token_slots = np.asarray(dispatch.sum(axis=(1, 2)))
    assert per_token_slots.max() == 2
    assert (np.asarray(combine) >= 0).all()


def test_gate_capacity_drops_overflow():
    # capacity_factor tiny → capacity 1 per expert → at most E tokens kept.
    gate = TopKGate(num_experts=2, top_k=1, capacity_factor=0.01,
                    min_capacity=1)
    x = _tokens(t=64)
    (_, dispatch, _), _ = gate.init_with_output(
        {"params": jax.random.PRNGKey(0)}, x, train=False)
    assert int(dispatch.sum()) <= 2


def test_gate_rejects_top3():
    gate = TopKGate(num_experts=4, top_k=3)
    with pytest.raises(ValueError, match="top 1 and 2"):
        gate.init(jax.random.PRNGKey(0), _tokens(), train=False)


@pytest.mark.parametrize("policy", ["RSample", "Jitter"])
def test_noisy_gate_policies_perturb_routing(policy):
    gate = TopKGate(num_experts=8, top_k=1, noisy_gate_policy=policy)
    x = _tokens(t=128, d=8, seed=1)
    variables = gate.init(
        {"params": jax.random.PRNGKey(0), "gate": jax.random.PRNGKey(1)},
        x, train=True)
    out_a = gate.apply(variables, x, train=True,
                       rngs={"gate": jax.random.PRNGKey(2)})
    out_b = gate.apply(variables, x, train=True,
                       rngs={"gate": jax.random.PRNGKey(3)})
    out_eval = gate.apply(variables, x, train=False)
    out_eval2 = gate.apply(variables, x, train=False)
    assert not np.allclose(np.asarray(out_a[0]), np.asarray(out_b[0]))
    np.testing.assert_array_equal(
        np.asarray(out_eval[0]), np.asarray(out_eval2[0]))  # eval: no noise


@pytest.mark.parametrize("mlp_type", ["standard", "residual"])
def test_moe_mlp_forward(mlp_type):
    moe = MoEMlp(num_experts=4, hidden_dim=32, mlp_type=mlp_type)
    x = jnp.asarray(np.random.RandomState(0).randn(2, 8, 16).astype(np.float32))
    variables = moe.init(jax.random.PRNGKey(0), x, train=False)
    out, aux_vars = moe.apply(variables, x, train=False, mutable=["aux_loss"])
    assert out.shape == x.shape
    assert np.isfinite(np.asarray(out)).all()
    leaves = jax.tree.leaves(dict(aux_vars).get("aux_loss", {}))
    assert leaves and float(leaves[0]) > 0


def test_moe_mlp_rejects_bad_type():
    moe = MoEMlp(num_experts=4, hidden_dim=32, mlp_type="bogus")
    x = jnp.zeros((2, 4, 16))
    with pytest.raises(ValueError, match="standard, residual"):
        moe.init(jax.random.PRNGKey(0), x, train=False)


def test_expert_parallel_matches_single_device(mesh):
    """EP sharding must be a pure placement choice: outputs identical."""
    moe = MoEMlp(num_experts=8, hidden_dim=32, expert_axis=None)
    x = jnp.asarray(np.random.RandomState(2).randn(4, 8, 16).astype(np.float32))
    variables = moe.init(jax.random.PRNGKey(0), x, train=False)
    ref, _ = moe.apply(variables, x, train=False, mutable=["aux_loss"])

    ep_mesh = create_mesh(MeshConfig(data=1, expert=8, fsdp=1, model=1,
                                     sequence=1))
    moe_ep = MoEMlp(num_experts=8, hidden_dim=32, expert_axis="expert")

    def fwd(v, x):
        out, _ = moe_ep.apply(v, x, train=False, mutable=["aux_loss"])
        return out

    with ep_mesh:
        out = jax.jit(fwd, in_shardings=(replicated(ep_mesh),
                                         replicated(ep_mesh)),
                      out_shardings=replicated(ep_mesh))(variables, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


def test_moe_model_registry_and_forward():
    model = get_model("moe_mlp", num_classes=10, num_experts=(4,),
                      mlp_type="residual", top_k=2)
    x = jnp.zeros((2, 32, 32, 3))
    variables = model.init(jax.random.PRNGKey(0), x, train=False)
    logits = model.apply(variables, x, train=False)
    assert logits.shape == (2, 10)


# -- the softmax top-k router of HeldExpertsMlp ------------------------------

def brute_softmax_route(logits, k):
    """Row by row in numpy float64: softmax over all experts, the ``k``
    highest (ties to the lower index), renormalised over the chosen."""
    experts, weights = [], []
    for row in logits.astype(np.float64):
        p = np.exp(row - row.max())
        p /= p.sum()
        chosen = sorted(range(row.size), key=lambda i: (-p[i], i))[:k]
        experts.append(chosen)
        weights.append(p[chosen] / p[chosen].sum())
    return np.array(experts), np.array(weights)


@pytest.mark.parametrize("seed,e,k,ties", [
    (0, 128, 8, False), (1, 128, 8, True), (2, 16, 2, True), (3, 8, 8, False)])
def test_the_softmax_router_against_a_brute_force_routing(seed, e, k, ties):
    """Against a float64 routing and against the benchmark's plain
    reference. With ``ties`` the logits are rounded to halves, so that a
    row's k-th probability is shared: the lower index wins. The weights are
    float32 softmaxes: 1e-6 of values under 1."""
    from benchmark.reference import keye_vl2 as ref
    from distributed_training_tpu.models.moe import softmax_topk_route

    rng = np.random.default_rng(seed)
    logits = rng.normal(0, 1.7, (24, e)).astype(np.float32)
    if ties:
        logits = np.round(logits * 2) / 2
    experts, w = softmax_topk_route(jnp.asarray(logits), top_k=k)
    want_e, want_w = brute_softmax_route(logits, k)
    np.testing.assert_array_equal(np.asarray(experts), want_e)
    np.testing.assert_allclose(np.asarray(w), want_w, atol=1e-6)
    np.testing.assert_allclose(np.asarray(w).sum(-1), 1.0, atol=1e-6)
    # the reference routes from x and the router's matrix: the identity
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "benchmark", "data", "toy-keye.json")) as fh:
        cfg = {**json.load(fh), "num_experts_per_tok": k}
    with jax.default_matmul_precision("highest"):
        ref_e, ref_w = ref.route(jnp.asarray(logits),
                                 {"router": jnp.eye(e)}, cfg)
    np.testing.assert_array_equal(np.asarray(ref_e), want_e)
    np.testing.assert_allclose(np.asarray(ref_w), want_w, atol=1e-6)


def test_the_held_experts_layer_takes_its_router_from_the_model():
    """``scoring`` is a field of the model's structure: the softmax router
    has no selection bias leaf, and a name that is neither is refused."""
    from distributed_training_tpu.models.moe import HeldExpertsMlp

    x = jnp.ones((3, 16))
    kw = dict(num_experts=8, held=(0, 8), hidden_dim=8, top_k=2)
    soft = HeldExpertsMlp(**kw, scoring="softmax", shared_experts=0)
    assert set(soft.init(jax.random.key(0), x)["params"]) == {
        "router", "w1", "w2", "w3"}
    sig = HeldExpertsMlp(**kw, n_group=2, topk_group=1, routed_scale=2.5)
    assert set(sig.init(jax.random.key(0), x)["params"]) == {
        "router", "router_bias", "w1", "w2", "w3", "shared"}
    with pytest.raises(ValueError, match="scoring"):
        HeldExpertsMlp(**kw, scoring="tanh").init(jax.random.key(0), x)
