"""Block weights stacked over the layers (serving/stacked.py): the stacked
tree gives back the model's own tree bitwise — plain, with matrices over
the size limit left as they are, with blocks whose trees differ (experts
every other layer), and int8-quantized; an engine
over a GPT stacks and its decode launch takes one buffer per kind of block
leaf; a DeepSeek-family engine keeps the per-leaf tree. Hot swap and
rollback on a stacked engine: tests/test_hotswap.py; the engine's tokens
against the sequential generator: tests/test_serving.py and the run-ahead
and packed-input suites, all of which now serve a stacked GPT."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import weights
from benchmark.families import deepseek_v32 as ds_family
from benchmark.reference import deepseek_v32 as ds_ref
from distributed_training_tpu.config import ServeConfig
from distributed_training_tpu.models.gpt import make_transformer_lm
from distributed_training_tpu.observability import trace
from distributed_training_tpu.serving.engine import Engine
from distributed_training_tpu.serving.quantize import (
    QuantizedTensor,
    is_quantized,
    quantize_params,
)
from distributed_training_tpu.serving.stacked import (
    STACK_LEAF_BYTES,
    StackedParams,
    per_layer,
    stack_plan,
)

with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "benchmark", "data", "toy-dsv32.json")) as _fh:
    DS_TOY = json.load(_fh)

LAYERS = 3
BLOCK_LEAVES = 12     # ln1 ×2, qkv ×2, out ×2, ln2 ×2, fc1 ×2, fc2 ×2
TOP_LEAVES = 4        # tok_embed, pos_embed, ln_f ×2 (lm_head: 1 more)


def gpt(**kw):
    kw = {"num_classes": 61, "num_layers": LAYERS, "num_heads": 2,
          "hidden_dim": 32, "max_len": 64, **kw}
    model = make_transformer_lm(**kw)
    params = model.init(jax.random.PRNGKey(0),
                        np.zeros((1, 8), np.int32))["params"]
    return model, params


def assert_bitwise(got, want):
    assert (jax.tree_util.tree_structure(got)
            == jax.tree_util.tree_structure(want))
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def last_build_attrs():
    return [s for s in trace.host_spans()
            if s.name == "setup.program_build"][-1].attrs


def test_a_gpt_tree_round_trips_bitwise():
    _, params = gpt()
    plan = stack_plan(params, "block")
    assert len(plan.groups) == BLOCK_LEAVES
    assert all(len(g) == LAYERS for g in plan.groups)
    stacked = plan.stack(params)
    assert isinstance(stacked, StackedParams)
    assert len(jax.tree_util.tree_leaves(stacked)) == (
        BLOCK_LEAVES + TOP_LEAVES + 1)
    for g in stacked.groups:
        assert g.shape[0] == LAYERS
    assert_bitwise(stacked.unstack(), params)
    assert_bitwise(jax.jit(per_layer)(stacked), params)
    assert per_layer(params) is params


def test_matrices_over_the_limit_keep_their_own_buffers():
    """At 256 wide every block matrix holds more than STACK_LEAF_BYTES:
    the four matrices of each block stay as they are, the eight gains and
    biases stack."""
    _, params = gpt(hidden_dim=256)
    plan = stack_plan(params, "block")
    leaves = jax.tree_util.tree_leaves(params)
    assert len(plan.groups) == 8
    assert all(leaves[k].nbytes <= STACK_LEAF_BYTES
               for g in plan.groups for k in g)
    assert len(plan.rest) == 4 * LAYERS + TOP_LEAVES + 1
    assert sum(leaves[k].nbytes > STACK_LEAF_BYTES
               for k in plan.rest) == 4 * LAYERS
    assert_bitwise(plan.stack(params).unstack(), params)


def test_blocks_whose_trees_differ_stack_what_repeats():
    """Experts every other layer, 2 in one and 4 in the other: the dense
    blocks' MLPs stack with each other, the two expert layers' leaves of
    different shapes stay as they are, attention stacks over all four."""
    _, params = gpt(num_layers=4, moe_num_experts=(2, 4), moe_every=2)
    plan = stack_plan(params, "block")
    sizes = sorted(len(g) for g in plan.groups)
    assert sizes.count(4) == 8          # ln1, attn, ln2 in every block
    assert sizes.count(2) == 4          # mlp fc1 / fc2 of blocks 0 and 2
    n_leaves = len(jax.tree_util.tree_leaves(params))
    assert len(plan.rest) == n_leaves - sum(sizes) > TOP_LEAVES + 1
    assert_bitwise(plan.stack(params).unstack(), params)


def test_an_int8_tree_stacks_values_and_scales_side_by_side():
    _, params = gpt()
    q = quantize_params(params)
    plan = stack_plan(q, "block")
    stacked = plan.stack(q)
    quantized = [g for g in stacked.groups if isinstance(g, QuantizedTensor)]
    assert len(quantized) == 4          # qkv, out, fc1, fc2 kernels
    assert all(g.q.shape[0] == g.scale.shape[0] == LAYERS
               for g in quantized)
    assert is_quantized(stacked)
    assert_bitwise(stacked.unstack(), q)


def test_a_plan_refuses_another_tree():
    _, params = gpt()
    _, deeper = gpt(num_layers=LAYERS + 1)
    with pytest.raises(ValueError, match="plan"):
        stack_plan(params, "block").stack(deeper)
    assert stack_plan(params, "layer") is None


def test_a_gpt_engine_launches_one_buffer_per_kind_of_block_leaf():
    model, params = gpt()
    eng = Engine(model, params, ServeConfig(max_batch=2, max_new_tokens=4,
                                            kv_page_size=8,
                                            prefill_chunk=8))
    assert isinstance(eng.params, StackedParams)
    pools = len(jax.tree_util.tree_leaves(eng._cache))
    assert pools == 2 * LAYERS
    assert last_build_attrs()["launch_buffers"] == (
        BLOCK_LEAVES + TOP_LEAVES + 1 + pools + 3)
    assert_bitwise(per_layer(eng.params), params)
    eng.submit(np.arange(1, 6, dtype=np.int32))
    (done,) = eng.run()
    assert done.tokens.size == 4


def test_a_deepseek_engine_keeps_the_per_leaf_tree():
    flat = weights.make(5, ds_ref.param_shapes(DS_TOY), jnp.float32)
    params = weights.unflatten(flat)
    model = ds_family.build_model(DS_TOY, {"dtype": "fp32",
                                           "logits_dtype": "fp32"})
    assert model.stacked_blocks is None
    eng = Engine(model, params, ServeConfig(
        max_batch=3, max_len=48, max_new_tokens=4, kv_page_size=4,
        kv_pages=36, prefill_chunk=16, temperature=0.0))
    assert eng.params is params
    assert last_build_attrs()["launch_buffers"] == (
        len(flat) + len(jax.tree_util.tree_leaves(eng._cache)) + 3)
