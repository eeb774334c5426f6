"""TransformerLM + sequence-parallel train step correctness.

The context-parallel invariant: a (data × sequence)-sharded train step must
produce the same loss, gradients, and updated params as a single-device step
on the full batch — the long-context generalization of the DDP-equivalence
property (SURVEY.md §4).
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from distributed_training_tpu.config import PrecisionConfig
from distributed_training_tpu.models import get_model
from distributed_training_tpu.runtime.mesh import MeshConfig, create_mesh
from distributed_training_tpu.train.lm_step import (
    lm_batch_shardings,
    make_lm_batch,
    make_lm_train_step,
)
from distributed_training_tpu.train.precision import LossScaleState
from distributed_training_tpu.train.train_state import init_train_state

VOCAB = 64


@pytest.fixture(scope="module")
def lm_mesh():
    return create_mesh(MeshConfig(data=2, fsdp=1, model=1, expert=1, sequence=4))


def _make_state(seq_axis, dtype="fp32", seed=0, max_len=128, opt="adam"):
    model = get_model(
        "transformer_lm", num_classes=VOCAB, seq_axis=seq_axis,
        num_layers=2, num_heads=2, hidden_dim=32, max_len=max_len)
    # SGD for strict equivalence tests: Adam's 1/sqrt(v) normalization
    # amplifies fp32 collective-reassociation noise into O(lr) param diffs.
    tx = (optax.sgd(0.1) if opt == "sgd" else
          optax.chain(optax.clip_by_global_norm(1.0), optax.adam(1e-3)))
    state = init_train_state(
        model, jax.random.PRNGKey(seed), (2, 16), tx,
        loss_scale=LossScaleState.create(PrecisionConfig(dtype=dtype)),
        input_dtype=jnp.int32)
    return model, state


def _tokens(b=4, t=65, seed=0):
    return np.random.RandomState(seed).randint(0, VOCAB, (b, t)).astype(np.int32)


def test_lm_forward_shapes():
    _, state = _make_state(None)
    batch = make_lm_batch(_tokens())
    logits = state.apply_fn(
        {"params": state.params}, jnp.asarray(batch["tokens"]), train=False)
    assert logits.shape == (4, 64, VOCAB)
    assert logits.dtype == jnp.float32


def test_sequence_parallel_step_matches_single_device(lm_mesh):
    """One (data=2 × sequence=4) step == one single-device step: loss and
    every updated parameter."""
    tokens = _tokens()
    batch = make_lm_batch(tokens)
    rng = jax.random.PRNGKey(7)

    # Oracle: unsharded model, plain full-batch step.
    _, oracle = _make_state(None, opt="sgd")

    def oracle_step(state, batch):
        def loss_fn(params):
            logits = state.apply_fn(
                {"params": params}, jnp.asarray(batch["tokens"]), train=True,
                rngs={"dropout": rng})
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, jnp.asarray(batch["targets"])).mean()
        loss, grads = jax.value_and_grad(loss_fn)(state.params)
        return state.apply_gradients(grads), loss

    oracle_new, oracle_loss = jax.jit(oracle_step)(oracle, batch)

    # Sequence-parallel: same init seed → same initial params.
    model, sp = _make_state("sequence", opt="sgd")
    gbatch = jax.device_put(
        {k: jnp.asarray(v) for k, v in batch.items()},
        lm_batch_shardings(lm_mesh))
    # model= path: the bound derives from the positional table itself.
    step = make_lm_train_step(lm_mesh, model=model, donate=False)
    sp_new, metrics = step(sp, gbatch, rng)

    np.testing.assert_allclose(
        float(metrics["loss"]), float(oracle_loss), atol=1e-5, rtol=1e-5)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-5, rtol=1e-4),
        sp_new.params, oracle_new.params)
    assert float(metrics["perplexity"]) == pytest.approx(
        float(np.exp(float(oracle_loss))), rel=1e-4)


def test_lm_loss_decreases_under_sequence_parallelism(lm_mesh):
    """Smoke: 30 sequence-parallel steps on a learnable pattern drop the loss."""
    # Learnable data: next token = (token + 1) % VOCAB.
    start = np.random.RandomState(0).randint(0, VOCAB, (8, 1))
    tokens = (start + np.arange(33)) % VOCAB
    batch = make_lm_batch(tokens.astype(np.int32))
    gbatch = jax.device_put(
        {k: jnp.asarray(v) for k, v in batch.items()},
        lm_batch_shardings(lm_mesh))

    model, state = _make_state("sequence")
    step = make_lm_train_step(lm_mesh, max_len=128, donate=False)
    rng = jax.random.PRNGKey(0)
    first = None
    for i in range(30):
        rng, sub = jax.random.split(rng)
        state, metrics = step(state, gbatch, sub)
        if first is None:
            first = float(metrics["loss"])
    last = float(metrics["loss"])
    assert last < first * 0.5, (first, last)


def test_sequence_parallel_zero1_matches_replicated(lm_mesh):
    """SP×ZeRO-1 (VERDICT r2 #2): the flagship long-context path with Adam
    state sharded over the data × sequence replica group must trace the
    SAME training trajectory as the replicated-state SP step — ZeRO is a
    placement, not a math change — while the moments actually live
    sharded."""
    from distributed_training_tpu.parallel.sharding import place_state

    tokens = _tokens(b=4, t=33)
    batch = make_lm_batch(tokens)

    def run(zero_stage, steps=3):
        model, state = _make_state("sequence", opt="adam")
        step = make_lm_train_step(lm_mesh, model=model, donate=False,
                                  zero_stage=zero_stage)
        state = place_state(state, step.state_shardings(state))
        gbatch = jax.device_put(
            {k: jnp.asarray(v) for k, v in batch.items()},
            step.batch_shardings)
        for i in range(steps):
            state, metrics = step(state, gbatch, jax.random.PRNGKey(i))
        return state, metrics

    s0, m0 = run(0)
    s1, m1 = run(1)
    np.testing.assert_allclose(float(m1["loss"]), float(m0["loss"]),
                               atol=1e-6, rtol=1e-6)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-6, rtol=1e-5),
        s1.params, s0.params)

    # The placement claim: at least the transformer-block Adam moments are
    # sharded over the 8-way data×sequence group (divisible dims shard;
    # tiny biases legitimately stay replicated).
    def sharded_leaves(tree):
        return [x for x in jax.tree.leaves(tree)
                if not x.sharding.is_fully_replicated]

    assert not sharded_leaves(s1.params)  # stage 1 keeps params replicated
    n_sharded = len(sharded_leaves(s1.opt_state))
    assert n_sharded > 0, "zero-1 opt state is fully replicated"
    assert not sharded_leaves(s0.opt_state)


def test_sequence_parallel_zero3_shards_params(lm_mesh):
    """Stage 3 under SP: params stored sharded over the replica group,
    gathered on use at step entry; the step still trains (finite loss,
    params move)."""
    from distributed_training_tpu.parallel.sharding import place_state

    model, state = _make_state("sequence", opt="adam")
    step = make_lm_train_step(lm_mesh, model=model, donate=False,
                              zero_stage=3)
    state = place_state(state, step.state_shardings(state))
    assert any(not x.sharding.is_fully_replicated
               for x in jax.tree.leaves(state.params))
    before = jax.tree.map(np.asarray, state.params)
    gbatch = jax.device_put(
        {k: jnp.asarray(v) for k, v in make_lm_batch(_tokens()).items()},
        step.batch_shardings)
    state, metrics = step(state, gbatch, jax.random.PRNGKey(0))
    assert np.isfinite(float(metrics["loss"]))
    moved = jax.tree.leaves(jax.tree.map(
        lambda a, b: float(np.abs(np.asarray(a) - b).max()),
        state.params, before))
    assert max(moved) > 0


def test_sequence_parallel_flash_matches_exact_impl(lm_mesh):
    """attn_impl='flash' under the sequence strategy (ring+flash, VERDICT
    r2 #3): the Pallas hop kernel must trace the same training trajectory
    as the exact-hop ring step."""
    tokens = _tokens(b=4, t=65)
    batch = make_lm_batch(tokens)

    def run(attn_impl, steps=2):
        model = get_model(
            "transformer_lm", num_classes=VOCAB, seq_axis="sequence",
            attn_impl=attn_impl,
            num_layers=2, num_heads=2, hidden_dim=32, max_len=128)
        tx = optax.sgd(0.1)
        state = init_train_state(
            model, jax.random.PRNGKey(0), (2, 16), tx,
            loss_scale=LossScaleState.create(PrecisionConfig(dtype="fp32")),
            input_dtype=jnp.int32)
        step = make_lm_train_step(lm_mesh, model=model, donate=False)
        gbatch = jax.device_put(
            {k: jnp.asarray(v) for k, v in batch.items()},
            step.batch_shardings)
        for i in range(steps):
            state, metrics = step(state, gbatch, jax.random.PRNGKey(i))
        return state, metrics

    s_exact, m_exact = run("exact")
    s_flash, m_flash = run("flash")
    np.testing.assert_allclose(float(m_flash["loss"]),
                               float(m_exact["loss"]), atol=1e-5, rtol=1e-5)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-5, rtol=1e-4),
        s_flash.params, s_exact.params)


@pytest.mark.parametrize("ce_chunk", [None, 8])
def test_sharded_eval_matches_unsharded_oracle(lm_mesh, ce_chunk):
    """Eval at trained lengths under SP (VERDICT r2 #4): the sharded ring
    eval forward must produce the same mean CE as an unsharded twin — and
    it is the only eval path that works when the context fits only
    sharded."""
    from distributed_training_tpu.train.lm_step import make_lm_eval_fn

    model, state = _make_state("sequence")
    batch = make_lm_batch(_tokens(b=4, t=65, seed=11))
    gbatch = jax.device_put(
        {k: jnp.asarray(v) for k, v in batch.items()},
        lm_batch_shardings(lm_mesh))

    eval_fn = make_lm_eval_fn(lm_mesh, model=model, ce_chunk=ce_chunk)
    ce_sharded = float(eval_fn(state.params, gbatch))

    twin = model.clone(seq_axis=None)
    logits = twin.apply({"params": state.params},
                        jnp.asarray(batch["tokens"]), train=False)
    ce_oracle = float(optax.softmax_cross_entropy_with_integer_labels(
        logits, jnp.asarray(batch["targets"])).mean())
    assert ce_sharded == pytest.approx(ce_oracle, abs=1e-5, rel=1e-5)


def test_lm_trainer_sequence_eval_end_to_end(lm_mesh):
    """LMTrainer.evaluate under the sequence strategy goes through the
    sharded path and returns a finite perplexity."""
    from distributed_training_tpu.config import (
        DataConfig,
        LMConfig,
        TrainConfig,
    )
    from distributed_training_tpu.train.lm_trainer import LMTrainer

    cfg = TrainConfig(
        model="transformer_lm", num_epochs=1, eval_every=1,
        lm=LMConfig(seq_len=32, vocab_size=VOCAB, num_layers=2, num_heads=2,
                    hidden_dim=32, max_len=64, train_sequences=64,
                    eval_sequences=16, ce_chunk_size=8),
        data=DataConfig(batch_size=8, prefetch=0))
    tr = LMTrainer(cfg, mesh=lm_mesh)
    _, eval_loader = tr.make_loaders()
    ppl = tr.evaluate(eval_loader)
    assert np.isfinite(ppl) and ppl > 1.0


def test_lm_dynamic_loss_scale_skips_bad_step(lm_mesh):
    """An overflowed gradient skips the whole update: params frozen, step
    not ticked, one hysteresis credit consumed — the commit_gradients skip
    transaction driven through the full sequence-parallel step."""
    model, state = _make_state("sequence", dtype="fp16")
    assert state.loss_scale.dynamic
    batch = make_lm_batch(_tokens())
    gbatch = jax.device_put(
        {k: jnp.asarray(v) for k, v in batch.items()},
        lm_batch_shardings(lm_mesh))
    step = make_lm_train_step(lm_mesh, max_len=128, donate=False)

    # Good step first: update applies, counter ticks.
    good_state, metrics = step(state, gbatch, jax.random.PRNGKey(0))
    assert float(metrics["grads_finite"]) == 1.0
    assert int(good_state.step) == 1

    # Force an overflow: a loss scale beyond fp32 range makes the scaled
    # loss (and thus every gradient) infinite.
    bad = good_state.replace(
        loss_scale=good_state.loss_scale.replace(scale=jnp.float32(1e38)))
    skipped, metrics = step(bad, gbatch, jax.random.PRNGKey(1))
    assert float(metrics["grads_finite"]) == 0.0
    assert int(skipped.step) == 1  # NOT ticked: the scheduler must not move
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)),
        skipped.params, bad.params)
    # First overflow consumes a hysteresis credit (DS hysteresis=2 default)
    # without halving the scale yet.
    assert int(skipped.loss_scale.hysteresis_left) == \
        int(bad.loss_scale.hysteresis_left) - 1
    assert float(skipped.loss_scale.scale) == pytest.approx(1e38)
    assert int(skipped.loss_scale.good_steps) == 0
