"""Composed parallelism: TP×SP and PP×TP train-step correctness.

Round-2 extension (VERDICT r1 #6): the explicit strategies (ring-attention
sequence parallelism, GPipe pipelining) compose with declarative megatron TP
through *partial-manual* shard_map — the strategy's own axes are manual,
``model`` stays automatic, and GSPMD inserts the row-parallel psums inside
each shard. The invariant tested here is the same DDP-equivalence property
as the single-strategy oracles (SURVEY.md §4): one composed step == one
single-device step, loss and every updated parameter.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from distributed_training_tpu.config import PrecisionConfig
from distributed_training_tpu.models import get_model
from distributed_training_tpu.parallel.sharding import place_state
from distributed_training_tpu.parallel.tensor_parallel import tp_state_shardings
from distributed_training_tpu.runtime.mesh import MeshConfig, create_mesh
from distributed_training_tpu.train.lm_step import (
    lm_batch_shardings,
    make_lm_batch,
    make_lm_train_step,
    make_pp_lm_train_step,
)
from distributed_training_tpu.train.precision import LossScaleState
from distributed_training_tpu.train.train_state import init_train_state

VOCAB = 64


@pytest.fixture(scope="module")
def sp_tp_mesh():
    return create_mesh(MeshConfig(data=2, sequence=2, model=2))


@pytest.fixture(scope="module")
def pp_tp_mesh():
    return create_mesh(MeshConfig(data=2, pipe=2, model=2))


def _make_state(seq_axis, seed=0):
    model = get_model(
        "transformer_lm", num_classes=VOCAB, seq_axis=seq_axis,
        num_layers=2, num_heads=2, hidden_dim=32, max_len=128)
    # SGD: strict 1e-5 equivalence (Adam amplifies reassociation noise).
    tx = optax.sgd(0.1)
    state = init_train_state(
        model, jax.random.PRNGKey(seed), (2, 16), tx,
        loss_scale=LossScaleState.create(PrecisionConfig(dtype="fp32")),
        input_dtype=jnp.int32)
    return model, state


def _tokens(b=4, t=33, seed=0):
    return np.random.RandomState(seed).randint(0, VOCAB, (b, t)).astype(np.int32)


def _oracle_step(state, batch, rng):
    def loss_fn(params):
        logits = state.apply_fn(
            {"params": params}, jnp.asarray(batch["tokens"]), train=True,
            rngs={"dropout": rng})
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, jnp.asarray(batch["targets"])).mean()
    loss, grads = jax.value_and_grad(loss_fn)(state.params)
    return state.apply_gradients(grads), loss


def _assert_tree_close(a, b, atol=1e-5, rtol=1e-4):
    jax.tree.map(
        lambda x, y: np.testing.assert_allclose(
            np.asarray(x), np.asarray(y), atol=atol, rtol=rtol), a, b)


class TestSequenceTensorComposition:
    def test_sp_tp_step_matches_single_device(self, sp_tp_mesh):
        """(data=2 × sequence=2 × model=2) ring step with megatron-sharded
        weights == single-device step."""
        batch = make_lm_batch(_tokens())
        rng = jax.random.PRNGKey(7)

        _, oracle = _make_state(None)
        oracle_new, oracle_loss = jax.jit(_oracle_step)(oracle, batch, rng)

        model, sp = _make_state("sequence")
        sp = place_state(sp, tp_state_shardings(sp, sp_tp_mesh, zero_stage=0))
        gbatch = jax.device_put(
            {k: jnp.asarray(v) for k, v in batch.items()},
            lm_batch_shardings(sp_tp_mesh))
        step = make_lm_train_step(sp_tp_mesh, model=model, donate=False)
        sp_new, metrics = step(sp, gbatch, rng)

        np.testing.assert_allclose(
            float(metrics["loss"]), float(oracle_loss), atol=1e-5, rtol=1e-5)
        _assert_tree_close(sp_new.params, oracle_new.params)

    def test_sp_tp_weights_actually_sharded(self, sp_tp_mesh):
        """The composed state's attention/MLP weights really split over the
        model axis (not silently replicated)."""
        _, state = _make_state("sequence")
        placed = place_state(
            state, tp_state_shardings(state, sp_tp_mesh, zero_stage=0))
        qkv = placed.params["block0"]["attn"]["qkv"]["kernel"]
        # [d, 3, H, hd] with H=2 sharded over model=2 → per-device H dim 1.
        shard_shape = qkv.sharding.shard_shape(qkv.shape)
        assert shard_shape[2] == qkv.shape[2] // 2
        fc1 = placed.params["block0"]["mlp"]["fc1"]["kernel"]
        assert fc1.sharding.shard_shape(fc1.shape)[1] == fc1.shape[1] // 2

    def test_sp_tp_loss_decreases(self, sp_tp_mesh):
        """Smoke: 25 composed steps on a learnable pattern drop the loss."""
        start = np.random.RandomState(0).randint(0, VOCAB, (8, 1))
        tokens = (start + np.arange(33)) % VOCAB
        batch = make_lm_batch(tokens.astype(np.int32))
        gbatch = jax.device_put(
            {k: jnp.asarray(v) for k, v in batch.items()},
            lm_batch_shardings(sp_tp_mesh))

        model, state = _make_state("sequence")
        state = place_state(
            state, tp_state_shardings(state, sp_tp_mesh, zero_stage=0))
        step = make_lm_train_step(sp_tp_mesh, model=model, donate=False)
        rng = jax.random.PRNGKey(0)
        first = None
        for _ in range(25):
            rng, sub = jax.random.split(rng)
            state, metrics = step(state, gbatch, sub)
            if first is None:
                first = float(metrics["loss"])
        assert float(metrics["loss"]) < first * 0.6, (
            first, float(metrics["loss"]))


class TestPipelineTensorComposition:
    def test_pp_tp_step_matches_single_device(self, pp_tp_mesh):
        """(data=2 × pipe=2 × model=2) GPipe step with megatron-sharded
        stage weights == single-device step."""
        from distributed_training_tpu.parallel.pipeline import (
            stack_block_params,
        )
        from distributed_training_tpu.train.train_state import TrainState

        model, _ = _make_state(None)
        rng0 = jax.random.PRNGKey(0)
        batch = make_lm_batch(_tokens())
        step_rng = jax.random.PRNGKey(7)

        variables = model.init({"params": rng0}, jnp.zeros((1, 8), jnp.int32),
                               train=False)

        def oracle_step(params, batch):
            def loss_fn(p):
                logits = model.apply(
                    {"params": p}, jnp.asarray(batch["tokens"]), train=False)
                return optax.softmax_cross_entropy_with_integer_labels(
                    logits, jnp.asarray(batch["targets"])).mean()
            loss, grads = jax.value_and_grad(loss_fn)(params)
            return jax.tree.map(lambda p, g: p - 0.1 * g, params, grads), loss

        oracle_params, oracle_loss = jax.jit(oracle_step)(
            dict(variables["params"]), batch)
        oracle_stacked, oracle_rest = stack_block_params(
            oracle_params, model.num_layers)

        step = make_pp_lm_train_step(pp_tp_mesh, model=model,
                                     num_microbatches=2, donate=False)
        plm = step.pipelined
        assert plm.tp_size == 2
        state = TrainState.create(
            apply_fn=plm.apply_fn, params=plm.init_params(rng0),
            tx=optax.sgd(0.1),
            loss_scale=LossScaleState.create(PrecisionConfig(dtype="fp32")))
        state = place_state(state, step.state_shardings(state))
        gbatch = jax.device_put(
            {k: jnp.asarray(v) for k, v in batch.items()},
            step.batch_shardings)
        new_state, metrics = step(state, gbatch, step_rng)

        np.testing.assert_allclose(
            float(metrics["loss"]), float(oracle_loss), atol=1e-5, rtol=1e-5)
        _assert_tree_close(new_state.params["blocks"], oracle_stacked)
        for key in ("tok_embed", "pos_embed", "ln_f", "lm_head"):
            _assert_tree_close(new_state.params[key], oracle_rest[key])

    def test_pp_tp_weights_sharded_both_axes(self, pp_tp_mesh):
        """Stacked block weights split over pipe (layer dim) AND model (TP
        dim); vocab-parallel embed/head split over model."""
        from distributed_training_tpu.train.train_state import TrainState

        model, _ = _make_state(None)
        step = make_pp_lm_train_step(pp_tp_mesh, model=model,
                                     num_microbatches=2, donate=False)
        plm = step.pipelined
        state = TrainState.create(
            apply_fn=plm.apply_fn, params=plm.init_params(jax.random.PRNGKey(0)),
            tx=optax.sgd(0.1),
            loss_scale=LossScaleState.create(PrecisionConfig(dtype="fp32")))
        placed = place_state(state, step.state_shardings(state))
        qkv = placed.params["blocks"]["attn"]["qkv"]["kernel"]
        # [L, d, 3, H, hd]: L over pipe, H over model.
        ss = qkv.sharding.shard_shape(qkv.shape)
        assert ss[0] == qkv.shape[0] // 2, "layer dim not pipe-sharded"
        assert ss[3] == qkv.shape[3] // 2, "head dim not model-sharded"
        emb = placed.params["tok_embed"]["embedding"]
        assert emb.sharding.shard_shape(emb.shape)[0] == emb.shape[0] // 2, (
            "vocab dim not model-sharded")


class TestLMTrainerComposition:
    def _cfg(self, **mesh_kw):
        from distributed_training_tpu.config import (
            DataConfig,
            LMConfig,
            MeshSpec,
            TrainConfig,
        )

        return TrainConfig(
            model="transformer_lm",
            num_epochs=1,
            log_interval=2,
            eval_every=1,
            mesh=MeshSpec(data=-1, **mesh_kw),
            data=DataConfig(batch_size=8, max_steps_per_epoch=4),
            lm=LMConfig(seq_len=32, vocab_size=VOCAB, num_layers=2,
                        num_heads=2, hidden_dim=32, max_len=64,
                        train_sequences=64, eval_sequences=16),
        )

    def test_lm_trainer_runs_sp_tp(self):
        from distributed_training_tpu.train.lm_trainer import LMTrainer

        trainer = LMTrainer(self._cfg(sequence=2, model=2))
        assert trainer.strategy == "sequence" and trainer.tp_size == 2
        result = trainer.fit()
        assert result["steps"] == 4
        assert np.isfinite(result["final_perplexity"])

    def test_lm_trainer_runs_pp_tp(self):
        from distributed_training_tpu.train.lm_trainer import LMTrainer

        trainer = LMTrainer(self._cfg(pipe=2, model=2))
        assert trainer.strategy == "pipeline" and trainer.tp_size == 2
        result = trainer.fit()
        assert result["steps"] == 4
        assert np.isfinite(result["final_perplexity"])

    def test_lm_trainer_runs_sequence_pipe(self):
        """seq×pipe composes since round 5 (was the engine's last refusal):
        the pipeline strategy drives a seq_axis model with ring attention
        inside each tick."""
        from distributed_training_tpu.train.lm_trainer import LMTrainer

        trainer = LMTrainer(self._cfg(sequence=2, pipe=2))
        assert trainer.strategy == "pipeline"
        result = trainer.fit()
        assert result["steps"] == 4
        assert np.isfinite(result["final_perplexity"])


class TestSequenceExpertComposition:
    """EP×SP (VERDICT r2 #8): MoE decoder FFNs under the ring strategy.

    Expert parallelism is pure *placement* — the gate, capacity, and aux
    loss are shard-local under SP either way (the DeepSpeed per-rank
    semantics) — so the invariant is placement-invariance: the dp×sp×ep
    step must trace exactly the dp×sp step with experts unsharded, while
    the expert weights actually live split over the expert axis.
    """

    def _moe_state(self, seed=0):
        model = get_model(
            "transformer_lm", num_classes=VOCAB, seq_axis="sequence",
            num_layers=2, num_heads=2, hidden_dim=32, max_len=128,
            moe_num_experts=4, moe_top_k=1, moe_capacity_factor=2.0,
            moe_expert_axis="expert")
        tx = optax.sgd(0.1)
        state = init_train_state(
            model, jax.random.PRNGKey(seed), (2, 16), tx,
            loss_scale=LossScaleState.create(PrecisionConfig(dtype="fp32")),
            input_dtype=jnp.int32)
        return model, state

    def test_sp_ep_step_is_placement_invariant(self):
        devices = jax.devices()
        ep_mesh = create_mesh(MeshConfig(data=2, sequence=2, expert=2),
                              devices=devices)
        ref_mesh = create_mesh(MeshConfig(data=2, sequence=2),
                               devices=devices[:4])
        batch = make_lm_batch(_tokens(b=4, t=33))
        rng = jax.random.PRNGKey(9)

        def run(mesh):
            model, state = self._moe_state()
            step = make_lm_train_step(mesh, model=model, donate=False)
            state = place_state(state, step.state_shardings(state))
            gbatch = jax.device_put(
                {k: jnp.asarray(v) for k, v in batch.items()},
                step.batch_shardings)
            new_state, metrics = step(state, gbatch, rng)
            return new_state, metrics

        s_ep, m_ep = run(ep_mesh)
        s_ref, m_ref = run(ref_mesh)
        np.testing.assert_allclose(float(m_ep["loss"]), float(m_ref["loss"]),
                                   atol=1e-6, rtol=1e-6)
        assert float(m_ep["aux_loss"]) > 0  # the MoE objective is live
        _assert_tree_close(
            jax.tree.map(np.asarray, s_ep.params),
            jax.tree.map(np.asarray, s_ref.params), atol=1e-5, rtol=1e-4)

        # Placement claim: expert weights split over the expert axis.
        w1 = s_ep.params["block1"]["moe_mlp"]["experts"]["w1"]
        assert w1.sharding.shard_shape(w1.shape)[0] == w1.shape[0] // 2

    def test_lm_trainer_runs_sp_ep(self):
        import dataclasses

        from distributed_training_tpu.train.lm_trainer import LMTrainer

        cfg = TestLMTrainerComposition()._cfg(sequence=2)
        cfg = cfg.replace(
            mesh=dataclasses.replace(cfg.mesh, data=2, sequence=2, expert=2),
            moe=dataclasses.replace(
                cfg.moe, enabled=True, num_experts=(4,), top_k=1,
                capacity_factor=2.0),
            lm=dataclasses.replace(cfg.lm, train_sequences=64,
                                   eval_sequences=32))
        trainer = LMTrainer(cfg)
        assert trainer.strategy == "sequence"
        result = trainer.fit()
        assert np.isfinite(result["final_perplexity"])


class TestSequenceGradAccum:
    def test_sp_accum_matches_single_shot(self, sp_tp_mesh):
        """SP grad accumulation (scan inside the shard_map body) == the
        single-shot step on the same effective batch: equal-sized
        microbatches make the mean of micro-means the full-batch mean, so
        grads, loss, and the updated params agree to fp32 tolerance.
        Composes with TP (model axis) for free — same partial-manual body."""
        tokens = _tokens(b=8)
        batch = make_lm_batch(tokens)
        rng = jax.random.PRNGKey(3)

        model, base = _make_state("sequence")
        placed = place_state(
            base, tp_state_shardings(base, sp_tp_mesh, zero_stage=0))
        gbatch = jax.device_put(
            {k: jnp.asarray(v) for k, v in batch.items()},
            lm_batch_shardings(sp_tp_mesh))

        one = make_lm_train_step(sp_tp_mesh, model=model, donate=False)
        acc = make_lm_train_step(sp_tp_mesh, model=model, donate=False,
                                 grad_accum_steps=2)
        s1, m1 = one(placed, gbatch, rng)
        s2, m2 = acc(placed, gbatch, rng)

        np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]),
                                   rtol=1e-6)
        _assert_tree_close(s2.params, s1.params, atol=1e-6, rtol=1e-5)

    def test_lm_trainer_runs_sp_accum(self):
        import dataclasses

        from distributed_training_tpu.train.lm_trainer import LMTrainer

        cfg = TestLMTrainerComposition()._cfg(sequence=2)
        # sequence=2 leaves data=4; eval stays micro-sized (8×4=32), so the
        # eval split must cover at least one global batch.
        cfg = cfg.replace(
            gradient_accumulation_steps=2,
            # accum doubles the effective train batch to 64 sequences/step;
            # the splits must cover max_steps_per_epoch=4 of them (and eval
            # one micro-sized global batch of 32).
            lm=dataclasses.replace(cfg.lm, train_sequences=256,
                                   eval_sequences=64))
        trainer = LMTrainer(cfg)
        assert trainer.grad_accum == 2 and trainer.strategy == "sequence"
        result = trainer.fit()
        assert result["steps"] == 4
        assert np.isfinite(result["final_perplexity"])


class TestSequencePipeComposition:
    """SP×PP (round 5): ring attention over the manual sequence axis
    INSIDE each pipeline tick — two explicit schedules over one
    activation stream, previously the engine's last composition refusal.
    The oracle property: identical params + batch ⇒ the composed step
    matches the plain (seq_axis=None) pipeline step, whose own
    equivalence to the single-device model is already pinned."""

    def test_sp_pp_step_matches_plain_pp(self):
        from distributed_training_tpu.train.train_state import TrainState

        toks = _tokens(b=8, t=17)
        batch = make_lm_batch(toks)
        rng = jax.random.PRNGKey(7)

        def run(seq_axis, mesh):
            model = get_model(
                "transformer_lm", num_classes=VOCAB, seq_axis=seq_axis,
                num_layers=2, num_heads=2, hidden_dim=32, max_len=128)
            step = make_pp_lm_train_step(mesh, model=model,
                                         num_microbatches=2, donate=False)
            plm = step.pipelined
            state = TrainState.create(
                apply_fn=plm.apply_fn,
                params=plm.init_params(jax.random.PRNGKey(0)),
                tx=optax.sgd(0.1),
                loss_scale=LossScaleState.create(
                    PrecisionConfig(dtype="fp32")))
            state = jax.device_put(state, step.state_shardings(state))
            gbatch = jax.device_put(
                {k: jnp.asarray(v) for k, v in batch.items()},
                step.batch_shardings)
            new_state, m = step(state, gbatch, rng)
            return jax.device_get(new_state.params), m

        pp = create_mesh(MeshConfig(data=4, pipe=2))
        spp = create_mesh(MeshConfig(data=2, pipe=2, sequence=2))
        ref_params, ref_m = run(None, pp)
        got_params, got_m = run("sequence", spp)
        np.testing.assert_allclose(float(got_m["loss"]),
                                   float(ref_m["loss"]), rtol=1e-6)
        _assert_tree_close(got_params, ref_params, atol=1e-6, rtol=1e-5)

    def test_pp_sp_tp_one_program_matches_plain_pp(self):
        """Every explicit axis at once (pipe × sequence × model in one
        compiled SPMD program; data=1 — ZeRO would be a no-op sharding
        here and is deliberately left out of the claim): the loss matches
        the plain PP oracle. A dropped psum on any of the three axes
        would break the equality."""
        from distributed_training_tpu.train.train_state import TrainState

        toks = _tokens(b=8, t=17)
        batch = make_lm_batch(toks)
        rng = jax.random.PRNGKey(7)

        def run(seq_axis, mesh):
            model = get_model(
                "transformer_lm", num_classes=VOCAB, seq_axis=seq_axis,
                num_layers=2, num_heads=2, hidden_dim=32, max_len=128)
            step = make_pp_lm_train_step(mesh, model=model,
                                         num_microbatches=2, donate=False)
            plm = step.pipelined
            state = TrainState.create(
                apply_fn=plm.apply_fn,
                params=plm.init_params(jax.random.PRNGKey(0)),
                tx=optax.sgd(0.1),
                loss_scale=LossScaleState.create(
                    PrecisionConfig(dtype="fp32")))
            state = jax.device_put(state, step.state_shardings(state))
            gbatch = jax.device_put(
                {k: jnp.asarray(v) for k, v in batch.items()},
                step.batch_shardings)
            _, m = step(state, gbatch, rng)
            return m

        ref = run(None, create_mesh(MeshConfig(data=4, pipe=2)))
        deep = run("sequence",
                   create_mesh(MeshConfig(data=1, pipe=2, sequence=2,
                                          model=2)))
        np.testing.assert_allclose(float(deep["loss"]), float(ref["loss"]),
                                   rtol=1e-5)
        assert float(deep["grads_finite"]) == 1.0

    def test_sp_pp_zero1_circular(self):
        """The deeper product: sequence × pipe × circular schedule ×
        ZeRO-1 runs one finite step."""
        from distributed_training_tpu.train.train_state import TrainState

        mesh = create_mesh(MeshConfig(data=2, pipe=2, sequence=2))
        model = get_model(
            "transformer_lm", num_classes=VOCAB, seq_axis="sequence",
            num_layers=4, num_heads=2, hidden_dim=32, max_len=128)
        step = make_pp_lm_train_step(mesh, model=model, num_microbatches=2,
                                     donate=False, zero_stage=1,
                                     virtual_stages=2)
        plm = step.pipelined
        state = TrainState.create(
            apply_fn=plm.apply_fn,
            params=plm.init_params(jax.random.PRNGKey(0)),
            tx=optax.adam(1e-3),
            loss_scale=LossScaleState.create(PrecisionConfig(dtype="fp32")))
        state = jax.device_put(state, step.state_shardings(state))
        batch = jax.device_put(
            {k: jnp.asarray(v) for k, v in
             make_lm_batch(_tokens(b=8, t=17)).items()},
            step.batch_shardings)
        _, m = step(state, batch, jax.random.PRNGKey(1))
        assert np.isfinite(float(m["loss"]))
        assert float(m["grads_finite"]) == 1.0
