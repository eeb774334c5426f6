"""Chunked cross-entropy: [B, T, vocab] logits never materialize.

Equivalence is the load-bearing property: chunked CE must reproduce the
whole-logits loss, gradients, and training trajectory bitwise (same fp32
head matmul, just sliced over time). The memory win itself is measured on
hardware (B8·T16384·V50304 fp32 logits = 26 GB > HBM).
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from distributed_training_tpu.config import (
    DataConfig,
    LMConfig,
    MeshSpec,
    PrecisionConfig,
    TrainConfig,
)
from distributed_training_tpu.models import get_model
from distributed_training_tpu.parallel.sharding import place_state
from distributed_training_tpu.train.lm_step import (
    chunked_ce_and_accuracy,
    make_lm_batch,
    make_lm_train_step,
    make_tp_lm_train_step,
)
from distributed_training_tpu.train.precision import LossScaleState
from distributed_training_tpu.train.train_state import init_train_state

VOCAB = 37


def _model(**kw):
    return get_model("transformer_lm", num_classes=VOCAB, num_layers=2,
                     num_heads=2, hidden_dim=32, max_len=64, **kw)


def _state(model, tx):
    return init_train_state(
        model, jax.random.PRNGKey(0), (2, 8), tx,
        loss_scale=LossScaleState.create(PrecisionConfig(dtype="fp32")),
        input_dtype=jnp.int32)


class TestHelper:
    def test_matches_full_ce(self):
        rng = np.random.RandomState(0)
        hidden = jnp.asarray(rng.randn(2, 16, 8), jnp.float32)
        w = jnp.asarray(rng.randn(8, VOCAB), jnp.float32)
        b = jnp.asarray(rng.randn(VOCAB), jnp.float32)
        targets = jnp.asarray(rng.randint(0, VOCAB, (2, 16)), jnp.int32)
        logits = hidden @ w + b
        want_ce = optax.softmax_cross_entropy_with_integer_labels(
            logits, targets).mean()
        want_acc = jnp.mean(
            (jnp.argmax(logits, -1) == targets).astype(jnp.float32))
        for chunk in (4, 8, 16):
            ce, acc = chunked_ce_and_accuracy(
                hidden, {"kernel": w, "bias": b}, targets, chunk)
            np.testing.assert_allclose(float(ce), float(want_ce), rtol=1e-6)
            np.testing.assert_allclose(float(acc), float(want_acc), rtol=1e-6)

    def test_grads_match_full_ce(self):
        rng = np.random.RandomState(1)
        hidden = jnp.asarray(rng.randn(2, 12, 8), jnp.float32)
        w = jnp.asarray(rng.randn(8, VOCAB), jnp.float32)
        b = jnp.zeros((VOCAB,), jnp.float32)
        targets = jnp.asarray(rng.randint(0, VOCAB, (2, 12)), jnp.int32)

        def full(h, w):
            return optax.softmax_cross_entropy_with_integer_labels(
                h @ w + b, targets).mean()

        def chunked(h, w):
            return chunked_ce_and_accuracy(
                h, {"kernel": w, "bias": b}, targets, 4)[0]

        ga = jax.grad(full, argnums=(0, 1))(hidden, w)
        gb = jax.grad(chunked, argnums=(0, 1))(hidden, w)
        for a, b_ in zip(ga, gb):
            np.testing.assert_allclose(a, b_, rtol=1e-5, atol=1e-7)

    def test_indivisible_chunk_rejected(self):
        hidden = jnp.zeros((1, 10, 4))
        with pytest.raises(ValueError, match="divide"):
            chunked_ce_and_accuracy(
                hidden, {"kernel": jnp.zeros((4, VOCAB)),
                         "bias": jnp.zeros(VOCAB)},
                jnp.zeros((1, 10), jnp.int32), 3)


class TestStepEquivalence:
    def test_tp_step_chunked_matches_plain(self, mesh):
        model = _model(seq_axis=None)
        tx = optax.adam(1e-3)
        tokens = jnp.asarray(
            np.random.RandomState(0).randint(0, VOCAB, (8, 17)), jnp.int32)
        batch = make_lm_batch(tokens)
        rng = jax.random.PRNGKey(5)

        def run(ce_chunk):
            step = make_tp_lm_train_step(
                mesh, model=model, donate=False, ce_chunk=ce_chunk)
            state = _state(model, tx)
            state = place_state(state, step.state_shardings(state))
            new_state, m = step(state, batch, rng)
            return jax.device_get(new_state.params), m

        pa, ma = run(None)
        pb, mb = run(4)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6),
            pa, pb)
        for k in ("loss", "accuracy", "perplexity"):
            np.testing.assert_allclose(
                float(ma[k]), float(mb[k]), rtol=1e-5)

    def test_sequence_step_chunked_matches_plain(self):
        from distributed_training_tpu.runtime.mesh import MeshConfig, create_mesh

        mesh = create_mesh(MeshConfig(data=2, sequence=4))
        model = _model(seq_axis="sequence")
        tx = optax.adam(1e-3)
        tokens = jnp.asarray(
            np.random.RandomState(0).randint(0, VOCAB, (4, 17)), jnp.int32)
        batch = make_lm_batch(tokens)  # T=16, 4 per sequence shard
        rng = jax.random.PRNGKey(5)

        def run(ce_chunk):
            step = make_lm_train_step(
                mesh, model=model, donate=False, ce_chunk=ce_chunk)
            state = _state(model, tx)
            new_state, m = step(state, batch, rng)
            return jax.device_get(new_state.params), m

        pa, ma = run(None)
        pb, mb = run(2)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6),
            pa, pb)
        np.testing.assert_allclose(float(ma["loss"]), float(mb["loss"]),
                                   rtol=1e-5)


class TestTrainerWiring:
    def test_lm_trainer_chunked_fit(self, mesh):
        from distributed_training_tpu.train.lm_trainer import LMTrainer

        cfg = TrainConfig(
            model="transformer_lm", num_epochs=1, log_interval=2,
            data=DataConfig(batch_size=2, max_steps_per_epoch=3),
            lm=LMConfig(seq_len=16, vocab_size=VOCAB, num_layers=1,
                        num_heads=2, hidden_dim=16, max_len=32,
                        ce_chunk_size=4, train_sequences=64,
                        eval_sequences=32),
        )
        result = LMTrainer(cfg, mesh=mesh).fit()
        assert np.isfinite(result["final_perplexity"])

    def test_lm_trainer_save_probs_fit(self, mesh):
        """ce_save_probs reaches the product surface (config → trainer →
        step builder), not just the bench harness."""
        from distributed_training_tpu.train.lm_trainer import LMTrainer

        cfg = TrainConfig(
            model="transformer_lm", num_epochs=1, log_interval=2,
            data=DataConfig(batch_size=2, max_steps_per_epoch=3),
            lm=LMConfig(seq_len=16, vocab_size=VOCAB, num_layers=1,
                        num_heads=2, hidden_dim=16, max_len=32,
                        ce_save_probs=True, train_sequences=64,
                        eval_sequences=32),
        )
        result = LMTrainer(cfg, mesh=mesh).fit()
        assert np.isfinite(result["final_perplexity"])

    def test_pipeline_composes_with_chunking(self, devices):
        """ce_chunk through the pipeline executor (round-3; the step-level
        equivalence is pinned by test_pp_ce_chunk_matches_full_logits) —
        the trainer wires it end-to-end."""
        import numpy as np

        from distributed_training_tpu.train.lm_trainer import LMTrainer

        cfg = TrainConfig(
            model="transformer_lm", num_epochs=1, eval_every=1,
            mesh=MeshSpec(data=-1, pipe=2),
            data=DataConfig(batch_size=4, max_steps_per_epoch=2),
            lm=LMConfig(seq_len=16, vocab_size=VOCAB, num_layers=2,
                        num_heads=2, hidden_dim=16, max_len=32,
                        num_microbatches=2, ce_chunk_size=4,
                        train_sequences=64, eval_sequences=32),
        )
        result = LMTrainer(cfg).fit()
        assert np.isfinite(result["final_perplexity"])

    @pytest.mark.parametrize("bad_chunk", [5, -4, 0])
    def test_invalid_chunk_rejected_at_construction(self, mesh, bad_chunk):
        from distributed_training_tpu.train.lm_trainer import LMTrainer

        cfg = TrainConfig(
            model="transformer_lm",
            data=DataConfig(batch_size=2),
            lm=LMConfig(seq_len=16, vocab_size=VOCAB, num_layers=1,
                        num_heads=2, hidden_dim=16, max_len=32,
                        ce_chunk_size=bad_chunk),
        )
        with pytest.raises(ValueError, match="ce_chunk_size"):
            LMTrainer(cfg, mesh=mesh)


class TestLogitsDtype:
    """The bf16-logits throughput lever (models/gpt.py::make_lm_head)."""

    def test_fused_ce_matches_optax_fp32(self):
        rng = np.random.RandomState(1)
        logits = jnp.asarray(rng.randn(4, 16, VOCAB) * 5, jnp.float32)
        targets = jnp.asarray(rng.randint(0, VOCAB, (4, 16)), jnp.int32)
        from distributed_training_tpu.train.lm_step import _fused_softmax_ce

        want = optax.softmax_cross_entropy_with_integer_labels(
            logits, targets).mean()
        got = _fused_softmax_ce(logits, targets)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-6)
        gw = jax.grad(lambda l: optax.softmax_cross_entropy_with_integer_labels(
            l, targets).mean())(logits)
        gg = jax.grad(lambda l: _fused_softmax_ce(l, targets))(logits)
        np.testing.assert_allclose(np.asarray(gg), np.asarray(gw),
                                   atol=1e-7, rtol=1e-5)

    def test_bf16_logits_model_emits_bf16_and_tracks_fp32_loss(self):
        model32 = _model(dtype=jnp.bfloat16)
        model16 = _model(dtype=jnp.bfloat16, logits_dtype=jnp.bfloat16)
        params = model32.init(
            jax.random.PRNGKey(0), jnp.zeros((2, 8), jnp.int32))["params"]
        toks = jnp.asarray(
            np.random.RandomState(0).randint(0, VOCAB, (2, 17)), jnp.int32)
        batch = make_lm_batch(toks)
        lo16 = model16.apply({"params": params}, batch["tokens"])
        lo32 = model32.apply({"params": params}, batch["tokens"])
        assert lo16.dtype == jnp.bfloat16
        from distributed_training_tpu.train.lm_step import _fused_softmax_ce

        ce16 = _fused_softmax_ce(lo16, batch["targets"])
        ce32 = _fused_softmax_ce(lo32, batch["targets"])
        assert ce16.dtype == jnp.float32
        # bf16 rounding of the logits perturbs the loss by O(2^-8) relative.
        np.testing.assert_allclose(np.asarray(ce16), np.asarray(ce32),
                                   rtol=3e-2)

    def test_chunked_ce_honors_logits_dtype(self):
        """ce_chunk × logits_dtype=bf16: the chunked path must compute the
        same bf16-logit CE as the unchunked head, not silently fp32."""
        model = _model(dtype=jnp.bfloat16, logits_dtype=jnp.bfloat16)
        params = model.init(
            jax.random.PRNGKey(0), jnp.zeros((2, 8), jnp.int32))["params"]
        toks = jnp.asarray(
            np.random.RandomState(0).randint(0, VOCAB, (2, 17)), jnp.int32)
        batch = make_lm_batch(toks)
        logits = model.apply({"params": params}, batch["tokens"])
        from distributed_training_tpu.train.lm_step import _fused_softmax_ce

        want = _fused_softmax_ce(logits, batch["targets"])
        hidden = model.apply({"params": params}, batch["tokens"],
                             return_hidden=True)
        ce, _ = chunked_ce_and_accuracy(
            hidden, params["lm_head"], batch["targets"], 8,
            logits_dtype=jnp.bfloat16)
        np.testing.assert_allclose(np.asarray(ce), np.asarray(want),
                                   rtol=1e-5)


class TestCEVariants:
    """Round-5 CE levers: accuracy derived from the CE max (deletes the
    argmax HBM pass) and the saved-probs backward (deletes the exp
    recompute from both head matmul fusions)."""

    def _data(self, dtype=jnp.float32):
        rng = np.random.RandomState(7)
        logits = jnp.asarray(rng.randn(4, 9, VOCAB) * 4, dtype)
        targets = jnp.asarray(rng.randint(0, VOCAB, (4, 9)), jnp.int32)
        return logits, targets

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_accuracy_from_max_matches_argmax(self, dtype):
        from distributed_training_tpu.train.lm_step import _fused_ce_rows

        logits, targets = self._data(dtype)
        _, correct = _fused_ce_rows(logits, targets, with_correct=True)
        want = (jnp.argmax(logits, -1) == targets).astype(jnp.float32)
        np.testing.assert_array_equal(np.asarray(correct), np.asarray(want))

    def test_accuracy_tie_semantics(self):
        """Ties count as correct (tie-inclusive top-1): when the label
        logit exactly equals another index's max, argmax-first would call
        it wrong, the max-equality form calls it right. Documented, not a
        bug — continuous logits tie with measure zero."""
        from distributed_training_tpu.train.lm_step import _fused_ce_rows

        logits = jnp.zeros((1, 1, VOCAB)).at[0, 0, 3].set(5.0)
        logits = logits.at[0, 0, 11].set(5.0)
        targets = jnp.asarray([[11]], jnp.int32)
        assert int(jnp.argmax(logits, -1)[0, 0]) == 3  # argmax says wrong
        _, correct = _fused_ce_rows(logits, targets, with_correct=True)
        assert float(correct[0, 0]) == 1.0

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_saved_probs_forward_bit_identical(self, dtype):
        from distributed_training_tpu.train.lm_step import (
            _ce_rows_saved_probs,
            _fused_ce_rows,
        )

        logits, targets = self._data(dtype)
        r1, c1 = _fused_ce_rows(logits, targets, with_correct=True)
        r2, c2 = _ce_rows_saved_probs(logits, targets, with_correct=True)
        np.testing.assert_array_equal(np.asarray(r1), np.asarray(r2))
        np.testing.assert_array_equal(np.asarray(c1), np.asarray(c2))

    def test_saved_probs_grad_within_bf16_rounding(self):
        from distributed_training_tpu.train.lm_step import (
            _ce_rows_saved_probs,
            _fused_ce_rows,
        )

        logits, targets = self._data()
        g1 = jax.grad(lambda lg: _fused_ce_rows(lg, targets).mean())(logits)
        g2 = jax.jit(jax.grad(
            lambda lg: _ce_rows_saved_probs(lg, targets).mean()))(logits)
        # p is rounded to bf16 (~2^-8 relative); the onehot term is exact.
        scale = float(jnp.max(jnp.abs(g1)))
        np.testing.assert_allclose(np.asarray(g2), np.asarray(g1),
                                   atol=5e-3 * scale)

    def test_saved_probs_refuses_ce_chunk(self, mesh):
        """ce_chunk remats per-chunk logits, which would silently discard
        the saved probs — the combination must refuse at construction."""
        model = _model(seq_axis=None)
        with pytest.raises(ValueError, match="ce_save_probs"):
            make_tp_lm_train_step(mesh, model=model, ce_chunk=4,
                                  ce_save_probs=True)

    def test_saved_probs_step_metrics_match(self, mesh):
        """Forward math is bit-identical, so step metrics must agree
        exactly; only the gradient sees the bf16-rounded probs."""
        model = _model(seq_axis=None)
        tx = optax.adam(1e-3)
        tokens = jnp.asarray(
            np.random.RandomState(0).randint(0, VOCAB, (8, 17)), jnp.int32)
        batch = make_lm_batch(tokens)
        rng = jax.random.PRNGKey(5)

        def run(save_probs):
            step = make_tp_lm_train_step(
                mesh, model=model, donate=False, ce_save_probs=save_probs)
            state = _state(model, tx)
            state = place_state(state, step.state_shardings(state))
            _, m = step(state, batch, rng)
            return m

        ma, mb = run(False), run(True)
        for k in ("loss", "accuracy", "perplexity"):
            np.testing.assert_allclose(float(ma[k]), float(mb[k]),
                                       rtol=1e-6)


class TestHeadBias:
    """head_bias=False (GPT-2's real head has none): the param disappears,
    forward stays finite, and the chunked CE tolerates the missing bias."""

    def test_no_bias_param_and_chunked_ce_matches(self):
        model = _model(head_bias=False)
        params = model.init(
            jax.random.PRNGKey(0), jnp.zeros((2, 8), jnp.int32))["params"]
        assert "bias" not in params["lm_head"]
        toks = jnp.asarray(
            np.random.RandomState(0).randint(0, VOCAB, (2, 17)), jnp.int32)
        batch = make_lm_batch(toks)
        logits = model.apply({"params": params}, batch["tokens"])
        from distributed_training_tpu.train.lm_step import _fused_softmax_ce

        want = _fused_softmax_ce(logits, batch["targets"])
        hidden = model.apply({"params": params}, batch["tokens"],
                             return_hidden=True)
        ce, _ = chunked_ce_and_accuracy(
            hidden, params["lm_head"], batch["targets"], 8)
        np.testing.assert_allclose(np.asarray(ce), np.asarray(want),
                                   rtol=1e-5)
