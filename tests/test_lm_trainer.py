"""LMTrainer end-to-end: strategy selection by mesh, data layer, resume.

The LM engine has no reference counterpart (SURVEY.md §5 "Long-context":
absent); its contract mirrors the image Trainer's — epoch loop, periodic
eval (perplexity), functional checkpoint/resume — with the parallel
strategy derived from the mesh axes.
"""

import numpy as np
import pytest

from distributed_training_tpu.config import (
    CheckpointConfig,
    DataConfig,
    LMConfig,
    MeshSpec,
    TrainConfig,
    ZeroConfig,
)
from distributed_training_tpu.data.lm_text import (
    TokenLoader,
    byte_corpus,
    synthetic_tokens,
)
from distributed_training_tpu.train.lm_trainer import LMTrainer

LM = LMConfig(seq_len=32, num_layers=2, num_heads=4, hidden_dim=32,
              max_len=64, train_sequences=256, eval_sequences=64,
              num_microbatches=2)


def _cfg(mesh, ckpt_dir, *, zero=0, epochs=2, resume=-1, interval=0):
    return TrainConfig(model="transformer_lm").replace(
        num_epochs=epochs, log_interval=4,
        data=DataConfig(batch_size=8, max_steps_per_epoch=4),
        lm=LM,
        mesh=mesh,
        zero=ZeroConfig(stage=zero),
        checkpoint=CheckpointConfig(
            directory=str(ckpt_dir), interval=interval, resume=resume),
    )


# -- data layer --------------------------------------------------------------


def test_synthetic_tokens_learnable_pattern():
    toks = synthetic_tokens(4, 16, vocab_size=64, seed=0)
    assert toks.shape == (4, 17)
    np.testing.assert_array_equal(toks[:, 1:], (toks[:, :-1] + 1) % 64)


def test_byte_corpus_windows(tmp_path):
    p = tmp_path / "corpus.txt"
    p.write_bytes(bytes(range(256)) * 4)
    toks = byte_corpus(str(p), 8, 16, seed=0)
    assert toks.shape == (8, 17)
    # Consecutive bytes of the file are consecutive values mod 256.
    np.testing.assert_array_equal(toks[:, 1:] % 256, (toks[:, :-1] + 1) % 256)
    with pytest.raises(ValueError, match="bytes"):
        byte_corpus(str(p), 2, 5000)


def test_token_loader_shards_and_reshuffles():
    toks = synthetic_tokens(64, 8, seed=0)
    loader = TokenLoader(toks, global_batch_size=16, seed=3,
                         process_index=1, process_count=2)
    assert len(loader) == 4
    b0 = [b["tokens"] for b in loader]
    assert all(b.shape == (8, 9) for b in b0)  # per-process half of 16
    b0_again = [b["tokens"] for b in loader]
    np.testing.assert_array_equal(b0[0], b0_again[0])  # same epoch = same order
    loader.set_epoch(1)
    b1 = [b["tokens"] for b in loader]
    assert not np.array_equal(b0[0], b1[0])  # set_epoch reshuffles


# -- engine ------------------------------------------------------------------

@pytest.mark.parametrize("name,mesh,zero", [
    ("sequence", MeshSpec(data=2, sequence=4), 0),
    ("tensor/dp", MeshSpec(data=2, model=4), 1),
    ("pipeline", MeshSpec(data=4, pipe=2), 0),
    ("tensor/dp", MeshSpec(data=-1), 0),
])
def test_lm_trainer_strategies_learn(tmp_path, name, mesh, zero):
    trainer = LMTrainer(_cfg(mesh, tmp_path, zero=zero))
    assert trainer.strategy == name
    result = trainer.fit()
    assert np.isfinite(result["final_perplexity"])
    # Steps per epoch depend on the mesh's data extent (global batch =
    # batch_size × data shards); the engine's own counter is the contract.
    assert result["steps"] == trainer._global_step > 0
    # The synthetic pattern is trivially learnable: even 8 tiny steps must
    # push held-out perplexity below the uniform-vocab 256.
    assert result["final_perplexity"] < 250


def test_lm_trainer_checkpoint_resume(tmp_path):
    mesh = MeshSpec(data=-1)
    r1 = LMTrainer(_cfg(mesh, tmp_path, epochs=2, interval=1)).fit()
    resumed = LMTrainer(_cfg(mesh, tmp_path, epochs=4, resume=1, interval=0))
    r2 = resumed.fit()
    # 2 epochs ran before the save, 2 more after resume; the step counter
    # carried through the checkpoint.
    assert r2["steps"] == r1["steps"] + 8


def test_lm_trainer_rejects_bad_meshes(tmp_path):
    # sequence×model and pipe×model compose since round 2, sequence×pipe
    # since round 5 (ring attention inside the pipeline stage) — the
    # remaining mesh errors are divisibility ones.
    with pytest.raises(ValueError, match="num_heads"):
        cfg = _cfg(MeshSpec(data=1, model=8), tmp_path)
        LMTrainer(cfg)


def test_lm_trainer_sequence_pipe_composes(tmp_path):
    """seq×pipe (round 5): the pipeline engine drives a seq_axis model —
    ring attention over the manual sequence axis inside each tick."""
    cfg = _cfg(MeshSpec(data=2, sequence=2, pipe=2), tmp_path)
    result = LMTrainer(cfg).fit()
    assert np.isfinite(result["final_perplexity"])


def test_metrics_accuracy_off_drops_key_same_loss(tmp_path):
    """lm.metrics_accuracy=False removes the per-step vocab argmax (a full
    extra HBM pass over the logits): the 'accuracy' metric key disappears
    while the training math — loss trajectory, steps — is unchanged."""
    import dataclasses as dc

    base = _cfg(MeshSpec(data=-1), tmp_path)
    on = LMTrainer(base)
    off = LMTrainer(base.replace(lm=dc.replace(LM, metrics_accuracy=False)))
    train_on, _ = on.make_loaders()
    train_off, _ = off.make_loaders()
    m_on = on.train_epoch(0, train_on)
    m_off = off.train_epoch(0, train_off)
    assert "accuracy" in m_on and "accuracy" not in m_off
    assert m_off["loss"] == pytest.approx(m_on["loss"], rel=1e-6)


def test_lm_trainer_circular_pipeline_zero1(tmp_path):
    """Round-4 knobs through the PRODUCT surface: LMTrainer with the
    circular schedule (virtual_stages=2), PP×ZeRO-1, bf16 logits, and no
    head bias trains and evaluates finitely."""
    import dataclasses

    cfg = _cfg(MeshSpec(data=4, pipe=2), tmp_path, zero=1, epochs=1)
    cfg = cfg.replace(lm=dataclasses.replace(
        LM, num_layers=4, virtual_stages=2, logits_dtype="bf16",
        head_bias=False))
    trainer = LMTrainer(cfg)
    assert trainer.train_step.pipelined.virtual_stages == 2
    assert trainer.train_step.pipelined.bubble_fraction < 1 / 3
    assert "bias" not in trainer.state.params["lm_head"]
    result = trainer.fit()
    assert np.isfinite(result["final_perplexity"])


def test_restore_head_bias_mismatch_names_the_knob(tmp_path):
    """Resuming a pre-round-5 checkpoint (lm_head WITH bias) into today's
    bias-less template must surface "set lm.head_bias=True", not a raw
    pytree-structure error (mirrors gpt/jax_tpu/generate.py's handler)."""
    import jax
    import jax.numpy as jnp
    import optax
    import pytest

    from distributed_training_tpu import checkpoint as ckpt_lib
    from distributed_training_tpu.config import PrecisionConfig
    from distributed_training_tpu.models import get_model
    from distributed_training_tpu.train.lm_trainer import restore_lm_checkpoint
    from distributed_training_tpu.train.precision import LossScaleState
    from distributed_training_tpu.train.train_state import init_train_state

    def state_for(head_bias):
        model = get_model("transformer_lm", num_classes=16, num_layers=1,
                          num_heads=2, hidden_dim=8, max_len=16,
                          head_bias=head_bias)
        return init_train_state(
            model, jax.random.PRNGKey(0), (1, 8), optax.sgd(0.1),
            loss_scale=LossScaleState.create(PrecisionConfig(dtype="fp32")),
            input_dtype=jnp.int32)

    ckpt_lib.save_checkpoint(str(tmp_path), 0, state_for(head_bias=True))
    with pytest.raises(ValueError, match="head_bias"):
        restore_lm_checkpoint(str(tmp_path), 0, state_for(head_bias=False))
    # The matching tree still restores through the guarded path.
    restored, _, _ = restore_lm_checkpoint(
        str(tmp_path), 0, state_for(head_bias=True))
    assert "bias" in restored.params["lm_head"]
