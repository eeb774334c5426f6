"""Quantized execution tests (serving/quantize.py; docs/SERVING.md
"Quantized execution").

Load-bearing properties, in order of importance:

1. **Determinism, not approximation-of-determinism**: quantization is
   round-to-nearest with per-channel scales computed from the weights
   alone (weights) or from each row's own K/V (cache) — so a quantized
   engine is bitwise-reproducible across runs, and a batched quantized
   run equals its own single-slot quantized oracle for every
   sampling/speculation mode. Quantization relocates the numerics; it
   never makes them batch- or timing-dependent.
2. **Bounded quality**: dequantized weights sit within half a scale
   step of the originals per channel, the fixed-seed eval loss moves
   by less than the documented bound, and greedy decode matches the
   fp32 engine's token streams at >= 0.98 per-token on the smoke
   geometry (wide hidden, small vocab — see the CI quantization
   drill).
3. **Off the hot path**: weights quantize ONCE at engine construction
   and at swap arm time (watcher thread); the compiled-program
   inventory stays at the paged engine's two programs, int8 KV
   included (quantize-on-scatter / dequantize-in-gather live inside
   the same jits).
4. **The serving plane composes**: hot-swap (validate/arm/barrier/
   rollback), preempt-and-restore, the prefix-cache trie, and journal
   recovery all operate on the quantized engine unchanged, bitwise
   against their own quantized oracles.

Engines compile real XLA programs, so the mechanics model is tiny;
the bitwise matrix covers every axis value (greedy/sampled x spec
0/2) pairwise in tier-1 and in full under ``-m slow``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import traverse_util

from distributed_training_tpu.config import ServeConfig
from distributed_training_tpu.models import get_model
from distributed_training_tpu.resilience.errors import SwapError
from distributed_training_tpu.serving import Engine, JournalCorruptError
from distributed_training_tpu.serving.quantize import (
    QuantizedTensor,
    dequantize_params,
    is_quantized,
    quantize_array,
    quantize_params,
    quantized_param_bytes,
    reduce_axes_for,
)

VOCAB = 31
MAX_LEN = 64
PS = 4


@pytest.fixture(scope="module")
def lm():
    """Mechanics model: tiny, so the bitwise matrix stays cheap."""
    model = get_model(
        "transformer_lm", num_classes=VOCAB, num_layers=1, num_heads=2,
        hidden_dim=16, max_len=MAX_LEN)
    params = model.init(jax.random.PRNGKey(0),
                        np.zeros((1, 8), np.int32))["params"]
    return model, params


@pytest.fixture(scope="module")
def lm_q():
    """Quality model: the CI drill's geometry — wide hidden (small
    relative quantization error), small vocab (wide top-2 logit gap),
    so greedy argmax survives int8 even at random init."""
    model = get_model(
        "transformer_lm", num_classes=16, num_layers=1, num_heads=2,
        hidden_dim=64, max_len=64)
    params = model.init(jax.random.PRNGKey(0),
                        np.zeros((1, 8), np.int32))["params"]
    return model, params


def make_engine(lm, **kw):
    model, params = lm
    kw.setdefault("max_batch", 2)
    kw.setdefault("max_new_tokens", 6)
    kw.setdefault("kv_page_size", PS)
    kw.setdefault("prefill_chunk", 4)
    return Engine(model, params, ServeConfig(**kw))


def _serve(eng, prompts, **submit_kw):
    """One request at a time, each run to completion — uids follow
    submission order, so outputs are comparable across engines
    (fold_in(seed, uid) parity)."""
    out = []
    for p in prompts:
        eng.submit(p, **submit_kw)
        out.extend(eng.run())
    return {f.uid: f for f in out}


PROMPTS = [np.asarray(s, np.int32)
           for s in ([3, 5, 7, 2], [11, 13, 4, 9, 1, 6], [8, 8, 8])]


# -- quantize_array / quantize_params mechanics -----------------------------
class TestQuantizeArray:
    def test_round_trip_bounded_per_channel(self):
        w = jax.random.normal(jax.random.PRNGKey(1), (8, 16), jnp.float32)
        qt = quantize_array(w, (0,))
        assert qt.q.dtype == jnp.int8
        assert qt.scale.shape == (1, 16)
        assert int(jnp.max(jnp.abs(qt.q.astype(jnp.int32)))) <= 127
        # Round-to-nearest: every element within half a scale step.
        err = jnp.abs(qt.dequantize() - w)
        assert bool(jnp.all(err <= qt.scale / 2 + 1e-7))
        # Per-channel max hits the int8 rail exactly.
        assert bool(jnp.all(jnp.max(jnp.abs(qt.q), axis=0) == 127))

    def test_zero_channel_gets_unit_scale(self):
        w = jnp.zeros((4, 3), jnp.float32).at[:, 1].set(2.0)
        qt = quantize_array(w, (0,))
        assert float(qt.scale[0, 0]) == 1.0  # no div-by-zero sentinel
        assert bool(jnp.all(qt.dequantize()[:, 0] == 0.0))
        assert bool(jnp.all(qt.dequantize()[:, 1] == 2.0))

    def test_astype_dequantizes(self):
        """The duck-typed contract the model relies on: ``astype`` on a
        QuantizedTensor yields the dequantized array in that dtype, so
        existing ``kernel.astype(self.dtype)`` call-sites dequantize
        with zero model changes."""
        w = jax.random.normal(jax.random.PRNGKey(2), (6, 5), jnp.float32)
        qt = quantize_array(w, (0,))
        out = qt.astype(jnp.bfloat16)
        assert out.dtype == jnp.bfloat16
        assert bool(jnp.all(jnp.abs(out.astype(jnp.float32) - w) < 0.1))


class TestQuantizeParams:
    def test_tree_structure_and_coverage(self, lm):
        """Matmul weights quantize with the documented reduce axes;
        layernorms, biases, positional tables and the logits head stay
        untouched."""
        _, params = lm
        qp = quantize_params(params)
        assert is_quantized(qp) and not is_quantized(params)
        flat = traverse_util.flatten_dict(params, sep="/")
        qflat = traverse_util.flatten_dict(
            qp, sep="/",
            is_leaf=lambda _, v: isinstance(v, QuantizedTensor))
        assert set(flat) == set(qflat)
        n_quant = 0
        for path, leaf in flat.items():
            axes = reduce_axes_for(path)
            qleaf = qflat[path]
            if axes is None:
                # Untouched: same object semantics (dtype + values).
                assert not isinstance(qleaf, QuantizedTensor), path
                assert qleaf.dtype == leaf.dtype, path
                assert bool(jnp.all(qleaf == leaf)), path
            else:
                n_quant += 1
                assert isinstance(qleaf, QuantizedTensor), path
                assert qleaf.q.shape == leaf.shape, path
                expect_scale = tuple(
                    1 if a in axes else d
                    for a, d in enumerate(leaf.shape))
                assert qleaf.scale.shape == expect_scale, path
        # 1 layer: tok_embed + qkv + out + fc1 + fc2 = 5 quantized.
        assert n_quant == 5

    def test_quantized_param_bytes(self, lm):
        _, params = lm
        qp = quantize_params(params)
        expect = sum(
            leaf.nbytes
            for leaf in jax.tree_util.tree_leaves(
                qp, is_leaf=lambda v: isinstance(v, QuantizedTensor))
            if isinstance(leaf, QuantizedTensor))
        got = quantized_param_bytes(qp)
        assert got == expect > 0
        assert quantized_param_bytes(params) == 0

    def test_dequantize_params_restores_structure(self, lm):
        _, params = lm
        deq = dequantize_params(quantize_params(params))
        assert (jax.tree_util.tree_structure(deq)
                == jax.tree_util.tree_structure(params))
        for a, b in zip(jax.tree_util.tree_leaves(params),
                        jax.tree_util.tree_leaves(deq)):
            assert a.shape == b.shape and a.dtype == b.dtype
            assert bool(jnp.all(jnp.abs(a - b) <= 0.05))


# -- config gating ----------------------------------------------------------
class TestConfig:
    @pytest.mark.parametrize("page_size", [None, 0, -1])
    def test_kv_page_size_below_one_rejected(self, page_size):
        """The option that selected the contiguous-slot engine."""
        with pytest.raises(ValueError, match="removed in PR 29"):
            ServeConfig(kv_page_size=page_size)

    def test_unknown_kv_dtype_rejected(self):
        with pytest.raises(ValueError, match="kv_dtype"):
            ServeConfig(kv_dtype="fp8", kv_page_size=4)


# -- engine: determinism (the repo's signature invariant, quantized) --------
# Every axis value (greedy/sampled, spec 0/2) pairwise in tier-1; the
# remaining off-diagonal pairs run under -m slow.
BITWISE_CASES = [(0.0, 0), (0.8, 2)]
BITWISE_CASES_SLOW = [(0.8, 0), (0.0, 2)]


class TestQuantizedDeterminism:
    def _check_oracle(self, lm, temp, spec_k):
        """Batched quantized run == its own single-slot quantized
        oracle: quantization must not introduce batch-composition
        dependence (per-row cache scales depend only on that row's own
        K/V)."""
        kw = dict(temperature=temp, spec_k=spec_k,
                  quantize_weights=True, kv_dtype="int8")
        batched = make_engine(lm, max_batch=2, **kw)
        oracle = make_engine(lm, max_batch=1, **kw)
        out_b = _serve(batched, PROMPTS)
        out_o = _serve(oracle, PROMPTS)
        for uid, fin in out_o.items():
            assert np.array_equal(fin.tokens, out_b[uid].tokens), uid
            assert fin.finish_reason == out_b[uid].finish_reason
        batched.check_balanced()

    @pytest.mark.parametrize("temp,spec_k", BITWISE_CASES)
    def test_batch_equals_single_slot_oracle(self, lm, temp, spec_k):
        self._check_oracle(lm, temp, spec_k)

    @pytest.mark.slow
    @pytest.mark.parametrize("temp,spec_k", BITWISE_CASES_SLOW)
    def test_batch_equals_single_slot_oracle_full(self, lm, temp, spec_k):
        self._check_oracle(lm, temp, spec_k)

    def test_two_runs_bitwise_identical(self, lm):
        outs = []
        for _ in range(2):
            eng = make_engine(lm, temperature=0.8,
                              quantize_weights=True, kv_dtype="int8")
            outs.append(_serve(eng, PROMPTS))
        for uid, fin in outs[0].items():
            assert np.array_equal(fin.tokens, outs[1][uid].tokens)

    def test_compiled_inventory_stays_two(self, lm):
        """Quantize-on-scatter / dequantize-in-gather live INSIDE the
        paged engine's two programs — int8 KV grows the inventory by
        zero."""
        from distributed_training_tpu.observability.sanitizer import (
            check_engine_inventory,
        )

        eng = make_engine(lm, quantize_weights=True, kv_dtype="int8")
        _serve(eng, PROMPTS[:2])  # warm both shapes
        assert check_engine_inventory(eng) == {"fused": 1, "decode": 1}


class TestQuantizedTelemetry:
    def test_counters_on_and_off(self, lm):
        on = make_engine(lm, quantize_weights=True, kv_dtype="int8")
        off = make_engine(lm)
        s_on, s_off = on.stats(), off.stats()
        assert s_on["quantized_params_bytes"] > 0
        assert s_on["weight_quant_s"] > 0.0
        assert s_off["quantized_params_bytes"] == 0
        assert s_off["weight_quant_s"] == 0.0
        # Cache geometry is config-deterministic either way.
        assert s_on["kv_bytes_per_token"] > 0
        assert s_off["kv_bytes_per_token"] > 0
        # The headline: int8 pages + scale planes vs fp32 rows.
        ratio = s_on["kv_bytes_per_token"] / s_off["kv_bytes_per_token"]
        assert ratio <= 0.55, ratio

    def test_counters_survive_reset(self, lm):
        eng = make_engine(lm, quantize_weights=True, kv_dtype="int8")
        before = eng.stats()
        eng.reset_stats()
        after = eng.stats()
        assert after["quantized_params_bytes"] \
            == before["quantized_params_bytes"]
        assert after["weight_quant_s"] == before["weight_quant_s"]
        assert after["kv_bytes_per_token"] == before["kv_bytes_per_token"]


# -- hot-swap on the quantized engine ---------------------------------------
class TestQuantizedHotSwap:
    def test_arm_quantizes_and_barrier_applies(self, lm):
        """arm_swap receives the restore path's fp32 tree, quantizes it
        on the calling (watcher) thread, and the barrier installs a
        quantized tree — post-swap output bitwise equals an engine
        BUILT quantized on the new weights."""
        model, params = lm
        params2 = model.init(jax.random.PRNGKey(9),
                             np.zeros((1, 8), np.int32))["params"]
        eng = make_engine(lm, quantize_weights=True, kv_dtype="int8")
        quant_s0 = eng.stats()["weight_quant_s"]
        _serve(eng, [PROMPTS[0]])
        eng.arm_swap(params2, epoch=1)
        out = _serve(eng, [PROMPTS[1]])  # barrier applies at next step
        assert eng.weights_epoch == 1
        assert is_quantized(eng.params)
        assert eng.stats()["swaps_completed"] == 1
        assert eng.stats()["weight_quant_s"] > quant_s0  # arm re-quantized
        # Greedy is uid-independent: a fresh quantized engine on the
        # new weights is the oracle.
        oracle = make_engine((model, params2), quantize_weights=True,
                             kv_dtype="int8")
        ref = _serve(oracle, [PROMPTS[0], PROMPTS[1]])
        (fin,) = out.values()
        ref_fin = [f for f in ref.values() if f.uid == 1]
        assert np.array_equal(fin.tokens, ref_fin[0].tokens)

    def test_validate_swap_accepts_fp32_and_quantized(self, lm):
        model, params = lm
        eng = make_engine(lm, quantize_weights=True, kv_dtype="int8")
        eng.validate_swap(params)                  # the restore tree
        eng.validate_swap(quantize_params(params))  # an already-staged tree
        with pytest.raises(SwapError):
            eng.validate_swap({"wrong": np.zeros(3, np.float32)})

    def test_rollback_rearms_quantized_prev(self, lm):
        model, params = lm
        params2 = model.init(jax.random.PRNGKey(9),
                             np.zeros((1, 8), np.int32))["params"]
        eng = make_engine(lm, quantize_weights=True, kv_dtype="int8")
        out0 = _serve(eng, [PROMPTS[0]])
        eng.arm_swap(params2, epoch=1)
        _serve(eng, [PROMPTS[1]])
        assert eng.weights_epoch == 1
        eng.rollback()  # re-arms the already-quantized previous tree
        out2 = _serve(eng, [PROMPTS[0]])
        assert eng.weights_epoch == -1  # back to the construction epoch
        assert is_quantized(eng.params)
        # Greedy: rolled-back weights reproduce the original stream.
        (a,), (b,) = out0.values(), out2.values()
        assert np.array_equal(a.tokens, b.tokens)


# -- prefix cache + preemption on the quantized engine ----------------------
PREAMBLE = (np.arange(1, 21, dtype=np.int32) * 3) % VOCAB  # 20 tokens


class TestQuantizedReuse:
    @pytest.mark.parametrize("temp", [0.0, 0.8])
    def test_prefix_hit_bitwise_equals_cold_quantized(self, lm, temp):
        """A trie hit aliases QUANTIZED pages; the gathered values are
        identical to a cold quantized prefill of the same tokens, so
        the hit stays bitwise-neutral inside the quantized numerics."""
        prompts = [np.concatenate([PREAMBLE, np.asarray(s, np.int32)])
                   for s in ([3, 5], [7, 9, 11])]
        kw = dict(temperature=temp, quantize_weights=True,
                  kv_dtype="int8")
        cold = make_engine(lm, **kw)
        warm = make_engine(lm, prefix_cache=True, **kw)
        cold_out = _serve(cold, prompts)
        warm_out = _serve(warm, prompts)
        assert warm.stats()["prefix_cache_hit_tokens"] == 20
        for uid, fin in cold_out.items():
            assert np.array_equal(fin.tokens, warm_out[uid].tokens), uid
        warm.check_balanced()

    def test_preempt_restore_bitwise_quantized(self, lm):
        """Preempt-and-restore snapshots / re-seats int8 pages + scale
        planes as one unit: the victim completes bitwise-equal to the
        unpreempted quantized run."""

        def run(num_tiers):
            eng = make_engine(lm, max_batch=1, num_tiers=num_tiers,
                              max_new_tokens=8, quantize_weights=True,
                              kv_dtype="int8")
            low = eng.submit(PREAMBLE, priority=num_tiers - 1,
                             max_new_tokens=8)
            for _ in range(8):
                eng.step()
            if num_tiers > 1:
                eng.submit(np.asarray([2, 4, 6], np.int32), priority=0,
                           max_new_tokens=4)
            done = {f.uid: f for f in eng.run()}
            eng.check_balanced()
            return eng, done[low.uid]

        # tier 1 = no competitor (the uninterrupted oracle); tier 2 =
        # the preemption run.
        e1, fin1 = run(1)
        e2, fin2 = run(2)
        assert e2.stats()["requests_preempted"] >= 1
        assert e1.stats()["requests_preempted"] == 0
        assert np.array_equal(fin1.tokens, fin2.tokens)


# -- journal recovery on the quantized engine -------------------------------
class TestQuantizedJournal:
    def test_recovery_redelivers_bitwise(self, lm, tmp_path):
        kw = dict(quantize_weights=True, kv_dtype="int8",
                  journal_dir=str(tmp_path))
        eng1 = make_engine(lm, **kw)
        eng1.recover()
        out1 = _serve(eng1, PROMPTS)
        eng1.journal.shutdown()
        eng2 = make_engine(lm, **kw)
        report = eng2.recover()
        redelivered = {f.uid: f for f in report["redelivered"]}
        assert set(redelivered) == set(out1)
        for uid, fin in out1.items():
            assert np.array_equal(redelivered[uid].tokens, fin.tokens)
        eng2.journal.shutdown()

    def test_fingerprint_pins_quantization_mode(self, lm, tmp_path):
        """A journal written by a quantized engine must not replay into
        a full-precision one (different numerics, different streams) —
        the fingerprint catches it like a seed mismatch."""
        eng1 = make_engine(lm, quantize_weights=True, kv_dtype="int8",
                           journal_dir=str(tmp_path))
        eng1.recover()
        _serve(eng1, [PROMPTS[0]])
        eng1.journal.shutdown()
        eng2 = make_engine(lm, journal_dir=str(tmp_path))
        with pytest.raises(JournalCorruptError, match="fingerprint"):
            eng2.recover()


# -- quality bounds (the lm_q geometry; see the CI quantization drill) ------
class TestQuality:
    def test_eval_loss_delta_bounded(self, lm_q):
        model, params = lm_q
        qparams = quantize_params(params)
        rng = np.random.RandomState(0)
        batch = rng.randint(0, 16, size=(4, 32)).astype(np.int32)

        def ce(p):
            logits = model.apply({"params": p}, batch)
            lp = jax.nn.log_softmax(
                logits[:, :-1].astype(jnp.float32), axis=-1)
            tgt = batch[:, 1:]
            return float(-jnp.mean(
                jnp.take_along_axis(lp, tgt[..., None], axis=-1)))

        delta = abs(ce(qparams) - ce(params))
        # Measured 5.3e-4 on this fixed seed; 0.01 is ~20x headroom
        # while still catching any quantization-coverage breakage
        # (dropping a channel axis moves it by >0.1).
        assert delta <= 0.01, delta

    def test_greedy_exact_match_vs_fp32(self, lm_q):
        """>= 0.98 per-token greedy agreement with the fp32 engine on
        the smoke geometry (this prompt seed measures 128/128; the
        bound leaves room for platform-level float drift)."""
        rng = np.random.RandomState(2)
        prompts = [rng.randint(0, 16, size=int(n)).astype(np.int32)
                   for n in rng.randint(8, 25, size=16)]

        def serve(quant):
            eng = make_engine(lm_q, max_batch=4, max_new_tokens=8,
                              quantize_weights=quant,
                              kv_dtype="int8" if quant else None)
            return {uid: f.tokens
                    for uid, f in _serve(eng, prompts).items()}

        a, b = serve(False), serve(True)
        match = total = 0
        for uid in a:
            total += max(len(a[uid]), len(b[uid]))
            match += sum(1 for x, y in zip(a[uid], b[uid]) if x == y)
        assert match / total >= 0.98, (match, total)
