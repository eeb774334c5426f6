"""Speculative decoding tests: draft-and-verify stays lossless.

Load-bearing properties, in order of importance:

1. **Oracle equivalence** (the acceptance criterion): greedy output
   under speculation — both drafters, ``spec_k`` ∈ {2, 4},
   2×+ pool oversubscription — is bitwise
   token-identical to the sequential :class:`Generator`. Drafts decide
   how many tokens one dispatch lands, never what any token is.
2. **Sampled distribution-identity**: fixed-seed sampled output under
   speculation is bitwise equal to the non-speculative engine's (the
   per-position ``fold_in(rng, pos)`` stream makes the verify window's
   samples THE sequential samples, so bitwise equality — strictly
   stronger than distribution equality — is the pinned form).
3. **Accept semantics**: the mask/argmax accept-length formulation
   (first mismatch, sentinel for all-match, validity clamps), EOS
   truncation mid-window, completion-budget clamping, and page-
   accounting balance across accept/rewind cycles.
4. **Draft economics**: drafted/accepted counters are deterministic
   (pure functions of each request's own stream — the bench gate holds
   them zero-drift), a perfect drafter yields acceptance 1.0 and
   ``spec_k + 1`` tokens per dispatch, and a weight hot-swap rolls a
   self-drafting drafter's params inside the barrier (no stale-drafter
   window).

Engines compile real XLA programs; shared runs are module fixtures and
the wide parameter sweep is marked ``slow`` (tier-1 budget).
"""

import jax
import numpy as np
import pytest

from distributed_training_tpu.config import ServeConfig
from distributed_training_tpu.inference import Generator, SampleConfig
from distributed_training_tpu.models import get_model
from distributed_training_tpu.serving import Engine, GPTDrafter, NGramDrafter
from distributed_training_tpu.serving.speculative import (
    accept_counts,
    truncate_at_eos,
)

VOCAB = 61
MAX_LEN = 64
N_NEW = 6
PROMPT_LENS = [3, 5, 9, 5, 3, 9]


@pytest.fixture(scope="module")
def lm():
    model = get_model(
        "transformer_lm", num_classes=VOCAB, num_layers=2, num_heads=2,
        hidden_dim=32, max_len=MAX_LEN, head_bias=True)
    params = model.init(jax.random.PRNGKey(0),
                        np.zeros((2, 16), np.int32))["params"]
    return model, params


@pytest.fixture(scope="module")
def prompts():
    rng = np.random.RandomState(1)
    return [rng.randint(0, VOCAB, size=l).astype(np.int32)
            for l in PROMPT_LENS]


@pytest.fixture(scope="module")
def oracle(lm, prompts):
    """Sequential-Generator greedy outputs — THE reference stream."""
    model, params = lm
    gen = Generator(model, params, SampleConfig(
        max_new_tokens=N_NEW, temperature=0.0))
    return [gen(p)[0] for p in prompts]


def _serve(model, params, prompts, drafter=None, **cfg_kw):
    cfg = ServeConfig(**cfg_kw)
    eng = Engine(model, params, cfg, drafter=drafter)
    for p in prompts:
        eng.submit(p)
    done = eng.run()
    assert len(done) == len(prompts)
    return eng, {f.uid: f for f in done}


class _OracleDrafter:
    """Test drafter that proposes the known-true continuation — the
    perfect-acceptance limit that pins the accept path end to end."""

    def __init__(self, prompts, outputs):
        self.streams = [np.concatenate([p, o]).astype(np.int32)
                        for p, o in zip(prompts, outputs)]

    def propose(self, context, k):
        n = context.size
        for full in self.streams:
            if full.size >= n and np.array_equal(full[:n], context):
                return full[n:n + k]
        return np.zeros((0,), np.int32)

    def on_weights_swap(self, params, epoch):
        pass

    def compiled_programs(self):
        return {}


class TestNGramDrafter:
    def test_longest_recent_match_wins(self):
        d = NGramDrafter(3, fallback_repeat=False)
        #                 0  1  2  3  4  5  6  7  8
        ctx = np.array([1, 2, 3, 9, 1, 2, 3, 1, 2, 3], np.int32)
        # Suffix trigram (1,2,3) matches at 0 (→9) and 4 (→1): the most
        # recent full match (start 4) wins, proposing its continuation.
        np.testing.assert_array_equal(d.propose(ctx, 3), [1, 2, 3])

    def test_backoff_to_shorter_ngram(self):
        d = NGramDrafter(3, fallback_repeat=False)
        ctx = np.array([7, 5, 1, 2, 5], np.int32)
        # No trigram/bigram recurrence ending the context; the suffix
        # unigram 5 last occurred at index 1 → proposes its
        # continuation [1, 2] (k-truncated).
        np.testing.assert_array_equal(d.propose(ctx, 2), [1, 2])

    def test_no_match_empty_or_fallback(self):
        ctx = np.array([1, 2, 3, 4], np.int32)
        bare = NGramDrafter(3, fallback_repeat=False).propose(ctx, 4)
        assert bare.size == 0
        # Fallback (default): pad to k by repeating the last token —
        # the verify window is fixed-width, so a guess is free compute.
        fb = NGramDrafter(3).propose(ctx, 4)
        np.testing.assert_array_equal(fb, [4, 4, 4, 4])

    def test_deterministic_and_short_context(self):
        d = NGramDrafter(3)
        ctx = np.array([5], np.int32)
        np.testing.assert_array_equal(d.propose(ctx, 2),
                                      d.propose(ctx, 2))
        assert NGramDrafter(
            3, fallback_repeat=False).propose(ctx, 2).size == 0
        with pytest.raises(ValueError, match="min_ngram"):
            NGramDrafter(0)


class TestAcceptHelpers:
    def test_accept_counts_mask_semantics(self):
        # window rows: [incoming, d1, d2, d3]; targets [t0, t1, t2, t3]
        tok = np.array([[9, 4, 5, 6],    # drafts 4,5,6
                        [9, 4, 5, 6],
                        [9, 4, 5, 6],
                        [9, 7, 5, 6]], np.int32)
        t = np.array([[4, 5, 6, 8],      # all drafts match → accept 3
                      [4, 5, 9, 8],      # d3 (6) != t2 (9) → accept 2
                      [4, 5, 6, 8],      # valid clamps accept to 1
                      [4, 5, 6, 8]], np.int32)  # d1 mismatch → 0
        valid = np.ones((4, 4), bool)
        valid[2, 2:] = False
        np.testing.assert_array_equal(
            accept_counts(tok, t, valid), [3, 2, 1, 0])

    def test_truncate_at_eos(self):
        toks = np.array([4, 7, 5], np.int32)
        np.testing.assert_array_equal(truncate_at_eos(toks, 7), [4, 7])
        np.testing.assert_array_equal(truncate_at_eos(toks, 9), toks)
        np.testing.assert_array_equal(truncate_at_eos(toks, None), toks)


class TestOracleEquivalence:
    @pytest.mark.parametrize("spec_k", [2, 4])
    def test_greedy_ngram_oversubscribed_pool_matches_generator(
            self, lm, prompts, oracle, spec_k):
        """Acceptance: speculation at spec_k ∈ {2, 4} under a pool with
        room for ONE request's commitment at a time (2 pages of 8 each,
        3-page pool) emits bitwise Generator-identical tokens, and the
        allocator drains balanced — accept-rewind leaks nothing."""
        model, params = lm
        eng, by_uid = _serve(model, params, prompts, max_batch=2,
                             max_new_tokens=N_NEW, temperature=0.0,
                             spec_k=spec_k, kv_pages=3)
        for uid in by_uid:
            np.testing.assert_array_equal(
                by_uid[uid].tokens, oracle[uid],
                err_msg=f"request {uid} diverged under spec_k={spec_k}")
        eng.pool.check_balanced()
        assert eng.stats()["drafted_tokens"] > 0

    def test_greedy_gpt_drafter_matches_generator(self, lm, prompts,
                                                  oracle):
        """A separate (smaller) GPT draft model behind the same Drafter
        protocol: its proposals are only proposals — output identical."""
        model, params = lm
        draft_model = get_model(
            "transformer_lm", num_classes=VOCAB, num_layers=1,
            num_heads=2, hidden_dim=16, max_len=MAX_LEN)
        draft_params = draft_model.init(
            jax.random.PRNGKey(7), np.zeros((1, 8), np.int32))["params"]
        drafter = GPTDrafter(draft_model, draft_params, window=8)
        eng, by_uid = _serve(model, params, prompts, max_batch=2,
                             max_new_tokens=N_NEW, temperature=0.0,
                             spec_k=2, drafter=drafter)
        for uid in by_uid:
            np.testing.assert_array_equal(by_uid[uid].tokens,
                                          oracle[uid])
        # The drafter contributes its single-shape 'draft' program.
        progs = eng.compiled_programs()
        assert progs.get("draft") == 1
        assert eng.stats()["drafted_tokens"] > 0

    def test_sampled_spec_bitwise_equal_to_nonspec(self, lm, prompts):
        """Fixed-seed sampled outputs: speculation on == speculation
        off, bitwise — the logit-trace/RNG stream is position-pinned,
        so the verify window draws the very samples sequential decode
        would (distribution-identity as an equality of realizations)."""
        model, params = lm
        subset = prompts[:2]
        _, base = _serve(model, params, subset, max_batch=2,
                         max_new_tokens=3, temperature=1.0, top_k=10)
        _, spec = _serve(model, params, subset, max_batch=2,
                         max_new_tokens=3, temperature=1.0, top_k=10,
                         spec_k=2)
        for uid in base:
            np.testing.assert_array_equal(base[uid].tokens,
                                          spec[uid].tokens)


class TestAcceptScheduling:
    def test_budget_clamp_never_overshoots(self, lm, prompts, oracle):
        """max_new_tokens=3 with spec_k=4: the useful draft width
        clamps to the remaining completion budget, the request finishes
        with exactly 3 tokens (reason 'length'), and they match the
        oracle prefix — speculation cannot emit past the budget."""
        model, params = lm
        eng, by_uid = _serve(model, params, [prompts[0]], max_batch=1,
                             max_new_tokens=3, temperature=0.0,
                             spec_k=4)
        fin = by_uid[0]
        assert fin.finish_reason == "length"
        np.testing.assert_array_equal(fin.tokens, oracle[0][:3])
        eng.pool.check_balanced()

    def test_one_token_budget_finishes_at_prefill(self, lm, prompts,
                                                  oracle):
        model, params = lm
        _, by_uid = _serve(model, params, [prompts[0]], max_batch=1,
                           max_new_tokens=1, temperature=0.0, spec_k=2)
        assert by_uid[0].tokens.size == 1
        assert by_uid[0].tokens[0] == oracle[0][0]

    def test_eos_with_speculation(self, lm):
        """Biased head forces EOS as the argmax: with speculation on,
        each request still finishes 'eos' with the single EOS token
        (mid-window continuation past EOS is truncated)."""
        model, params = lm
        eos = 7
        biased = dict(params)
        head = dict(biased["lm_head"])
        head["bias"] = head["bias"].at[eos].add(1e4)
        biased["lm_head"] = head
        eng = Engine(model, biased, ServeConfig(
            max_batch=1, max_new_tokens=N_NEW, eos_id=eos, spec_k=3))
        eng.submit(np.array([1, 2], np.int32))
        eng.submit(np.array([3, 4, 5], np.int32))
        done = eng.run()
        assert len(done) == 2
        for f in done:
            assert f.finish_reason == "eos"
            assert f.tokens.tolist() == [eos]
        eng.pool.check_balanced()


class TestDraftEconomics:
    def test_perfect_drafter_accepts_everything(self, lm, prompts,
                                                oracle, tmp_path):
        """The perfect-acceptance limit: an oracle drafter yields
        acceptance 1.0 and the analytic per-dispatch token count —
        N_NEW-1 decode tokens over ceil((N_NEW-1)/(spec_k+1)) dispatch
        lanes per request. The spec keys ride stats AND the flight dump
        (strict JSON)."""
        import json

        model, params = lm
        spec_k = 2
        eng, by_uid = _serve(
            model, params, prompts[:2], max_batch=1,
            max_new_tokens=N_NEW, temperature=0.0, spec_k=spec_k,
            drafter=_OracleDrafter(prompts, oracle))
        for uid in by_uid:
            np.testing.assert_array_equal(by_uid[uid].tokens,
                                          oracle[uid])
        st = eng.stats()
        assert st["spec_acceptance_rate"] == 1.0
        # Per request: 5 decode tokens in 2 lanes (3 + 2) → 2.5.
        assert st["spec_tokens_per_dispatch"] == pytest.approx(2.5)
        assert st["accepted_tokens"] == st["drafted_tokens"] > 0
        assert st["spec_rollback_s"] >= 0.0
        path = str(tmp_path / "spec_flight.json")
        snap = eng.dump_flight(path)
        assert snap["serving"]["drafted_tokens"] == st["drafted_tokens"]
        json.load(open(path))

    def test_draft_counters_deterministic_across_runs(self, lm,
                                                      prompts):
        """drafted/accepted are pure functions of each request's own
        stream: two identical measurement windows on one warm engine
        agree exactly (the zero-drift contract the bench gate
        enforces)."""
        model, params = lm
        eng = Engine(model, params, ServeConfig(
            max_batch=2, max_new_tokens=N_NEW, temperature=0.0,
            spec_k=2))

        def window():
            for p in prompts:
                eng.submit(p)
            assert len(eng.run()) == len(prompts)
            s = eng.stats()
            eng.reset_stats()
            return (s["drafted_tokens"], s["accepted_tokens"],
                    s["spec_tokens_per_dispatch"])

        first = window()
        assert first[0] > 0
        assert window() == first

    def test_spec_off_reports_neutral_economics(self, lm, prompts):
        model, params = lm
        eng, _ = _serve(model, params, prompts[:1], max_batch=1,
                        max_new_tokens=2, temperature=0.0)
        st = eng.stats()
        assert st["drafted_tokens"] == st["accepted_tokens"] == 0
        assert st["spec_acceptance_rate"] == 0.0
        assert st["spec_tokens_per_dispatch"] == 1.0


class TestHotSwapMidSpeculation:
    def test_swap_rolls_mirror_drafter_inside_barrier(self, lm,
                                                      prompts):
        """A weight swap mid-speculation must leave no stale-drafter
        window: the self-drafting (mirror) GPT drafter's params ARE the
        engine's params after the barrier, and serving continues
        (accept machinery unaffected — a stale draft would only have
        cost acceptance, never correctness)."""
        model, params = lm
        eng = Engine(model, params, ServeConfig(
            max_batch=1, max_new_tokens=N_NEW, temperature=0.0,
            spec_k=2, spec_drafter="gpt", spec_draft_window=8))
        assert eng.drafter.mirror_target
        assert eng.drafter.params is eng.params
        params2 = model.init(jax.random.PRNGKey(3),
                             np.zeros((1, 8), np.int32))["params"]
        eng.submit(prompts[0])
        eng.step()  # seat + first chunk
        eng.arm_swap(params2, epoch=1)
        done = eng.run()
        assert len(done) == 1 and done[0].tokens.size == N_NEW
        assert eng.weights_epoch == 1
        assert eng.drafter.params is eng.params
        assert eng.params is params2
        eng.pool.check_balanced()


class TestServeBenchSpecCli:
    def test_spec_flags_reach_the_sla_line(self, monkeypatch, capsys):
        """The bench surface: --spec-k wires through ServeConfig, the
        SLA line carries the draft economics, and the pool drains
        balanced (serve_bench asserts check_balanced internally)."""
        import json

        from conftest import load_cli_module

        bench = load_cli_module("tools/serve_bench.py")
        monkeypatch.setattr("sys.argv", [
            "serve_bench.py", "--requests", "4", "--rate", "500",
            "--max-batch", "2", "--num-layers", "1", "--num-heads", "2",
            "--hidden-dim", "32", "--vocab-size", "32",
            "--model-max-len", "64", "--prompt-len", "6",
            "--max-new-tokens", "8", "--spec-k", "2"])
        assert bench.main() == 0
        line = capsys.readouterr().out.strip().splitlines()[-1]
        stats = json.loads(line)
        assert stats["requests_finished"] == 4
        assert stats["drafted_tokens"] > 0
        assert stats["spec_tokens_per_dispatch"] >= 1.0


@pytest.mark.slow
class TestSpecSweep:
    """Wider spec_k sweep (heavy: one engine compile per point)."""

    @pytest.mark.parametrize("spec_k", [1, 3, 5])
    def test_paged_sweep_matches_generator(self, lm, prompts, oracle,
                                           spec_k):
        model, params = lm
        eng, by_uid = _serve(model, params, prompts, max_batch=2,
                             max_new_tokens=N_NEW, temperature=0.0,
                             spec_k=spec_k)
        for uid in by_uid:
            np.testing.assert_array_equal(by_uid[uid].tokens,
                                          oracle[uid])
        eng.pool.check_balanced()
