"""SLO-tiered scheduling + lossless preempt-and-requeue tests.

Load-bearing properties, in order of importance:

1. **Lossless preemption** (the repo's signature invariant, extended):
   a sequence evicted mid-flight to seat a higher tier — pages freed,
   commitment released, requeued carrying its emitted tokens — produces
   a final token stream BITWISE identical to an uninterrupted run.
   The re-seat re-prefills prompt+emitted (same positions, same
   ``fold_in(rng, position)`` stream) and continues decoding exactly
   where it left off. Pinned greedy AND sampled, pages of 8 AND 16,
   speculation on AND off; ``check_balanced()`` stays leak-free after
   every preempt/requeue cycle.
2. **Selective degradation mechanics**: strict tier order with no
   lower-tier skip-ahead past a blocked higher tier, weighted-fair
   tenant selection within a tier, per-tenant quotas that fall through
   (never idle slots), tier-aware shedding (best-effort drops first,
   the high tier never sheds while lower work is queued), and reserved
   slot headroom for tier 0.
3. **Drain + deadline correctness under preemption**: ``drain()``
   completes requeued sequences rather than dropping them, and a
   preempted sequence whose deadline expires reports
   ``preempted_timeout`` (not ``timeout``) so telemetry attributes the
   miss to preemption pressure.
4. **Traffic scenarios** (tools/traffic.py): every generator is a pure
   function of (seed, params) — deterministic, arrival-sorted, and
   admissible by construction.

Engines compile real XLA programs, so the model is tiny and parameter
combinations are trimmed to cover every axis value in both greedy and
sampled modes rather than the full product.
"""

import json
import time

import jax
import numpy as np
import pytest

from distributed_training_tpu.config import ServeConfig
from distributed_training_tpu.models import get_model
from distributed_training_tpu.serving import (
    FINISH_LENGTH,
    FINISH_PREEMPT_TIMEOUT,
    FINISH_SHED,
    FINISH_TIMEOUT,
    ActiveSequence,
    Engine,
    QueueFullError,
    Request,
    RequestQueue,
    SlotScheduler,
)

VOCAB = 31
MAX_LEN = 48


@pytest.fixture(scope="module")
def lm():
    model = get_model(
        "transformer_lm", num_classes=VOCAB, num_layers=1, num_heads=2,
        hidden_dim=16, max_len=MAX_LEN)
    params = model.init(jax.random.PRNGKey(0),
                        np.zeros((1, 8), np.int32))["params"]
    return model, params


@pytest.fixture(scope="module")
def prompts():
    rng = np.random.RandomState(3)
    return [rng.randint(0, VOCAB, size=l).astype(np.int32)
            for l in (5, 7, 3, 6)]


def _solo_outputs(model, params, reqs, **cfg_kw):
    """Uninterrupted oracle: serve ``reqs`` one at a time on a single
    slot (uids follow submission order, matching the preemption run's
    — the RNG stream is fold_in(seed, uid), so uid parity is what
    bitwise comparison requires)."""
    eng = Engine(model, params, ServeConfig(max_batch=1, **cfg_kw))
    out = {}
    for prompt, max_new in reqs:
        req = eng.submit(prompt, max_new_tokens=max_new)
        for fin in eng.run():
            out[fin.uid] = fin.tokens.tolist()
        assert req.uid in out
    return out


# Spec 0/2 under both greedy and sampled temperatures, and the page
# size every benchmark cell runs (16; the default is 8) at one corner
# of each, without paying for the full 8-way product.
PREEMPT_CASES = [
    ({"prefill_chunk": 4}, 0.0),
    ({"prefill_chunk": 4}, 0.8),
    ({"prefill_chunk": 4, "kv_page_size": 16}, 0.0),
    ({"prefill_chunk": 4, "spec_k": 2}, 0.8),
    ({"prefill_chunk": 4, "spec_k": 2}, 0.0),
    ({"prefill_chunk": 4, "kv_page_size": 16, "spec_k": 2}, 0.8),
]


class TestLosslessPreemption:
    @pytest.mark.parametrize("cfg_kw,temp", PREEMPT_CASES)
    def test_preempted_resumed_bitwise(self, lm, prompts, cfg_kw, temp):
        """THE invariant: preempt a mid-decode best-effort sequence for
        a tier-0 arrival; both outputs must equal the uninterrupted
        single-slot oracle bitwise, and the pool must drain balanced."""
        model, params = lm
        eng = Engine(model, params, ServeConfig(
            max_batch=1, max_new_tokens=8, num_tiers=2,
            temperature=temp, **cfg_kw))
        low = eng.submit(prompts[0], priority=1, max_new_tokens=8)
        for _ in range(3):  # emit a few tokens before the interloper
            eng.step()
        assert len(eng.scheduler.sequence(0).tokens) >= 1
        high = eng.submit(prompts[1], priority=0, max_new_tokens=4)
        done = {f.uid: f for f in eng.run()}
        eng.pool.check_balanced()
        stats = eng.stats()
        assert stats["requests_preempted"] >= 1
        assert stats["preempted_token_recompute"] >= prompts[0].size
        assert done[low.uid].finish_reason == FINISH_LENGTH
        # The high tier finished FIRST despite arriving second — that
        # is what the preemption bought.
        assert (done[high.uid].last_token_t
                < done[low.uid].last_token_t)
        solo = _solo_outputs(model, params,
                             [(prompts[0], 8), (prompts[1], 4)],
                             temperature=temp, **cfg_kw)
        assert done[low.uid].tokens.tolist() == solo[low.uid]
        assert done[high.uid].tokens.tolist() == solo[high.uid]

    def test_preempt_mid_prefill_restarts_clean(self, lm, prompts):
        """A sequence evicted while still CHUNK-PREFILLING (no token
        emitted yet) restarts from its prompt: same TTFT clock, same
        output, pool balanced."""
        model, params = lm
        eng = Engine(model, params, ServeConfig(
            max_batch=1, max_new_tokens=6, num_tiers=2,
            prefill_chunk=2))
        low = eng.submit(prompts[1], priority=1)  # 7 tokens = 4 chunks
        eng.step()  # first chunk only — still prefilling
        seq = eng.scheduler.sequence(0)
        assert seq.prefilling and not seq.tokens
        high = eng.submit(prompts[2], priority=0, max_new_tokens=4)
        done = {f.uid: f for f in eng.run()}
        eng.pool.check_balanced()
        assert eng.stats()["requests_preempted"] == 1
        solo = _solo_outputs(model, params,
                             [(prompts[1], 6), (prompts[2], 4)],
                             prefill_chunk=2)
        assert done[low.uid].tokens.tolist() == solo[low.uid]
        assert done[high.uid].tokens.tolist() == solo[high.uid]

    def test_repeated_preemption_cycles_leak_free(self, lm, prompts):
        """Several preempt/requeue cycles across a 2-slot engine with an
        oversubscribed pool: every request still completes bitwise-equal
        to the oracle and the pool drains balanced."""
        model, params = lm
        cfg_kw = dict(max_new_tokens=6, prefill_chunk=4, kv_pages=14)
        eng = Engine(model, params, ServeConfig(
            max_batch=2, num_tiers=2, **cfg_kw))
        subs = []  # (uid, prompt, max_new)
        for p in (prompts[0], prompts[1]):
            subs.append((eng.submit(p, priority=1).uid, p, 6))
        for _ in range(3):
            eng.step()
        # Two high-tier arrivals: with 2 slots both low-tier sequences
        # are evicted (pages AND slots contended).
        for p in (prompts[2], prompts[3]):
            subs.append((
                eng.submit(p, priority=0, max_new_tokens=4).uid, p, 4))
        assert eng.phase in ("serving", "overloaded")
        done = {f.uid: f for f in eng.run()}
        eng.pool.check_balanced()
        stats = eng.stats()
        assert stats["requests_preempted"] >= 2
        assert stats["tier1_requests_preempted"] >= 2
        assert stats["tier0_requests_preempted"] == 0
        solo = _solo_outputs(
            model, params, [(p, m) for _, p, m in subs], **cfg_kw)
        for uid, _, _ in subs:
            assert done[uid].tokens.tolist() == solo[uid], uid


class TestDrainAndDeadlines:
    def test_drain_completes_requeued(self, lm, prompts):
        """drain() owes a preempted-and-requeued sequence its
        completion: admission closes, but the resumption re-seats and
        finishes with its full budget — nothing is dropped."""
        model, params = lm
        eng = Engine(model, params, ServeConfig(
            max_batch=1, max_new_tokens=8, num_tiers=2,
            prefill_chunk=4))
        low = eng.submit(prompts[0], priority=1)
        for _ in range(4):
            eng.step()
        eng.submit(prompts[1], priority=0, max_new_tokens=4)
        # Force the preemption pass (the high arrival preempts low): the
        # engine runs one device step ahead, so the first call lands
        # the step in flight and the second, with nothing on its way,
        # preempts.
        eng.step()
        eng.step()
        assert eng.stats()["requests_preempted"] == 1
        done = {f.uid: f for f in eng.drain()}
        eng.pool.check_balanced()
        assert done[low.uid].finish_reason == FINISH_LENGTH
        assert done[low.uid].tokens.size == 8
        assert eng.stats()["drained"] is True

    def test_preempted_then_expired_reports_preempted_timeout(
            self, lm, prompts):
        """Satellite bugfix pin: the deadline clock keeps running while
        a preempted sequence waits requeued; its eviction must report
        ``preempted_timeout`` (carrying the partial tokens), never plain
        ``timeout`` — and the two counters stay distinct. The deadline
        is rewound on the REQUEUED entry directly (a generous config
        deadline would otherwise race the first-step compile time)."""
        import dataclasses

        model, params = lm
        eng = Engine(model, params, ServeConfig(
            max_batch=1, max_new_tokens=8, num_tiers=2,
            prefill_chunk=4, deadline_ms=600000.0))
        low = eng.submit(prompts[0], priority=1)
        for _ in range(3):
            eng.step()
        eng.submit(prompts[1], priority=0, max_new_tokens=8)
        eng.step()  # lands the step in flight: the preemption waits
        emitted_before = len(eng.scheduler.sequence(0).tokens)
        assert emitted_before >= 1
        eng.step()  # nothing in flight: preempts low
        assert eng.stats()["requests_preempted"] == 1
        entry = eng.queue.peek()
        assert isinstance(entry, ActiveSequence)
        assert entry.request.uid == low.uid
        # Rewind the requeued sequence's total deadline into the past —
        # exactly what waiting out a 600 s queue delay would do.
        entry.request = dataclasses.replace(
            entry.request, deadline_t=time.perf_counter() - 1.0)
        done = {f.uid: f for f in eng.drain()}
        eng.pool.check_balanced()
        fin = done[low.uid]
        assert fin.finish_reason == FINISH_PREEMPT_TIMEOUT
        assert fin.slot is None  # evicted queue-side, no slot track
        assert fin.tokens.size == emitted_before  # partial tokens kept
        stats = eng.stats()
        assert stats["requests_preempt_timed_out"] == 1
        assert stats["requests_timed_out"] == 0

    def test_finish_reason_attribution_unit(self):
        """ActiveSequence.finish_reason: the same expired deadline is
        ``timeout`` for a never-preempted sequence and
        ``preempted_timeout`` after a preemption."""
        req = Request(uid=0, prompt=np.ones(3, np.int32),
                      max_new_tokens=8, arrival_t=0.0, deadline_t=1.0)
        seq = ActiveSequence(request=req, slot=0)
        seq.note_token(5, 0.5)
        assert seq.finish_reason(None, now=2.0) == FINISH_TIMEOUT
        seq.prepare_resume()
        assert seq.preempts == 1
        assert seq.finish_reason(None, now=2.0) == FINISH_PREEMPT_TIMEOUT
        # EOS/length still beat the deadline either way.
        seq.tokens = [1] * 8
        assert seq.finish_reason(None, now=2.0) == FINISH_LENGTH

    def test_resume_prefix_snapshot_unit(self):
        """prepare_resume snapshots prompt+emitted-minus-last; the
        prefix must NOT drift as more tokens land after the re-seat."""
        req = Request(uid=0, prompt=np.asarray([1, 2, 3], np.int32),
                      max_new_tokens=8, arrival_t=0.0)
        seq = ActiveSequence(request=req, slot=0)
        for i, tok in enumerate((7, 8, 9)):
            seq.note_token(tok, float(i))
        seq.prefill_pos = 3
        seq.prepare_resume()
        assert seq.prefill_tokens.tolist() == [1, 2, 3, 7, 8]
        assert seq.prefilling
        seq.prefill_pos = seq.prefill_tokens.size
        assert not seq.prefilling
        seq.note_token(10, 3.0)  # decodes further after the re-seat
        assert seq.prefill_tokens.tolist() == [1, 2, 3, 7, 8]
        assert not seq.prefilling


class TestTiersAndFairness:
    def _queue(self, **kw):
        return RequestQueue(budget=32, default_max_new_tokens=4, **kw)

    def test_tier_order_strict_fifo_within_tier(self):
        q = self._queue(num_tiers=3)
        a = q.submit([1], priority=2)
        b = q.submit([1], priority=0)
        c = q.submit([1], priority=1)
        d = q.submit([1], priority=0)
        order = [q.pop() for _ in range(4)]
        assert [r.uid for r in order] == [b.uid, d.uid, c.uid, a.uid]

    def test_priority_out_of_range_rejected(self):
        q = self._queue(num_tiers=2)
        with pytest.raises(ValueError, match="priority"):
            q.submit([1], priority=2)
        with pytest.raises(ValueError, match="priority"):
            q.submit([1], priority=-1)

    def test_weighted_fair_tenant_selection(self):
        """Weight 2:1 — over repeated seats tenant a receives ~2x the
        service of tenant b (service is charged in token units, so the
        pick sequence follows the weighted deficit exactly)."""
        q = self._queue(num_tiers=1,
                        tenant_weights={"a": 2.0, "b": 1.0})
        for _ in range(6):
            q.submit([1], tenant="a")
            q.submit([1], tenant="b")
        picks = []
        for _ in range(9):
            cand = q.next_candidate({})
            picks.append(cand.tenant)
            q.take(cand)
        # First pick ties at service 0 -> lexicographic "a"; from there
        # the 2:1 weights alternate a,a,b.
        assert picks.count("a") == 6 and picks.count("b") == 3

    def test_tenant_quota_falls_through_tiers(self):
        """A tier whose queued tenants are all at quota must not idle
        the slot — the next tier seats instead."""
        q = self._queue(num_tiers=2, tenant_quota=2)
        q.submit([1], priority=0, tenant="a")
        low = q.submit([1], priority=1, tenant="b")
        # tenant a already holds 2 slots -> tier 0 is quota-blocked.
        cand = q.next_candidate({"a": 2})
        assert cand.uid == low.uid
        # Quota freed -> tier 0 wins again.
        cand = q.next_candidate({"a": 1})
        assert cand.uid == 0

    def test_tier_aware_shed_prefers_best_effort(self):
        """On a full queue a high-tier submit sheds the NEWEST queued
        best-effort entry (surfaced via take_shed); an incoming
        best-effort submit on a queue full of high-tier work sheds
        ITSELF with the typed QueueFullError."""
        q = self._queue(num_tiers=2, max_depth=2)
        q.submit([1], priority=1)
        victim = q.submit([1], priority=1)
        keeper = q.submit([1], priority=0)  # sheds the newest tier-1
        shed = q.take_shed()
        assert [e.uid for e in shed] == [victim.uid]
        assert q.shed_by_tier == [0, 1]
        assert len(q) == 2  # the older tier-1 entry + the keeper
        with pytest.raises(QueueFullError):
            q.submit([1], priority=1)  # nothing below tier 1 to shed
        assert q.shed_by_tier == [0, 2]
        assert keeper.priority == 0

    def test_requeue_reseats_in_arrival_order(self):
        """A preempted resumption re-enters its tier ahead of younger
        same-tier work (uid order), so preemption never reorders a
        tenant's stream."""
        q = self._queue(num_tiers=2)
        old = q.submit([1], priority=1)
        young = q.submit([1], priority=1)
        cand = q.next_candidate({})
        assert cand.uid == old.uid
        q.take(cand)
        seq = ActiveSequence(request=old, slot=0)
        seq.note_token(4, 0.0)
        seq.prepare_resume()
        q.requeue(seq)
        heads = [q.pop() for _ in range(2)]
        assert isinstance(heads[0], ActiveSequence)
        assert heads[0].request.uid == old.uid
        assert heads[1].uid == young.uid

    def test_reserved_slots_hold_headroom_for_tier0(self):
        """SlotScheduler with reserved_slots=1 on 2 slots: best-effort
        fills only the unreserved slot; a tier-0 arrival takes the
        reserve without needing a preemption."""
        q = self._queue(num_tiers=2)
        q.submit([1], priority=1)
        q.submit([1], priority=1)
        sched = SlotScheduler(2, reserved_slots=1)
        seated = sched.admit(q)
        assert len(seated) == 1 and sched.num_active == 1
        assert len(q) == 1  # second best-effort blocked on the reserve
        q.submit([1], priority=0)
        seated = sched.admit(q)
        # Tier 0 ignores the reserve; the queued tier-1 stays blocked.
        assert [s.request.priority for s in seated] == [0]
        assert sched.num_active == 2 and len(q) == 1

    def test_take_tolerates_concurrent_shed(self):
        """A producer-side tier-aware shed can remove the scheduler's
        chosen candidate between next_candidate() and take() (separate
        lock sections): take() must report False — nothing removed,
        nothing charged — and the admission pass re-polls instead of
        crashing."""
        q = self._queue(num_tiers=2, max_depth=1)
        cand = q.submit([1], priority=1)
        picked = q.next_candidate({})
        assert picked.uid == cand.uid
        q.submit([1], priority=0)  # full queue: sheds the tier-1 entry
        assert [e.uid for e in q.take_shed()] == [cand.uid]
        assert q.take(picked) is False
        # The pass re-polls and seats the tier-0 entry normally.
        sched = SlotScheduler(1)
        seated = sched.admit(q)
        assert [s.request.priority for s in seated] == [0]

    def test_futile_preemption_is_bounded(self):
        """A candidate that could never seat even after evicting EVERY
        strictly-lower-tier active must not evict any of them (the
        engine's preempt_helps futility bound): best-effort progress is
        only thrown away when it buys an admission."""
        q = self._queue(num_tiers=2)
        q.submit([1], priority=1)
        q.submit([1], priority=1)
        sched = SlotScheduler(2)
        sched.admit(q)
        assert sched.num_active == 2
        q.submit([1] * 20, priority=0)  # too big for the whole pool
        preempted = []
        seated = sched.admit(
            q, on_preempt=preempted.append,
            preempt_helps=lambda entry, victims: False)
        assert seated == [] and preempted == []
        assert sched.num_active == 2  # nothing evicted for nothing

    def test_engine_futility_bound_keeps_best_effort_running(self, lm,
                                                             prompts):
        """Engine-level futility bound: a tier-0 candidate whose
        worst-case commitment exceeds available + EVERY preemptible
        page (most of the pool is pinned by non-preemptible tier-0
        work) must not evict the best-effort sequence — eviction is
        only paid when it buys an admission. The blocked candidate
        still seats later, once finished tier-0 work returns pages."""
        model, params = lm
        # 6-page pool (size 8). Tier-0 A commits 3 pages (9+8=17 tok),
        # tier-1 B commits 2 (3+8=11), leaving 1 available. Tier-0 C
        # needs 4 (24+8=32): 1 free + 2 preemptible (B) = 3 < 4 —
        # evicting B buys nothing, so B must keep decoding.
        eng = Engine(model, params, ServeConfig(
            max_batch=3, num_tiers=2, kv_page_size=8, kv_pages=6,
            max_len=32, max_new_tokens=8, prefill_chunk=4))
        a = eng.submit(np.arange(9, dtype=np.int32) % VOCAB,
                       priority=0, max_new_tokens=8)
        low = eng.submit(prompts[2], priority=1, max_new_tokens=8)
        for _ in range(4):
            eng.step()
        assert eng.scheduler.num_active == 2
        c = eng.submit(np.arange(24, dtype=np.int32) % VOCAB,
                       priority=0, max_new_tokens=8)
        eng.step()
        assert eng.stats()["requests_preempted"] == 0  # futile: skipped
        assert eng.scheduler.num_active == 2  # A and B still seated
        assert eng.phase == "overloaded"  # C is head-of-line blocked
        done = {f.uid: f for f in eng.run()}
        eng.pool.check_balanced()
        assert eng.stats()["requests_preempted"] == 0
        for uid in (a.uid, low.uid, c.uid):
            assert done[uid].tokens.size == 8

    def test_preemption_strictly_rank_ordered(self):
        """scheduler.admit only ever evicts STRICTLY lower tiers: an
        equal-tier candidate waits (no churn), and the victim is the
        worst tier's newest sequence."""
        q = self._queue(num_tiers=3)
        q.submit([1], priority=1)
        q.submit([1], priority=2)
        sched = SlotScheduler(2)
        sched.admit(q)
        assert sched.num_active == 2
        # Equal tier: no preemption, stays queued.
        q.submit([1], priority=2)
        assert sched.admit(q) == []
        assert len(q) == 1
        # Higher tier: evicts the tier-2 victim, not the tier-1 one;
        # the requeued victim cannot re-seat (both slots now hold
        # equal-or-higher tiers), so it waits with the other tier-2.
        q.submit([1], priority=0)
        preempted = []
        seated = sched.admit(q, on_preempt=preempted.append)
        assert [s.request.priority for s in seated] == [0]
        assert [p.request.priority for p in preempted] == [2]
        active = sorted(s.request.priority for s in sched.active())
        assert active == [0, 1]
        assert len(q) == 2


class TestTrafficScenarios:
    def test_scenarios_deterministic_sorted_admissible(self):
        from tools.traffic import SCENARIOS, make_scenario

        kw = dict(seed=5, requests=40, rate=200, mean_prompt_len=8,
                  max_prompt_len=40, max_new_tokens=16, vocab_size=64,
                  budget=56)
        for name, scen in SCENARIOS.items():
            a = make_scenario(name, **kw)
            b = make_scenario(name, **kw)
            assert len(a) == len(b) >= 1, name
            for x, y in zip(a, b):
                assert x.arrival_s == y.arrival_s, name
                assert np.array_equal(x.prompt, y.prompt), name
                assert (x.priority, x.tenant, x.max_new_tokens) == \
                    (y.priority, y.tenant, y.max_new_tokens), name
            assert all(a[i].arrival_s <= a[i + 1].arrival_s
                       for i in range(len(a) - 1)), name
            for r in a:
                assert 1 <= r.prompt.size <= 40, name
                assert r.prompt.size + r.max_new_tokens <= 56, name
                assert 0 <= r.priority < scen.num_tiers, name
            tiers = set(r.priority for r in a)
            assert len(tiers) == scen.num_tiers, (name, tiers)

    def test_unknown_scenario_raises(self):
        from tools.traffic import make_scenario

        with pytest.raises(ValueError, match="unknown scenario"):
            make_scenario("nope", seed=0, requests=1, rate=1.0,
                          mean_prompt_len=4, max_prompt_len=8,
                          max_new_tokens=4, vocab_size=8, budget=16)

    def test_different_seeds_differ(self):
        from tools.traffic import make_scenario

        kw = dict(requests=20, rate=100, mean_prompt_len=8,
                  max_prompt_len=30, max_new_tokens=8, vocab_size=64,
                  budget=40)
        a = make_scenario("bursty", seed=1, **kw)
        b = make_scenario("bursty", seed=2, **kw)
        assert [r.arrival_s for r in a] != [r.arrival_s for r in b]


class TestServeBenchOverloadCli:
    def test_overload_drill_selective_degradation(self, monkeypatch,
                                                  capsys):
        """The CI drill in miniature: two_tier_burst at an unsustainable
        rate under the deterministic --virtual-dt drive. Tier 0 must
        finish everything it submitted un-shed while tier 1 absorbs the
        shed/preempt pressure, and the SLA line must carry the per-tier
        keys the bench gate diffs. A call releases 1.8 ms of arrivals
        (2 before the engine ran one step ahead): a request of 8 tokens
        now holds its slot nine calls, not eight — its successor is
        seated one iteration after its last token lands — and at 2 ms
        the best-effort tier is shed before any of it is served."""
        from conftest import load_cli_module

        bench = load_cli_module("tools/serve_bench.py")
        monkeypatch.setattr("sys.argv", [
            "serve_bench.py", "--requests", "24", "--rate", "800",
            "--max-batch", "2", "--kv-pages", "24", "--num-layers", "1",
            "--num-heads", "2", "--hidden-dim", "32",
            "--model-max-len", "64", "--prompt-len", "8",
            "--max-new-tokens", "8", "--prefill-chunk", "8",
            "--scenario", "two_tier_burst", "--virtual-dt", "1.8",
            "--max-queue-depth", "6"])
        assert bench.main() == 0
        stats = json.loads(
            capsys.readouterr().out.strip().splitlines()[-1])
        assert stats["scenario"] == "two_tier_burst"
        for key in ("requests_preempted", "preempted_token_recompute",
                    "tier0_requests_finished", "tier1_requests_finished",
                    "tier0_requests_shed", "tier1_requests_shed",
                    "tier0_ttft_hist_p99_ms", "tier1_ttft_hist_p99_ms",
                    "requests_preempt_timed_out", "shed_at_submit"):
            assert key in stats, key
        # Selective degradation: the high tier is untouched while the
        # best-effort tier sheds and is preempted.
        assert stats["tier0_requests_shed"] == 0
        assert stats["tier1_requests_shed"] > 0
        assert stats["requests_preempted"] > 0
        assert stats["requests_timed_out"] == 0
        # two_tier_burst submits 40% tier-0 (see tools/traffic.py).
        assert stats["tier0_requests_finished"] == 10
        # Ordering claim, scale-free: the high tier's p99 beats the
        # best-effort tier's.
        assert (stats["tier0_ttft_hist_p99_ms"]
                < stats["tier1_ttft_hist_p99_ms"])


@pytest.mark.slow
class TestChaosComposition:
    def test_preempt_storm_during_speculation_and_hotswap(self, lm):
        """The composed drill: a preemption storm (best-effort work
        occupying every slot, tier-0 waves evicting it) runs WITH
        speculative decoding while a live weight hot-swap barrier fires
        mid-storm. Zero failed requests, pool balanced, and — because
        the swapped-in tree carries identical values — every output
        bitwise equal to the uninterrupted single-slot oracle."""
        model, params = lm
        from tools.traffic import make_scenario

        reqs = make_scenario(
            "preempt_storm", seed=7, requests=18, rate=500,
            mean_prompt_len=6, max_prompt_len=20, max_new_tokens=10,
            vocab_size=VOCAB, budget=MAX_LEN)
        cfg_kw = dict(max_new_tokens=10, prefill_chunk=4, spec_k=2,
                      kv_pages=30)
        eng = Engine(model, params, ServeConfig(
            max_batch=2, num_tiers=2, **cfg_kw))
        same_values = jax.tree.map(lambda a: np.asarray(a).copy(),
                                   params)
        submitted = 0
        it = 0
        uids = {}
        done = {}
        while submitted < len(reqs):
            vnow = it * 0.002
            while (submitted < len(reqs)
                   and reqs[submitted].arrival_s <= vnow):
                r = reqs[submitted]
                req = eng.submit(r.prompt,
                                 max_new_tokens=r.max_new_tokens,
                                 priority=r.priority, tenant=r.tenant)
                uids[submitted] = req.uid
                submitted += 1
                if submitted == 9:
                    # Same-values tree: the barrier machinery runs for
                    # real (validate + install + drafter re-point) but
                    # outputs stay comparable to the no-swap oracle.
                    eng.arm_swap(same_values, epoch=1)
            for fin in eng.step():
                done[fin.uid] = fin
            it += 1
        for fin in eng.drain():
            done[fin.uid] = fin
        eng.pool.check_balanced()
        stats = eng.stats()
        assert stats["requests_finished"] == len(reqs)
        assert stats["requests_preempted"] >= 1
        assert stats["requests_shed"] == 0
        assert stats["requests_timed_out"] == 0
        assert stats["requests_preempt_timed_out"] == 0
        assert stats["swaps_completed"] == 1
        assert stats["drafted_tokens"] > 0
        solo = _solo_outputs(
            model, params,
            [(r.prompt, r.max_new_tokens) for r in reqs], **cfg_kw)
        for i, r in enumerate(reqs):
            uid = uids[i]
            assert done[uid].tokens.tolist() == solo[uid], (i, uid)
