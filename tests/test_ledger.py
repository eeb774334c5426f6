"""Per-request latency ledger: conserved millisecond attribution.

Load-bearing properties, in order of importance:

1. **Conservation** (the invariant): every finished request's
   ``(cause, start, end)`` intervals partition its wall lifetime —
   ``sum(intervals) == finish_t − arrival_t`` within
   ``ledger.EPSILON_S`` — under EVERY composition the engine supports:
   greedy/sampled × pages of 8/16 × speculation on/off × preemption ×
   hot-swap × crash recovery, and for queue-side completions (timeout,
   shed) that never reached a slot.
2. **TTFT decomposition**: for an unpreempted, unrecovered request,
   ``queue_wait + prefill (+ journal_admit) == TTFT`` exactly — the
   ledger's totals reproduce the independently measured SLA number.
3. **Deterministic token attribution**: the per-cause token counters
   are pure functions of each request's token stream
   (``ledger_tokens_decode == tokens_emitted``,
   ``ledger_tokens_recompute`` mirrors the preempt/recovery recompute
   counters) — the zero-drift evidence the bench gate holds.
4. **Audit enforcement**: a tampered or unclosed ledger is COUNTED
   (``ledger_conservation_violations``) — the invariant is checked
   in-engine at every completion, not post-hoc.
5. **Window-reset semantics** (round-17 precedent extended): the
   per-cause LIFETIME histograms and the violation audit survive
   ``Engine.reset_stats``; the windowed token counters start fresh.

Engines compile real XLA programs, so the model is tiny and the tier-1
matrix covers every axis value pairwise; the full 8-way product runs
under ``-m slow`` (the CI ledger drill exercises the big
preempt-storm × swap × spec composition through serve_bench).
"""

import time

import jax
import numpy as np
import pytest

from distributed_training_tpu.config import ServeConfig
from distributed_training_tpu.models import get_model
from distributed_training_tpu.serving import (
    FINISH_TIMEOUT,
    Engine,
    FinishedRequest,
    LatencyLedger,
    QueueFullError,
    ServeTelemetry,
)
from distributed_training_tpu.serving.ledger import (
    CAUSE_DECODE,
    CAUSE_JOURNAL_ADMIT,
    CAUSE_PREEMPT_REQUEUE,
    CAUSE_PREFILL,
    CAUSE_QUEUE_WAIT,
    CAUSE_RECOMPUTE,
    CAUSE_RECOVERY,
    CAUSE_SWAP_BARRIER,
    EPSILON_S,
    LEDGER_CAUSES,
    TOKEN_CAUSES,
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
def lm_params2(lm):
    model, _ = lm
    return model.init(jax.random.PRNGKey(1),
                      np.zeros((1, 8), np.int32))["params"]


@pytest.fixture(scope="module")
def prompts():
    rng = np.random.RandomState(3)
    return [rng.randint(0, VOCAB, size=l).astype(np.int32)
            for l in (5, 7, 3, 6)]


def _audit(fins, engine=None):
    """Every finished request's ledger closed and conserved; zero
    engine-side violations."""
    assert fins
    for f in fins:
        led = f.ledger
        assert led is not None and led.closed, f"uid {f.uid}: no ledger"
        v = led.violations(ttft_ms=f.ttft_ms)
        assert not v, f"uid {f.uid} ({f.finish_reason}): {v}"
    if engine is not None:
        st = engine.stats()
        assert st["ledger_conservation_violations"] == 0, st


def _ttft_split(fins):
    """Property 2: queue_wait + prefill (+ journal_admit) == TTFT for
    every request untouched by preemption/recovery."""
    checked = 0
    for f in fins:
        if f.ttft_ms is None:
            continue
        totals = f.ledger.totals_ms()
        if any(totals.get(c) for c in (CAUSE_PREEMPT_REQUEUE,
                                       CAUSE_RECOMPUTE, CAUSE_RECOVERY)):
            continue
        split = (totals.get(CAUSE_QUEUE_WAIT, 0.0)
                 + totals.get(CAUSE_PREFILL, 0.0)
                 + totals.get(CAUSE_JOURNAL_ADMIT, 0.0)
                 + totals.get(CAUSE_SWAP_BARRIER, 0.0))
        assert abs(split - f.ttft_ms) <= EPSILON_S * 1e3 * 4, (
            f.uid, split, f.ttft_ms, totals)
        checked += 1
    assert checked > 0


class TestLedgerUnit:
    def test_stamp_coalesce_clamp_and_totals(self):
        led = LatencyLedger(10.0)
        led.stamp("queue_wait", 11.0)
        led.stamp("prefill", 11.5)
        led.stamp("prefill", 12.0)      # coalesces with the previous
        led.stamp("decode", 11.0)       # clock glitch: clamps, 0-width
        led.stamp("decode", 13.0)
        assert [iv[0] for iv in led.intervals] == [
            "queue_wait", "prefill", "decode"]
        assert led.total_s("prefill") == pytest.approx(1.0)
        led.add_tokens("decode", 3)
        led.add_tokens("decode", 2)
        assert led.tokens == {"decode": 5}
        led.close("decode", 13.25)
        assert led.closed and led.finish_t == pytest.approx(13.25)
        assert not led.violations()
        assert led.lifetime_ms == pytest.approx(3250.0)
        d = led.to_dict()
        assert d["conserved"] and len(d["intervals"]) == 3

    def test_admit_handoff_materializes_on_engine_stamp(self):
        """The journal_admit span is a producer-thread HANDOFF (one
        attribute store); the interval itself is appended by the next
        engine-side stamp — and if the engine raced ahead (seated the
        request before the fsync returned), the span clamps away
        without ever breaking conservation."""
        led = LatencyLedger(0.0)
        led.note_admit_done(0.004)
        led.stamp(CAUSE_QUEUE_WAIT, 0.010)  # seat materializes both
        assert [iv[0] for iv in led.intervals] == [
            CAUSE_JOURNAL_ADMIT, CAUSE_QUEUE_WAIT]
        assert led.total_s(CAUSE_JOURNAL_ADMIT) == pytest.approx(0.004)
        led.close(CAUSE_DECODE, 0.020)
        assert not led.violations()
        # Raced: the engine seated BEFORE the admit write returned —
        # the admission span clamps away entirely, even when the
        # admit-done instant lands AFTER the seat (billing the post-
        # seat span to journal_admit would mislabel in-slot work).
        for admit_t in (0.002, 0.015):
            led2 = LatencyLedger(0.0)
            led2.stamp(CAUSE_QUEUE_WAIT, 0.010)
            led2.note_admit_done(admit_t)
            led2.close(CAUSE_DECODE, 0.020)
            assert led2.total_s(CAUSE_JOURNAL_ADMIT) == 0.0
            assert led2.total_s(CAUSE_DECODE) == pytest.approx(0.010)
            assert not led2.violations()

    def test_unclosed_and_tampered_ledgers_violate(self):
        led = LatencyLedger(0.0)
        led.stamp("queue_wait", 1.0)
        assert led.violations()  # never closed
        led.close("decode", 2.0)
        assert not led.violations()
        # Tamper: an interval that no longer telescopes breaks the sum.
        led.intervals[0][2] = 0.5
        v = led.violations()
        assert v and "sum(intervals)" in v[0]

    def test_ttft_boundary_and_early_decode_checks(self):
        led = LatencyLedger(0.0)
        led.stamp("queue_wait", 0.010)
        led.stamp("prefill", 0.020)
        led.stamp("decode", 0.050)
        led.close("decode")
        assert not led.violations(ttft_ms=20.0)
        # First token instant not on a stamp boundary:
        assert any("boundary" in s for s in led.violations(ttft_ms=15.0))
        # decode attributed before the first token:
        assert any("before the first token" in s
                   for s in led.violations(ttft_ms=60.0))

    def test_telemetry_counts_violations(self):
        tel = ServeTelemetry(64)
        led = LatencyLedger(0.0)
        led.stamp("queue_wait", 1.0)  # never closed -> violation
        fin = FinishedRequest(
            uid=7, prompt=np.zeros((2,), np.int32),
            tokens=np.zeros((0,), np.int32),
            finish_reason=FINISH_TIMEOUT, ttft_ms=None, tpot_ms=None,
            arrival_t=0.0, first_token_t=None, ledger=led)
        tel.on_finished(fin)
        assert tel.ledger_conservation_violations == 1
        assert "uid 7" in tel.ledger_violation_last
        st = tel.stats()
        assert st["ledger_conservation_violations"] == 1
        # Redelivered results (no ledger) are skipped, never violations.
        tel.on_finished(FinishedRequest(
            uid=8, prompt=np.zeros((2,), np.int32),
            tokens=np.zeros((0,), np.int32),
            finish_reason=FINISH_TIMEOUT, ttft_ms=None, tpot_ms=None,
            arrival_t=0.0, first_token_t=None))
        assert tel.ledger_conservation_violations == 1

    def test_stats_keys_always_present(self):
        st = ServeTelemetry(64).stats()
        for c in LEDGER_CAUSES:
            assert st[f"ledger_{c}_ms_total"] == 0.0
        for c in TOKEN_CAUSES:
            assert st[f"ledger_tokens_{c}"] == 0
        assert st["ledger_requests"] == 0
        assert st["ledger_conservation_violations"] == 0


# Every axis value (greedy/sampled, spec 0/2) appears twice across the
# tier-1 cases, and the page size every benchmark cell runs (16; the
# default is 8) once.
MATRIX_T1 = [
    ({"prefill_chunk": 4}, 0.0),
    ({"prefill_chunk": 4, "spec_k": 2}, 0.8),
    ({"prefill_chunk": 4, "kv_page_size": 16}, 0.8),
    ({"prefill_chunk": 4, "spec_k": 2}, 0.0),
]
MATRIX_FULL = [
    ({"prefill_chunk": 4, **({"spec_k": spec} if spec else {})}, temp)
    for spec in (0, 2)
    for temp in (0.0, 0.8)
]


class TestConservationMatrix:
    def _run(self, lm, prompts, cfg_kw, temp):
        model, params = lm
        eng = Engine(model, params, ServeConfig(
            max_batch=2, max_new_tokens=6, temperature=temp, **cfg_kw))
        for p in prompts:
            eng.submit(p)
        done = eng.run()
        assert len(done) == len(prompts)
        _audit(done, eng)
        _ttft_split(done)
        st = eng.stats()
        assert st["ledger_requests"] == len(prompts)
        assert st["ledger_tokens_decode"] == st["tokens_emitted"]
        assert st["ledger_tokens_prefill"] == sum(p.size for p in prompts)
        assert st["ledger_tokens_recompute"] == 0
        if cfg_kw.get("spec_k"):
            assert st["ledger_tokens_spec_draft"] == st["drafted_tokens"]
            assert st["ledger_tokens_spec_accept"] == \
                st["accepted_tokens"]

    @pytest.mark.parametrize("cfg_kw,temp", MATRIX_T1)
    def test_conservation(self, lm, prompts, cfg_kw, temp):
        self._run(lm, prompts, cfg_kw, temp)

    @pytest.mark.slow
    @pytest.mark.parametrize("cfg_kw,temp", MATRIX_FULL)
    def test_conservation_full(self, lm, prompts, cfg_kw, temp):
        self._run(lm, prompts, cfg_kw, temp)


class TestChaosCompositions:
    def test_preempt_swap_spec_conserves(self, lm, lm_params2, prompts):
        """Preemption × hot-swap barrier × speculation in one run: the
        evicted request's ledger carries preempt_requeue + recompute,
        in-flight requests carry swap_barrier, everything conserves,
        and the recompute token counter mirrors the engine-global one."""
        model, params = lm
        eng = Engine(model, params, ServeConfig(
            max_batch=1, max_new_tokens=8, num_tiers=2,
            prefill_chunk=4, spec_k=2))
        eng.submit(prompts[0], priority=1, max_new_tokens=8)
        for _ in range(3):
            eng.step()
        eng.submit(prompts[1], priority=0, max_new_tokens=4)
        eng.arm_swap(lm_params2, epoch=1)
        done = eng.run()
        assert len(done) == 2
        st = eng.stats()
        assert st["requests_preempted"] == 1
        assert st["swaps_completed"] == 1
        _audit(done, eng)
        preempted = [f for f in done
                     if f.ledger.totals_ms().get(CAUSE_PREEMPT_REQUEUE)]
        assert len(preempted) == 1
        assert preempted[0].ledger.totals_ms().get(CAUSE_RECOMPUTE)
        assert any(CAUSE_SWAP_BARRIER in f.ledger.totals_ms()
                   for f in done)
        assert st["ledger_tokens_recompute"] == \
            st["preempted_token_recompute"]

    def test_mid_prefill_preempt_token_split(self, lm):
        """A request preempted MID-prefill re-prefills its whole prompt,
        but only the positions it had actually written count as
        recompute — the never-written tail stays first-time 'prefill'
        work, so ledger_tokens_prefill == the prompt size exactly and
        ledger_tokens_recompute == preempted_token_recompute."""
        model, params = lm
        rng = np.random.RandomState(7)
        long_prompt = rng.randint(0, VOCAB, size=16).astype(np.int32)
        eng = Engine(model, params, ServeConfig(
            max_batch=1, max_new_tokens=4, num_tiers=2,
            prefill_chunk=4))
        eng.submit(long_prompt, priority=1)
        eng.step()  # one 4-token chunk written, 12 to go
        seq = eng.scheduler.sequence(0)
        assert seq.prefilling and 0 < seq.prefill_pos < 16
        # the chunk in flight lands before the victim leaves
        written = seq.prefill_pos + eng.prefill_chunk
        eng.submit(rng.randint(0, VOCAB, size=3).astype(np.int32),
                   priority=0, max_new_tokens=2)
        done = eng.run()
        st = eng.stats()
        assert st["requests_preempted"] == 1
        assert st["preempted_token_recompute"] == written
        assert st["ledger_tokens_recompute"] == written
        assert st["ledger_tokens_prefill"] == 16 + 3
        _audit(done, eng)

    @pytest.mark.parametrize("temp", [0.0, 0.8])
    def test_crash_recovery_conserves(self, lm, prompts, tmp_path, temp):
        """Kill/restart on the journal: resumed requests bill pre_crash
        (durable tokens) + recovery (downtime/replay, wall-anchored) +
        recompute (the re-prefill), conserve exactly, and the recompute
        token counter mirrors tokens_recomputed_on_recovery."""
        model, params = lm
        cfg = dict(max_batch=2, max_new_tokens=8, prefill_chunk=4,
                   temperature=temp, journal_dir=str(tmp_path))
        eng = Engine(model, params, ServeConfig(**cfg))
        eng.recover()
        for p in prompts[:3]:
            eng.submit(p)
        for _ in range(4):
            eng.step()
        eng.journal.persist()
        eng.journal.crash()

        eng2 = Engine(model, params, ServeConfig(**cfg))
        rep = eng2.recover()
        done = eng2.run()
        st = eng2.stats()
        assert st["requests_recovered"] == 3
        fins = done + rep["completed_at_replay"]
        _audit(fins, eng2)
        resumed = [f for f in done
                   if f.ledger.totals_ms().get(CAUSE_RECOVERY)]
        assert resumed
        assert st["ledger_tokens_recompute"] == \
            st["tokens_recomputed_on_recovery"]
        # Redelivered results carry no ledger and are not audited.
        assert all(f.ledger is None for f in rep["redelivered"])
        assert st["ledger_conservation_violations"] == 0

    def test_queue_timeout_and_shed_conserve(self, lm, prompts):
        """The unit pin the issue names: requests finished with reason
        timeout (queue-side deadline) or shed (tier-aware drop) still
        conserve — their whole lifetime bills to waiting causes."""
        model, params = lm
        eng = Engine(model, params, ServeConfig(
            max_batch=1, max_new_tokens=4, prefill_chunk=4, num_tiers=2,
            max_queue_depth=2, ttft_deadline_ms=1.0))
        eng.submit(prompts[0], priority=1)
        eng.submit(prompts[1], priority=1)
        # Full queue + higher tier -> the newest tier-1 entry sheds.
        eng.submit(prompts[2], priority=0)
        time.sleep(0.005)  # run out the 1 ms TTFT deadlines
        done = eng.drain()
        st = eng.stats()
        reasons = sorted(f.finish_reason for f in done)
        assert "shed" in reasons and "timeout" in reasons, reasons
        _audit(done, eng)
        for f in done:
            if f.tokens.size == 0:  # never served: waiting causes only
                assert set(f.ledger.totals_ms()) <= {
                    CAUSE_QUEUE_WAIT, CAUSE_PREEMPT_REQUEUE}, \
                    f.ledger.totals_ms()

    def test_slot_deadline_eviction_conserves(self, lm, prompts):
        """A mid-decode total-deadline eviction (partial tokens) closes
        the ledger at the eviction boundary and conserves."""
        model, params = lm
        eng = Engine(model, params, ServeConfig(
            max_batch=1, max_new_tokens=40, prefill_chunk=4,
            deadline_ms=30.0))
        eng.submit(prompts[0])
        done = eng.run()
        assert len(done) == 1
        assert done[0].finish_reason in ("timeout", "length")
        _audit(done, eng)

    def test_queue_full_shed_at_submit_has_no_completion(self, lm,
                                                         prompts):
        model, params = lm
        eng = Engine(model, params, ServeConfig(
            max_batch=1, max_new_tokens=4, max_queue_depth=1))
        eng.submit(prompts[0])  # queued (nothing has stepped yet)
        with pytest.raises(QueueFullError):
            eng.submit(prompts[1])  # full queue, nothing lower to shed
        done = eng.drain()
        assert len(done) == 1  # the rejected request never existed
        _audit(done, eng)


class TestLedgerTelemetry:
    def test_reset_stats_preserves_lifetime_histograms(self, lm,
                                                       prompts):
        """The round-17 precedent extended (the issue's bugfix): a
        warm-up window reset must preserve the per-cause lifetime
        histograms AND the conservation audit, while the windowed
        deterministic counters start fresh."""
        model, params = lm
        eng = Engine(model, params, ServeConfig(
            max_batch=2, max_new_tokens=4, prefill_chunk=4))
        for p in prompts[:2]:
            eng.submit(p)
        eng.run()
        tel = eng.telemetry
        decode_hist = tel.ledger_cause_ms[CAUSE_DECODE]
        assert decode_hist.total > 0
        counts_before = {c: tel.ledger_cause_ms[c].total
                         for c in LEDGER_CAUSES}
        # Stage a violation so the audit-carry is observable too.
        bad = LatencyLedger(0.0)
        bad.stamp(CAUSE_QUEUE_WAIT, 1.0)  # never closed
        tel.on_finished(FinishedRequest(
            uid=99, prompt=np.zeros((1,), np.int32),
            tokens=np.zeros((0,), np.int32),
            finish_reason=FINISH_TIMEOUT, ttft_ms=None, tpot_ms=None,
            arrival_t=0.0, first_token_t=None, ledger=bad))
        eng.reset_stats()
        st = eng.stats()
        # Lifetime evidence preserved...
        for c in LEDGER_CAUSES:
            assert eng.telemetry.ledger_cause_ms[c].total == \
                (counts_before[c] + (1 if c == CAUSE_QUEUE_WAIT else 0))
        assert st["ledger_conservation_violations"] == 1
        # ...windowed surfaces fresh: the SLA line's per-cause totals
        # describe only the requests the new window audits (warm-up
        # wall time never pollutes the decomposition).
        assert st["ledger_requests"] == 0
        for c in TOKEN_CAUSES:
            assert st[f"ledger_tokens_{c}"] == 0
        for c in LEDGER_CAUSES:
            assert st[f"ledger_{c}_ms_total"] == 0.0
        assert eng.telemetry.ledger_top == []

    def test_flight_surfaces_carry_ledger(self, lm, prompts, tmp_path):
        """The per-cause histograms and the slowest-request
        decomposition ride the serving section of dumps and live
        scrapes (strict JSON, flight_report-renderable)."""
        import json

        from distributed_training_tpu.observability.flight_recorder \
            import FlightRecorder

        model, params = lm
        eng = Engine(model, params, ServeConfig(
            max_batch=2, max_new_tokens=4, prefill_chunk=4))
        for p in prompts:
            eng.submit(p)
        done = eng.run()
        snap = eng.flight_snapshot()
        srv = snap["serving"]
        assert srv["ledger_requests"] == len(done)
        assert f"ledger_{CAUSE_DECODE}_ms" in srv["histograms"]
        top = srv["ledger_top"]
        assert top and top[0]["lifetime_ms"] >= top[-1]["lifetime_ms"]
        assert set(top[0]["causes_ms"]) <= set(LEDGER_CAUSES)
        json.dumps(snap, allow_nan=False)  # strict JSON or bust
        path = str(tmp_path / "ledger_flight.json")
        eng.dump_flight(path)
        loaded = FlightRecorder.load(path)
        assert loaded["serving"]["ledger_requests"] == len(done)

        import tools.flight_report as fr

        text = fr.render(fr.summarize(loaded))
        assert "latency ledger" in text
        assert "0 conservation violation(s)" in text
