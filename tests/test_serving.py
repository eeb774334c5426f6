"""Serving subsystem tests: continuous batching over the KV cache.

Load-bearing properties, in order of importance:

1. **Oracle equivalence**: batched continuous-batching greedy decode —
   paged KV pool + chunked prefill, the default — is token-identical to
   the sequential :class:`Generator` (temperature 0) run per prompt:
   slot packing, page-table gathers, chunked prefill, and mid-flight
   refills must not change a single emitted token.
2. **Composition independence**: a request's tokens are bitwise
   independent of which other requests share the batch (engine at
   max_batch=N == engine at max_batch=1), greedy AND sampled — per-row
   arithmetic independence and fold_in(uid, position) RNG guarantee it.
   The solo engine runs a DIFFERENT prefill chunking (chunk 4, forcing
   multi-chunk prefills) against the batched engine's single-chunk
   prefills, so the same equality pins chunking invisibility.
3. **Scheduler mechanics**: FIFO admission (page-aware under an
   oversubscribed pool), slot refill at iteration boundaries,
   EOS/length eviction, typed page-accounted admission rejection.
4. **Telemetry**: the SLA summary carries all five latency/throughput
   fields plus the page-pool utilization view; the flight dump
   round-trips through FlightRecorder.load.

Engines compile real XLA programs, so the expensive greedy runs are
module-scoped fixtures shared across the assertion classes.
"""

import json
import time

import jax
import numpy as np
import pytest

from distributed_training_tpu.config import ServeConfig
from distributed_training_tpu.inference import (
    CacheBudgetError,
    Generator,
    SampleConfig,
    cache_budget,
)
from distributed_training_tpu.inference.sampler import check_cache_fits
from distributed_training_tpu.models import get_model
from distributed_training_tpu.serving import (
    FINISH_EOS,
    FINISH_LENGTH,
    FINISH_TIMEOUT,
    DrainingError,
    Engine,
    QueueFullError,
    RequestQueue,
    SlotScheduler,
)

VOCAB = 61
MAX_LEN = 64
N_NEW = 6
# Three distinct lengths only: the Generator oracle and the unpadded
# (bucket-1) engine retrace per prompt length, so variety is capped to
# what buys coverage — one sub-bucket, one at-bucket, one cross-bucket.
PROMPT_LENS = [3, 5, 9, 5, 3, 9]


@pytest.fixture(scope="module")
def lm():
    # head_bias=True so the EOS tests can force an argmax by construction.
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


def _serve(model, params, prompts, **cfg_kw):
    """Run one engine over ``prompts``; returns (engine, {uid: result})."""
    cfg = ServeConfig(**cfg_kw)
    eng = Engine(model, params, cfg)
    for p in prompts:
        eng.submit(p)
    done = eng.run()
    assert len(done) == len(prompts)
    return eng, {f.uid: f for f in done}


@pytest.fixture(scope="module")
def batched_greedy(lm, prompts):
    """6 greedy requests through 2 slots (3× oversubscription) — the
    shared continuous-batching run."""
    model, params = lm
    return _serve(model, params, prompts, max_batch=2,
                  max_new_tokens=N_NEW, temperature=0.0, flush_every=2)


@pytest.fixture(scope="module")
def solo_greedy(lm, prompts):
    """Same requests, one slot, and a 4-token prefill chunk (prompts 5
    and 9 split across iterations): the sequential + differently-chunked
    counterpart of ``batched_greedy``."""
    model, params = lm
    return _serve(model, params, prompts, max_batch=1,
                  max_new_tokens=N_NEW, temperature=0.0, prefill_chunk=4)


class TestOracleEquivalence:
    def test_batched_greedy_matches_sequential_generator(
            self, lm, prompts, batched_greedy):
        """Acceptance: ≥2× more requests than slots; every completion is
        token-identical to the per-prompt sequential Generator."""
        model, params = lm
        _, by_uid = batched_greedy
        gen = Generator(model, params, SampleConfig(
            max_new_tokens=N_NEW, temperature=0.0))
        for uid, p in enumerate(prompts):
            np.testing.assert_array_equal(
                by_uid[uid].tokens, gen(p)[0],
                err_msg=f"request {uid} diverged from sequential decode")

    def test_batched_vs_sequential_engine_bitwise_greedy(
            self, batched_greedy, solo_greedy):
        """A request's tokens must not depend on batch composition OR on
        the prefill chunking: max_batch=2/single-chunk output is bitwise
        equal to max_batch=1/chunk-4 (multi-chunk) output."""
        _, batched = batched_greedy
        _, solo = solo_greedy
        for uid in batched:
            np.testing.assert_array_equal(batched[uid].tokens,
                                          solo[uid].tokens)

    def test_oversubscribed_pool_completes_and_matches(self, lm, prompts,
                                                       batched_greedy):
        """A pool with room for ONE request's worst-case commitment at a
        time (each needs 2 pages of 8; the pool holds 3): page-aware
        admission leaves the second slot EMPTY until pages free, yet
        every request completes with bitwise-identical tokens and the
        allocator drains balanced (no leak, no stranded commitment)."""
        model, params = lm
        eng, by_uid = _serve(model, params, prompts, max_batch=2,
                             max_new_tokens=N_NEW, temperature=0.0,
                             kv_pages=3)
        _, oracle = batched_greedy
        for uid in by_uid:
            np.testing.assert_array_equal(by_uid[uid].tokens,
                                          oracle[uid].tokens)
        eng.pool.check_balanced()
        assert eng.stats()["admission_blocked_s"] > 0

    def test_batched_vs_sequential_engine_bitwise_sampled(self, lm, prompts):
        """Same independence for stochastic sampling: the RNG is a pure
        function of request uid and position, not of slot neighbors."""
        model, params = lm
        subset = prompts[:3]
        _, batched = _serve(model, params, subset, max_batch=3,
                            max_new_tokens=4, temperature=1.0, top_k=10)
        _, solo = _serve(model, params, subset, max_batch=1,
                         max_new_tokens=4, temperature=1.0, top_k=10)
        for uid in batched:
            np.testing.assert_array_equal(batched[uid].tokens,
                                          solo[uid].tokens)


class TestSchedulerMechanics:
    def test_slot_refill_under_oversubscription(self, batched_greedy):
        """2 slots, 6 requests: freed slots refill at iteration
        boundaries, every request completes, the queue high-water mark
        sees the oversubscription."""
        eng, by_uid = batched_greedy
        assert eng.idle
        assert eng.scheduler.num_active == 0
        for f in by_uid.values():
            assert f.finish_reason == FINISH_LENGTH
            assert f.tokens.size == N_NEW
        assert eng.stats()["queue_depth_max"] >= 4

    def test_fifo_fairness_under_full_queue(self, batched_greedy):
        """Absolute first-token times are nondecreasing in arrival order
        for shape-identical co-queued requests (lengths repeat across the
        burst): admission is FIFO, never slot- or recency-biased."""
        _, by_uid = batched_greedy
        times = [by_uid[uid].first_token_t for uid in range(len(by_uid))]
        assert times == sorted(times), f"non-FIFO first tokens: {times}"

    def test_eos_eviction_frees_slot(self, lm):
        """Force EOS as the argmax (biased head): sequences finish with
        reason 'eos', and the freed slot serves the queued request."""
        model, params = lm
        eos = 7
        biased = dict(params)
        head = dict(biased["lm_head"])
        head["bias"] = head["bias"].at[eos].add(1e4)
        biased["lm_head"] = head
        eng = Engine(model, biased, ServeConfig(
            max_batch=1, max_new_tokens=N_NEW, eos_id=eos))
        eng.submit(np.array([1, 2], np.int32))
        eng.submit(np.array([3, 4, 5], np.int32))
        done = eng.run()
        assert len(done) == 2
        for f in done:
            assert f.finish_reason == FINISH_EOS
            assert f.tokens[-1] == eos
            assert f.tokens.size == 1  # EOS is the argmax immediately

    def test_one_token_budget_finishes_at_prefill(self, lm, prompts,
                                                  batched_greedy):
        """max_new_tokens=1 completes without any decode iteration (the
        prefill emits the token) and matches the full run's first token."""
        model, params = lm
        eng = Engine(model, params, ServeConfig(
            max_batch=2, max_new_tokens=1))
        eng.submit(prompts[0])
        done = eng.run()
        assert len(done) == 1 and done[0].tokens.size == 1
        _, by_uid = batched_greedy
        assert done[0].tokens[0] == by_uid[0].tokens[0]

    def test_scheduler_unit(self):
        """SlotScheduler admits FIFO into free slots and reports masks."""
        sched = SlotScheduler(2)
        q = RequestQueue(budget=32, default_max_new_tokens=4)
        for i in range(3):
            q.submit(np.arange(1 + i))
        seated = sched.admit(q)
        assert [s.request.uid for s in seated] == [0, 1]
        assert sched.num_active == 2 and len(q) == 1
        assert sched.active_mask().tolist() == [True, True]
        # Finish slot 0 (budget reached) → evict → refill seats uid 2.
        for _ in range(4):
            sched.sequence(0).note_token(9, t=1.0)
        done = sched.evict_finished(eos_id=None)
        assert [f.uid for f in done] == [0]
        assert sched.active_mask().tolist() == [False, True]
        seated = sched.admit(q)
        assert [s.request.uid for s in seated] == [2]
        assert seated[0].slot == 0  # lowest free slot reused


class TestAdmissionControl:
    def test_cache_budget_helper(self, lm):
        model, _ = lm
        assert cache_budget(model) == MAX_LEN
        assert cache_budget(model, 16) == 16
        assert cache_budget(model, 10 * MAX_LEN) == MAX_LEN  # table caps
        with pytest.raises(ValueError, match="max_len"):
            cache_budget(model, 0)

    def test_check_cache_fits_raises_typed(self, lm):
        model, _ = lm
        with pytest.raises(CacheBudgetError, match="exceeds the KV cache"):
            check_cache_fits(model, MAX_LEN, 1)
        assert issubclass(CacheBudgetError, ValueError)

    def test_oversized_request_rejected_at_submit(self, lm):
        """Admission errors speak page-based accounting now: pages
        needed vs what the table/pool can ever serve one sequence."""
        model, params = lm
        eng = Engine(model, params, ServeConfig(
            max_batch=1, max_new_tokens=2, max_len=16))
        with pytest.raises(CacheBudgetError,
                           match=r"needs 3 KV page\(s\) of 8"):
            eng.submit(np.arange(15, dtype=np.int32))  # 15 + 2 > 16
        eng.submit(np.arange(8, dtype=np.int32))       # 8 + 2 fits
        assert len(eng.run()) == 1
        assert eng.queue.rejected == 1

    def test_empty_prompt_rejected(self, lm):
        model, params = lm
        eng = Engine(model, params, ServeConfig(max_batch=1))
        with pytest.raises(ValueError, match="empty prompt"):
            eng.submit(np.zeros((0,), np.int32))


class TestGracefulDegradation:
    """Resilience round (docs/RESILIENCE.md): drain semantics, bounded
    admission, and per-request deadlines."""

    def test_drain_completes_inflight_and_rejects_new(self, lm, prompts):
        model, params = lm
        eng = Engine(model, params, ServeConfig(
            max_batch=1, max_new_tokens=3))
        for p in prompts[:3]:
            eng.submit(p)
        done = eng.drain()
        # Everything accepted before the close completes (3 requests
        # through 1 slot: queued ones drain too, not just the slot).
        assert len(done) == 3 and eng.idle and eng.draining
        with pytest.raises(DrainingError, match="draining"):
            eng.submit(prompts[0])
        stats = eng.stats()
        assert stats["drained"] is True
        assert stats["requests_drain_rejected"] == 1
        assert stats["requests_finished"] == 3
        # drain() is idempotent: nothing new can arrive, second call is [].
        assert eng.drain() == []

    def test_bounded_queue_sheds_typed(self, lm, prompts):
        model, params = lm
        eng = Engine(model, params, ServeConfig(
            max_batch=1, max_new_tokens=2, max_queue_depth=1))
        eng.submit(prompts[0])  # queued (no iteration has run)
        with pytest.raises(QueueFullError, match="max_depth"):
            eng.submit(prompts[1])
        assert eng.stats()["requests_shed"] == 1
        # The accepted request is unharmed by the shed.
        assert len(eng.run()) == 1

    def test_queue_deadline_evicts_with_timeout(self, lm, prompts,
                                                tmp_path):
        model, params = lm
        eng = Engine(model, params, ServeConfig(
            max_batch=1, max_new_tokens=3, ttft_deadline_ms=50.0))
        # Arrival backdated past the TTFT deadline: the engine must
        # evict it from the queue with reason 'timeout' and zero tokens
        # instead of spending a prefill on a request that already
        # missed its SLA.
        eng.submit(prompts[0], arrival_t=time.perf_counter() - 1.0)
        eng.submit(prompts[1])  # fresh: must be served normally
        done = eng.run()
        by_reason = {f.finish_reason: f for f in done}
        timed_out = by_reason[FINISH_TIMEOUT]
        assert timed_out.tokens.size == 0
        assert timed_out.ttft_ms is None and timed_out.first_token_t is None
        assert by_reason[FINISH_LENGTH].tokens.size == 3
        stats = eng.stats()
        assert stats["requests_timed_out"] == 1
        assert stats["requests_finished"] == 2
        # Timeout telemetry reaches the flight dump as strict JSON.
        path = str(tmp_path / "timeout_flight.json")
        snap = eng.dump_flight(path)
        assert snap["serving"]["requests_timed_out"] == 1
        json.load(open(path))

    def test_slot_deadline_eviction_unit(self):
        """Total-deadline slot eviction, host-side (deterministic): a
        decoding sequence past deadline_t leaves with reason 'timeout'
        and its partial tokens; EOS/length on the same token win."""
        from distributed_training_tpu.serving.request import (
            ActiveSequence,
            Request,
        )

        def seq(deadline_t, tokens, max_new=8):
            req = Request(uid=0, prompt=np.array([1], np.int32),
                          max_new_tokens=max_new, arrival_t=0.0,
                          deadline_t=deadline_t)
            s = ActiveSequence(request=req, slot=0)
            for i, t in enumerate(tokens):
                s.note_token(t, t=float(i))
            return s

        assert seq(5.0, [3, 4]).finish_reason(None, now=4.0) is None
        assert seq(5.0, [3, 4]).finish_reason(None, now=5.0) \
            == FINISH_TIMEOUT
        # Natural completion on the deadline token is NOT a timeout.
        assert seq(5.0, [3, 7]).finish_reason(7, now=6.0) == FINISH_EOS
        assert seq(5.0, [3, 4], max_new=2).finish_reason(None, now=6.0) \
            == FINISH_LENGTH
        # The scheduler frees the slot and returns the partial tokens.
        sched = SlotScheduler(1)
        q = RequestQueue(budget=32, default_max_new_tokens=4,
                         deadline_ms=1.0)
        q.submit(np.array([1, 2], np.int32),
                 arrival_t=time.perf_counter() - 1.0)
        seated = sched.admit(q)
        seated[0].note_token(9, t=time.perf_counter())
        done = sched.evict_finished(None, now=time.perf_counter())
        assert [f.finish_reason for f in done] == [FINISH_TIMEOUT]
        assert done[0].tokens.tolist() == [9]
        assert sched.num_active == 0

    def test_ttft_deadline_evicts_mid_prefill(self, lm, prompts):
        """Chunked prefill opens a seated-but-no-first-token window: a
        request past its TTFT deadline mid-prefill must
        leave with reason 'timeout' — not monopolize the chunk lane for
        its remaining chunks and then pollute the TTFT percentiles with
        a deadline-violating sample."""
        import dataclasses

        from distributed_training_tpu.serving.request import (
            ActiveSequence,
            Request,
        )

        # Host-side semantics first (deterministic): no first token +
        # expired TTFT deadline → timeout; a landed first token wins.
        req = Request(uid=0, prompt=np.arange(8, dtype=np.int32),
                      max_new_tokens=4, arrival_t=0.0,
                      ttft_deadline_t=5.0)
        mid = ActiveSequence(request=req, slot=0, prefill_pos=4)
        assert mid.finish_reason(None, now=4.0) is None
        assert mid.finish_reason(None, now=5.0) == FINISH_TIMEOUT
        got_first = ActiveSequence(request=req, slot=0, prefill_pos=8)
        got_first.note_token(3, t=5.0)
        assert got_first.finish_reason(None, now=6.0) is None

        # Through the engine: seat a multi-chunk prompt (12 tokens,
        # chunk 4), then expire its TTFT deadline after the first chunk
        # — the next iteration must evict it, return its pages, and
        # leave the slot serving fresh traffic.
        model, params = lm
        eng = Engine(model, params, ServeConfig(
            max_batch=1, max_new_tokens=4, prefill_chunk=4,
            ttft_deadline_ms=10_000.0))
        long_prompt = np.arange(12, dtype=np.int32) % VOCAB
        eng.submit(long_prompt)
        assert eng.step() == []  # seated + chunk 1 of 3, no first token
        seq = eng.scheduler._slots[0]
        assert seq.prefilling and not seq.tokens
        seq.request = dataclasses.replace(
            seq.request, ttft_deadline_t=time.perf_counter() - 1e-3)
        done = eng.step()
        assert [f.finish_reason for f in done] == [FINISH_TIMEOUT]
        assert done[0].tokens.size == 0 and done[0].ttft_ms is None
        assert eng.stats()["requests_timed_out"] == 1
        # Pages and commitment fully reclaimed; the slot serves again.
        eng.pool.check_balanced()
        eng.submit(prompts[0])
        fresh = eng.run()
        assert [f.finish_reason for f in fresh] == [FINISH_LENGTH]
        assert fresh[0].tokens.size == 4


class TestTelemetry:
    def test_stats_fields_flight_dump_and_report(self, batched_greedy,
                                                 tmp_path):
        from conftest import load_cli_module

        from distributed_training_tpu.observability import FlightRecorder

        eng, by_uid = batched_greedy
        stats = eng.stats()
        for key in ("throughput_tok_s", "ttft_p50_ms", "ttft_p95_ms",
                    "tpot_p50_ms", "tpot_p95_ms", "queue_depth_max"):
            assert key in stats, key
        assert stats["throughput_tok_s"] > 0
        assert stats["ttft_p95_ms"] >= stats["ttft_p50_ms"] > 0
        assert stats["tokens_emitted"] == len(by_uid) * N_NEW
        for f in by_uid.values():
            assert f.ttft_ms > 0 and f.tpot_ms > 0

        path = str(tmp_path / "serve_flight.json")
        eng.dump_flight(path)
        snap = FlightRecorder.load(path)  # strict-JSON + format round-trip
        assert snap["serving"]["requests_finished"] == len(by_uid)
        assert snap["flushes"], "iteration flushes missing from the ring"

        report = load_cli_module("tools/flight_report.py")
        summary = report.summarize(snap)
        assert summary["serving"]["requests_finished"] == len(by_uid)
        text = report.render(summary)
        assert "serving:" in text and "ttft" in text


class TestUtilizationAccounting:
    """KV/slot utilization accounting (serving/metrics.py) — the
    measured evidence for the paged-KV roadmap claim that ``max_len``
    slot reservation wastes capacity."""

    @staticmethod
    def _paged_expectation(lens, chunk, ps):
        """Per-request analytic reserved/written sums under the paged
        engine: prefill chunk k holds min(k*chunk, L) written tokens on
        ceil(.../ps) pages; decode iteration j (1..N_NEW-1) holds L+j
        tokens on ceil((L+j)/ps) pages. Per-request sums — independent
        of batch composition by construction."""
        res = wr = 0
        for l in lens:
            k = 0
            while k * chunk < l:
                k += 1
                w = min(k * chunk, l)
                wr += w
                res += -(-w // ps) * ps
            for j in range(1, N_NEW):
                wr += l + j
                res += -(-(l + j) // ps) * ps
        return res, wr

    def test_kv_reserved_vs_written_pinned_mixed_lengths(
            self, batched_greedy):
        """Acceptance: with the paged allocator the reservation tracks
        the write head to page granularity — the analytic pin, and the
        ratio stays < 1.5 on the mixed-length workload. Both counters stay
        workload-deterministic (per-request sums over each request's own
        prefill-chunk and decode iterations)."""
        eng, by_uid = batched_greedy
        exp_reserved, exp_written = self._paged_expectation(
            PROMPT_LENS, eng.prefill_chunk, eng.page_size)
        stats = eng.stats()
        assert stats["kv_written_tokens"] == exp_written
        assert stats["kv_reserved_tokens"] == exp_reserved
        assert stats["kv_reserved_vs_written"] == exp_reserved / exp_written
        assert stats["kv_reserved_vs_written"] < 1.5
        # Pool-occupancy accounting: pages allocated per iteration are
        # exactly reserved/page_size (only chunk-active or decoding
        # slots hold pages), so the new gate metric is analytic too.
        assert stats["kv_pages_allocated_iters"] \
            == exp_reserved // eng.page_size
        assert 0.0 < stats["page_pool_occupancy_mean"] <= 1.0

    def test_admission_breakdown_and_occupancy(self, batched_greedy):
        eng, by_uid = batched_greedy
        stats = eng.stats()
        assert 0.0 < stats["slot_occupancy_mean"] <= 1.0
        # Every request got seated and prefilled exactly once.
        assert len(eng.telemetry.queue_wait_ms) == len(PROMPT_LENS)
        assert len(eng.telemetry.prefill_ms) == len(PROMPT_LENS)
        assert stats["prefill_p50_ms"] > 0
        assert stats["queue_wait_p95_ms"] >= stats["queue_wait_p50_ms"] >= 0
        # 6 requests through 2 slots, all submitted up front: the queue
        # head spent time blocked on full slots.
        assert stats["admission_blocked_s"] > 0

    def test_queue_wait_histograms_match_trace_arithmetic(self, lm,
                                                          prompts):
        """The per-request queue-wait/prefill samples are the same
        arithmetic the trace spans carry: arrival→seated and
        seated→first-token, straight off the request records."""
        model, params = lm
        eng, by_uid = _serve(model, params, prompts, max_batch=2,
                             max_new_tokens=2)
        # TTFT decomposes exactly into the two spans: arrival→seated
        # (queue wait) + seated→first-token (prefill compute).
        assert (sum(eng.telemetry.queue_wait_ms)
                + sum(eng.telemetry.prefill_ms)) == pytest.approx(
            sum(eng.telemetry.ttft_ms))
        assert eng.telemetry.queue_wait_hist.total == len(prompts)
        assert eng.telemetry.prefill_hist.total == len(prompts)
        # Histogram sums equal the sample sums (same observations).
        assert eng.telemetry.queue_wait_hist.sum == pytest.approx(
            sum(eng.telemetry.queue_wait_ms))
        assert eng.telemetry.prefill_hist.sum == pytest.approx(
            sum(eng.telemetry.prefill_ms))


class TestServeBenchCli:
    def test_emits_parseable_json_line(self, monkeypatch, capsys):
        """Acceptance: serve_bench on the CPU backend prints one strict-
        JSON line carrying all five latency/throughput fields."""
        from conftest import load_cli_module

        bench = load_cli_module("tools/serve_bench.py")
        monkeypatch.setattr("sys.argv", [
            "serve_bench.py", "--requests", "6", "--rate", "500",
            "--max-batch", "2", "--num-layers", "1", "--num-heads", "2",
            "--hidden-dim", "32", "--model-max-len", "64",
            "--prompt-len", "6", "--max-new-tokens", "4"])
        assert bench.main() == 0
        line = capsys.readouterr().out.strip().splitlines()[-1]
        stats = json.loads(line)
        for key in ("throughput_tok_s", "ttft_p50_ms", "ttft_p95_ms",
                    "tpot_p50_ms", "tpot_p95_ms", "queue_depth_max",
                    # histogram-derived (fixed-bucket) SLO percentiles
                    "ttft_hist_p50_ms", "ttft_hist_p95_ms",
                    "ttft_hist_p99_ms", "tpot_hist_p50_ms",
                    "tpot_hist_p95_ms", "tpot_hist_p99_ms"):
            assert key in stats, key
        assert stats["throughput_tok_s"] > 0
        assert stats["requests_finished"] == 6
        assert stats["ttft_hist_p99_ms"] >= stats["ttft_hist_p50_ms"] > 0


@pytest.mark.parametrize("cli", [
    "gpt/jax_tpu/serve.py", "tools/serve_bench.py", "tools/serve_net.py"])
def test_cli_parser_refuses_page_size_zero(cli, monkeypatch, capsys):
    """``--kv-page-size 0`` selected the contiguous-slot engine: now a
    parser error, before a model or an engine is built."""
    from conftest import load_cli_module

    mod = load_cli_module(cli)
    monkeypatch.setattr("sys.argv", [cli, "--kv-page-size", "0"])
    with pytest.raises(SystemExit) as exit_info:
        mod.main()
    assert exit_info.value.code == 2
    assert "removed in PR 29" in capsys.readouterr().err


@pytest.mark.slow
class TestServeCliSigterm:
    def test_sigterm_drains_and_emits_valid_dump(self, tmp_path):
        """Acceptance: serve.py under SIGTERM completes every in-flight
        request, rejects late ones with the typed DrainingError, and
        still emits the SLA JSON line plus a loadable flight dump."""
        import os
        import signal as signal_mod
        import subprocess
        import sys
        import time as time_mod

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        pfile = tmp_path / "prompts.txt"
        pfile.write_text("".join(f"prompt {i}\n" for i in range(4)))
        dump = tmp_path / "drain_flight.json"
        stderr_path = tmp_path / "serve.stderr"
        env = dict(os.environ)
        env.update(PYTHONPATH=repo, JAX_PLATFORMS="cpu")
        with open(stderr_path, "w") as errfh:
            proc = subprocess.Popen(
                [sys.executable,
                 os.path.join(repo, "gpt", "jax_tpu", "serve.py"),
                 "-c", str(tmp_path / "nockpt"),
                 "--prompts-file", str(pfile),
                 "--num-layers", "1", "--num-heads", "2",
                 "--hidden-dim", "32", "--model-max-len", "128",
                 "--max-new-tokens", "64", "--max-batch", "2",
                 "--json",
                 "--flight-dump", str(dump)],
                stdout=subprocess.PIPE, stderr=errfh, text=True, env=env)
            # SIGTERM only once the guard is installed ("engine ready"):
            # earlier, the default disposition would just kill the
            # process and test nothing.
            deadline = time_mod.time() + 240
            while time_mod.time() < deadline:
                if "engine ready" in open(stderr_path).read():
                    break
                time_mod.sleep(0.2)
                assert proc.poll() is None, open(stderr_path).read()[-2000:]
            else:
                proc.kill()
                raise AssertionError("serve.py never reported ready")
            time_mod.sleep(0.3)
            proc.send_signal(signal_mod.SIGTERM)
            out, _ = proc.communicate(timeout=240)
        assert proc.returncode == 0, open(stderr_path).read()[-2000:]
        stats = json.loads(
            [ln for ln in out.splitlines() if ln.strip()][-1])
        assert stats["drained"] is True
        # Every prompt either completed before the drain or was rejected
        # with the typed error after it — none vanished.
        assert stats["requests_finished"] \
            + stats["requests_drain_rejected"] == 4
        snap = json.load(open(dump))  # strict JSON, serving section intact
        assert snap["serving"]["drained"] is True


class TestServeCli:
    def test_serves_prompt_file_and_prints_stats(self, tmp_path,
                                                 monkeypatch, capsys):
        from conftest import load_cli_module

        pfile = tmp_path / "prompts.txt"
        pfile.write_text("ab\ncdef\n\nxy\n")  # blank line skipped
        serve_cli = load_cli_module("gpt/jax_tpu/serve.py")
        monkeypatch.setattr("sys.argv", [
            "serve.py", "-c", str(tmp_path / "nockpt"),
            "--prompts-file", str(pfile),
            "--num-layers", "1", "--num-heads", "2", "--hidden-dim", "32",
            "--model-max-len", "64", "--max-new-tokens", "4",
            "--max-batch", "2", "--json"])
        assert serve_cli.main() == 0
        out = capsys.readouterr().out
        lines = [ln for ln in out.splitlines() if ln.strip()]
        assert sum(ln.startswith("[serve] #") for ln in lines) == 3
        stats = json.loads(lines[-1])
        assert stats["requests_finished"] == 3
        assert stats["throughput_tok_s"] > 0
