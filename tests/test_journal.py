"""Crash-durable serving: write-ahead request journal + lossless
restart recovery (serving/journal.py, Engine.recover()).

Load-bearing properties, in order of importance:

1. **Lossless crash recovery** (the tentpole): kill the engine with
   requests in flight, restart on the same journal — finished results
   re-deliver from the log exactly once, unfinished requests re-seat
   through the round-16 resume path, and every completed output is
   BITWISE identical to the uninterrupted single-slot oracle (greedy
   and sampled, speculation on and off). Tokens past
   the last durable flush are recomputed by the same
   ``fold_in(rng, position)`` induction, not lost.
2. **Durable-format robustness**: length-prefixed crc-framed records;
   a torn tail (truncation, bit flip, garbage append) truncates at the
   last good record and quarantines the severed bytes — never a crash;
   segment rotation compacts finished-and-acked requests so the
   journal's footprint tracks in-flight state, not history.
3. **Replay idempotence + the client cursor**: recovering twice yields
   the same state; redelivery repeats until the CLIENT acks (a
   recovery attempt that died before its consumer took delivery loses
   nothing), and after the ack nothing redelivers again.
4. **Deadlines survive restart**: arrival/first-token clocks are
   wall-anchored in the journal, so downtime keeps billing — a request
   whose deadline expired while the engine was dead completes
   ``timeout`` (``preempted_timeout`` if the journal shows a
   preemption) at replay instead of resurrecting.

Engines compile real XLA programs, so the model is tiny and the
crash-matrix is trimmed to cover every axis value rather than the full
product (the CI crash-recovery drill exercises the real ``kill -9``
path through serve_bench subprocesses).
"""

import dataclasses
import json
import os
import struct
import time
import zlib

import jax
import numpy as np
import pytest

from distributed_training_tpu.config import ServeConfig
from distributed_training_tpu.models import get_model
from distributed_training_tpu.serving import (
    FINISH_EOS,
    FINISH_LENGTH,
    FINISH_PREEMPT_TIMEOUT,
    FINISH_TIMEOUT,
    ActiveSequence,
    Engine,
    FinishedRequest,
    JournalCorruptError,
    Request,
    RequestJournal,
)

VOCAB = 31
MAX_LEN = 48
# The ServeConfig-default RNG/sampling/weights fingerprint (what an
# Engine with default sampling and no checkpoint writes); unit tests
# that hand-craft journals reuse it so a real engine can recover them.
DEFAULT_FP = {"seed": 0, "temperature": 0.0, "top_k": None,
              "top_p": None, "eos_id": None, "pad_id": 0,
              "quantize_weights": False, "kv_dtype": None,
              "weights_epoch": -1}


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
    slot (uid parity with the crash run is what the bitwise comparison
    requires — the RNG stream is fold_in(seed, uid))."""
    eng = Engine(model, params, ServeConfig(max_batch=1, **cfg_kw))
    out = {}
    for prompt, max_new in reqs:
        req = eng.submit(prompt, max_new_tokens=max_new)
        for fin in eng.run():
            out[fin.uid] = fin.tokens.tolist()
        assert req.uid in out
    return out


def _mk_req(uid, prompt_len=4, mnt=8, arrival_t=None, **kw):
    return Request(
        uid=uid, prompt=np.arange(1, prompt_len + 1, dtype=np.int32),
        max_new_tokens=mnt,
        arrival_t=time.perf_counter() if arrival_t is None else arrival_t,
        **kw)


def _frames(path):
    """(offset, payload) per well-formed record in a segment file."""
    data = open(path, "rb").read()
    out, off = [], 0
    while off + 8 <= len(data):
        ln, crc = struct.unpack_from("<II", data, off)
        payload = data[off + 8:off + 8 + ln]
        if len(payload) < ln or zlib.crc32(payload) != crc:
            break
        out.append((off, payload))
        off += 8 + ln
    return out


def _segment(path):
    segs = [os.path.join(path, n) for n in sorted(os.listdir(path))
            if n.startswith("wal-") and n.endswith(".log")]
    assert len(segs) == 1, segs
    return segs[0]


class TestJournalUnit:
    def _journal(self, d, **kw):
        kw.setdefault("fingerprint", DEFAULT_FP)
        j = RequestJournal(str(d), **kw)
        j.recover()
        return j

    def test_roundtrip_and_ack_drop(self, tmp_path):
        j = self._journal(tmp_path)
        a, b = _mk_req(0, priority=0), _mk_req(1, prompt_len=3)
        j.log_admit(a)
        j.log_admit(b)
        seq = ActiveSequence(request=a, slot=0)
        for i, tok in enumerate((7, 8, 9)):
            seq.note_token(tok, time.perf_counter())
        j.note_tokens(seq)
        fin = FinishedRequest.from_active(seq, FINISH_LENGTH)
        j.note_finish(fin)
        j.ack(0)
        j.shutdown()

        j2 = self._journal(tmp_path)
        state = j2.recover()
        # 0 finished AND acked -> dropped entirely; 1 still pending.
        assert sorted(state.requests) == [1]
        assert state.max_uid == 1  # acked uids never get reused
        e = state.requests[1]
        assert e.prompt == [1, 2, 3]
        assert e.tokens == [] and not e.finished
        j2.shutdown()

    def test_token_batches_are_idempotent_by_base(self, tmp_path):
        j = self._journal(tmp_path)
        req = _mk_req(0)
        j.log_admit(req)
        seq = ActiveSequence(request=req, slot=0)
        seq.note_token(4, time.perf_counter())
        j.note_tokens(seq)
        seq.note_token(5, time.perf_counter())
        seq.note_token(6, time.perf_counter())
        j.note_tokens(seq)
        j.note_tokens(seq)  # no-op: nothing new
        j.shutdown()
        state = self._journal(tmp_path).recover()
        assert state.requests[0].tokens == [4, 5, 6]

    def test_unrecovered_append_raises_typed(self, tmp_path):
        j = RequestJournal(str(tmp_path), fingerprint=DEFAULT_FP)
        with pytest.raises(JournalCorruptError) as ei:
            j.log_admit(_mk_req(0))
        assert ei.value.reason == "unrecovered"

    def test_shutdown_refuses_appends(self, tmp_path):
        """An append after shutdown() must refuse loudly — a silently
        pending-forever admission would break 'accepted ⇒ durable'."""
        j = self._journal(tmp_path)
        j.shutdown()
        with pytest.raises(JournalCorruptError) as ei:
            j.log_admit(_mk_req(0))
        assert ei.value.reason == "closed"

    def test_weights_epoch_tail_fingerprint(self, tmp_path):
        """The LAST cfg record wins: a hot-swap journals its new
        weights_epoch, and a restart serving different weights than the
        journal's tail is refused typed (recomputing 'lost' tokens
        under the wrong model would silently break the bitwise
        contract); a restart on the swapped weights recovers."""
        j = self._journal(tmp_path)
        j.log_admit(_mk_req(0))
        j.update_fingerprint(weights_epoch=2)  # a hot-swap landed
        j.shutdown()
        j2 = RequestJournal(str(tmp_path), fingerprint=DEFAULT_FP)
        with pytest.raises(JournalCorruptError) as ei:
            j2.recover()
        assert ei.value.reason == "fingerprint"
        j3 = RequestJournal(
            str(tmp_path),
            fingerprint={**DEFAULT_FP, "weights_epoch": 2})
        state = j3.recover()
        assert sorted(state.requests) == [0]
        j3.shutdown()

    def test_fingerprint_mismatch_refuses_replay(self, tmp_path):
        j = self._journal(tmp_path)
        j.log_admit(_mk_req(0))
        j.shutdown()
        j2 = RequestJournal(str(tmp_path),
                            fingerprint={**DEFAULT_FP, "seed": 1})
        with pytest.raises(JournalCorruptError) as ei:
            j2.recover()
        assert ei.value.reason == "fingerprint"

    def test_torn_tail_truncated_and_quarantined(self, tmp_path):
        j = self._journal(tmp_path)
        for uid in range(3):
            j.log_admit(_mk_req(uid))
        j.shutdown()
        seg = _segment(tmp_path)
        with open(seg, "ab") as fh:
            fh.write(b"\xff" * 37)  # a crash mid-append
        j2 = RequestJournal(str(tmp_path), fingerprint=DEFAULT_FP)
        state = j2.recover()
        j2.shutdown()
        assert sorted(state.requests) == [0, 1, 2]
        assert state.torn_bytes == 37
        corrupt = [n for n in os.listdir(tmp_path) if ".corrupt" in n]
        assert len(corrupt) == 1
        # The quarantine holds the severed bytes; the next recovery is
        # clean (the tail was truncated at the last good record and the
        # survivors compacted forward).
        state2 = self._journal(tmp_path).recover()
        assert state2.torn_bytes == 0
        assert sorted(state2.requests) == [0, 1, 2]

    def test_crc_flip_kills_only_the_tail(self, tmp_path):
        j = self._journal(tmp_path)
        for uid in range(3):
            j.log_admit(_mk_req(uid))
        j.shutdown()
        seg = _segment(tmp_path)
        frames = _frames(seg)
        last_off, last_payload = frames[-1]
        assert b'"u":2' in last_payload
        with open(seg, "r+b") as fh:
            fh.seek(last_off + 8)  # first payload byte of last record
            byte = fh.read(1)
            fh.seek(last_off + 8)
            fh.write(bytes([byte[0] ^ 0xFF]))
        j2 = RequestJournal(str(tmp_path), fingerprint=DEFAULT_FP)
        state = j2.recover()
        j2.shutdown()
        assert sorted(state.requests) == [0, 1]  # the flipped admit died
        assert state.torn_bytes > 0

    def test_truncation_mid_record(self, tmp_path):
        j = self._journal(tmp_path)
        for uid in range(3):
            j.log_admit(_mk_req(uid))
        j.shutdown()
        seg = _segment(tmp_path)
        with open(seg, "r+b") as fh:
            fh.seek(0, 2)
            fh.truncate(fh.tell() - 3)
        state = self._journal(tmp_path).recover()
        assert sorted(state.requests) == [0, 1]

    def test_rotation_bounds_journal_size(self, tmp_path):
        """Satellite: a preempt-storm-shaped churn (admit, tokens,
        preempt, re-tokens, finish, ack per request) must stay under a
        pinned size bound — finished-and-acked requests compact away,
        so the footprint tracks in-flight state, not history."""
        seg_bytes = 4096
        j = self._journal(tmp_path, segment_bytes=seg_bytes,
                          fsync="none")
        t = time.perf_counter()
        for uid in range(300):
            req = _mk_req(uid)
            j.log_admit(req)
            seq = ActiveSequence(request=req, slot=0)
            for tok in range(4):
                seq.note_token(tok, t)
            j.note_tokens(seq)
            j.note_preempt(seq)
            for tok in range(4, 8):
                seq.note_token(tok, t)
            j.note_tokens(seq)
            j.note_finish(FinishedRequest.from_active(seq, FINISH_LENGTH))
            j.ack(uid)
        # One unfinished straggler must SURVIVE every compaction.
        j.log_admit(_mk_req(300, prompt_len=6))
        j.persist()
        j.shutdown()
        total = sum(os.path.getsize(os.path.join(tmp_path, n))
                    for n in os.listdir(tmp_path))
        assert j.segments_rotated > 0
        assert total < 4 * seg_bytes, total
        state = self._journal(tmp_path).recover()
        assert sorted(state.requests) == [300]
        assert state.max_uid == 300

    def test_write_fault_retains_and_retries_batch(self, tmp_path):
        """A transient disk fault must lose NOTHING and must not end
        durability: the failed batch returns to the queue head and the
        next persist lands it (replay idempotence absorbs any
        half-written prefix)."""
        j = self._journal(tmp_path)
        j.pause()  # deterministic: we drive persist() by hand
        j.log_note({"cursor": 7}, flush=False)
        seg_fd, j._fd = j._fd, None
        os.close(seg_fd)
        seg = _segment(tmp_path)
        j._fd = os.open(os.devnull, os.O_WRONLY)
        os.close(j._fd)  # a dead fd: the next write raises EBADF
        with pytest.raises(OSError):
            j.persist()
        assert j.write_errors == 1
        j._fd = os.open(seg, os.O_WRONLY | os.O_APPEND)
        j.persist()  # the retried batch lands
        j.shutdown()
        state = self._journal(tmp_path).recover()
        assert state.notes.get("cursor") == 7

    def test_double_recovery_is_idempotent(self, tmp_path):
        j = self._journal(tmp_path)
        req = _mk_req(0)
        j.log_admit(req)
        seq = ActiveSequence(request=req, slot=0)
        seq.note_token(9, time.perf_counter())
        j.note_tokens(seq)
        j.note_preempt(seq)
        j.shutdown()
        a = self._journal(tmp_path).recover()
        b = self._journal(tmp_path).recover()
        assert sorted(a.requests) == sorted(b.requests) == [0]
        for s in (a, b):
            e = s.requests[0]
            assert e.tokens == [9] and e.preempts == 1

    def test_notes_last_write_wins_and_survive_compaction(self, tmp_path):
        j = self._journal(tmp_path, segment_bytes=4096, fsync="none")
        for i in range(200):
            j.log_note({"submitted": i + 1})
        j.shutdown()
        state = self._journal(tmp_path).recover()
        assert state.notes == {"submitted": 200}

    def test_deadline_offsets_roundtrip(self, tmp_path):
        j = self._journal(tmp_path)
        now = time.perf_counter()
        j.log_admit(_mk_req(0, arrival_t=now, ttft_deadline_t=now + 1.5,
                            deadline_t=now + 30.0))
        j.shutdown()
        e = self._journal(tmp_path).recover().requests[0]
        assert e.ttft_rel_s == pytest.approx(1.5)
        assert e.deadline_rel_s == pytest.approx(30.0)


# Spec 0/2 across greedy and sampled, without the full product: the
# resume path they share is tier-1-pinned by test_preemption, and these
# + the CI crash drill carry the per-push recovery claim.
CRASH_CASES = [
    ({"prefill_chunk": 4}, 0.0),
    ({"prefill_chunk": 4, "spec_k": 2}, 0.8),
]


class TestCrashRecovery:
    @pytest.mark.parametrize("cfg_kw,temp", CRASH_CASES)
    def test_crash_resume_bitwise(self, lm, prompts, tmp_path, cfg_kw,
                                  temp):
        """THE invariant: kill an engine with requests in flight — one
        past its last durable flush — restart on the journal, and every
        output (redelivered + recomputed) equals the uninterrupted
        single-slot oracle bitwise."""
        model, params = lm
        cfg = ServeConfig(max_batch=2, max_new_tokens=8,
                          temperature=temp, journal_dir=str(tmp_path),
                          **cfg_kw)
        eng = Engine(model, params, cfg)
        eng.recover()
        uids = [eng.submit(p, max_new_tokens=8).uid
                for p in prompts[:3]]
        done = {}
        for _ in range(6):
            for f in eng.step():
                done[f.uid] = f.tokens.tolist()
        # Everything so far is durable; the NEXT iterations' tokens
        # (and possibly a finish) are enqueued but never persisted —
        # the tail a kill -9 loses and recovery must recompute.
        eng.journal.pause()
        for _ in range(3):
            for f in eng.step():
                done[f.uid] = f.tokens.tolist()
        eng.journal.crash()

        eng2 = Engine(model, params, cfg)
        rep = eng2.recover()
        out = {f.uid: f.tokens.tolist()
               for f in rep["redelivered"] + rep["completed_at_replay"]}
        for f in eng2.drain():
            out[f.uid] = f.tokens.tolist()
        eng2.pool.check_balanced()
        solo = _solo_outputs(model, params, [(p, 8) for p in prompts[:3]],
                             temperature=temp, **cfg_kw)
        assert sorted(out) == uids
        for uid in uids:
            assert out[uid] == solo[uid], uid
        stats = eng2.stats()
        assert stats["requests_recovered"] == 3
        assert stats["tokens_recomputed_on_recovery"] > 0
        assert stats["journal_records_written"] > 0
        eng2.journal.shutdown()

    def test_crash_while_preempted_recovers_with_attribution(
            self, lm, prompts, tmp_path):
        """A crash while a preempted sequence sits requeued: recovery
        rebuilds the resumption (emitted tokens + preempt count) and
        the continued outputs stay bitwise; the preemption attribution
        survives the restart."""
        model, params = lm
        cfg = ServeConfig(max_batch=1, max_new_tokens=8, num_tiers=2,
                          prefill_chunk=4, journal_dir=str(tmp_path))
        eng = Engine(model, params, cfg)
        eng.recover()
        low = eng.submit(prompts[0], priority=1, max_new_tokens=8)
        for _ in range(3):
            eng.step()
        assert len(eng.scheduler.sequence(0).tokens) >= 1
        high = eng.submit(prompts[1], priority=0, max_new_tokens=4)
        eng.step()  # lands the step in flight: the preemption waits
        eng.step()  # the preemption pass: low requeues mid-flight
        assert eng.stats()["requests_preempted"] == 1
        eng.journal.persist()
        eng.journal.crash()

        eng2 = Engine(model, params, cfg)
        rep = eng2.recover()
        assert rep["resumed"] == 2
        # The requeued victim restores into its tier as a resumption
        # carrying its emitted tokens AND its preempt count (the
        # high-tier head, mid-prefill at the crash, restores fresh).
        entry = eng2.queue._tiers[1][0]
        assert isinstance(entry, ActiveSequence)
        assert entry.request.uid == low.uid and entry.preempts == 1
        out = {f.uid: f.tokens.tolist() for f in eng2.drain()}
        eng2.pool.check_balanced()
        solo = _solo_outputs(model, params,
                             [(prompts[0], 8), (prompts[1], 4)],
                             prefill_chunk=4)
        assert out[low.uid] == solo[low.uid]
        assert out[high.uid] == solo[high.uid]
        eng2.journal.shutdown()

    def test_redelivery_repeats_until_acked_then_stops(
            self, lm, prompts, tmp_path):
        """The client cursor (replay idempotence): a finished result
        redelivers on EVERY recovery until the consumer acks — a
        recovery attempt that died before its consumer took delivery
        loses nothing — and after the ack it never redelivers again.
        Double replay of the same journal is a state no-op throughout."""
        model, params = lm
        cfg = ServeConfig(max_batch=2, max_new_tokens=6,
                          prefill_chunk=4, journal_dir=str(tmp_path))
        eng = Engine(model, params, cfg)
        eng.recover()
        for p in prompts[:2]:
            eng.submit(p, max_new_tokens=6)
        finished = {f.uid: f.tokens.tolist() for f in eng.run()}
        assert len(finished) == 2
        eng.journal.crash()  # finishes durable (writer ran), no acks

        def recover_once(ack):
            e = Engine(model, params, cfg)
            rep = e.recover()
            assert rep["resumed"] == 0 and not rep["completed_at_replay"]
            redelivered = {f.uid: f.tokens.tolist()
                           for f in rep["redelivered"]}
            if ack:
                e.journal.ack(list(redelivered))
            e.journal.shutdown()
            return redelivered

        # Two un-acked recoveries redeliver identically (kill -9 mid
        # replay converges); the acked one is final.
        assert recover_once(ack=False) == finished
        assert recover_once(ack=True) == finished
        assert recover_once(ack=False) == {}

    def test_finish_condition_met_in_journal_completes_at_replay(
            self, lm, tmp_path):
        """Crash between the last emit and the finish record's flush:
        the journaled stream already satisfies EOS/budget, so replay
        completes the request with the right reason instead of
        re-seating a sequence that has nothing left to decode."""
        model, params = lm
        j = RequestJournal(str(tmp_path), fingerprint=DEFAULT_FP)
        j.recover()
        t = time.perf_counter()
        length = _mk_req(0, mnt=3)
        j.log_admit(length)
        seq = ActiveSequence(request=length, slot=0)
        for tok in (4, 5, 6):  # budget reached, finish never flushed
            seq.note_token(tok, t)
        j.note_tokens(seq)
        eos_req = _mk_req(1, mnt=8)
        j.log_admit(eos_req)
        seq2 = ActiveSequence(request=eos_req, slot=0)
        seq2.note_token(2, t)  # == eos_id below
        j.note_tokens(seq2)
        j.shutdown()

        eng = Engine(model, params, ServeConfig(
            max_batch=1, eos_id=2, journal_dir=str(tmp_path)))
        with pytest.raises(JournalCorruptError):
            eng.recover()  # eos_id changes the fingerprint: refused
        eng = Engine(model, params, ServeConfig(
            max_batch=1, journal_dir=str(tmp_path)))
        rep = eng.recover()
        reasons = {f.uid: f.finish_reason
                   for f in rep["completed_at_replay"]}
        assert reasons[0] == FINISH_LENGTH
        assert rep["resumed"] == 1  # no eos configured: 1 keeps going
        done = {f.uid: f for f in eng.drain()}
        assert done[1].tokens.size == 8
        eng.journal.shutdown()
        # Same journal under an engine whose fingerprint MATCHES an
        # eos config: hand-craft the eos fingerprint to prove the eos
        # branch too.
        j3 = RequestJournal(str(tmp_path / "eos"),
                            fingerprint={**DEFAULT_FP, "eos_id": 2})
        j3.recover()
        j3.log_admit(eos_req)
        j3.note_tokens(seq2)
        j3.shutdown()
        eng3 = Engine(model, params, ServeConfig(
            max_batch=1, eos_id=2, journal_dir=str(tmp_path / "eos")))
        rep3 = eng3.recover()
        assert [f.finish_reason for f in rep3["completed_at_replay"]] \
            == [FINISH_EOS]
        eng3.journal.shutdown()

    def test_deadline_expired_during_downtime(self, lm, tmp_path):
        """Satellite: deadline clocks keep running across downtime. A
        request whose total deadline passed while the engine was dead
        completes ``timeout`` at replay — ``preempted_timeout`` when
        the journal shows a preemption (partial tokens kept) — and one
        whose deadline still has slack resumes with the remaining
        budget mapped into the new process's clock."""
        model, params = lm
        j = RequestJournal(str(tmp_path), fingerprint=DEFAULT_FP)
        j.recover()
        t = time.perf_counter()
        # "Admitted 10 s ago", 1 s total deadline, preempted after one
        # token: expired 9 s of downtime ago.
        preempted = _mk_req(0, arrival_t=t - 10.0, deadline_t=t - 9.0)
        j.log_admit(preempted)
        seq = ActiveSequence(request=preempted, slot=0)
        seq.note_token(5, t - 9.5)
        j.note_tokens(seq)
        j.note_preempt(seq)
        # Fresh request past its TTFT deadline, never served.
        fresh = _mk_req(1, arrival_t=t - 10.0, ttft_deadline_t=t - 9.0)
        j.log_admit(fresh)
        # Still-live request: 1 h of total deadline left.
        alive = _mk_req(2, arrival_t=t - 10.0, deadline_t=t + 3600.0)
        j.log_admit(alive)
        j.shutdown()

        eng = Engine(model, params, ServeConfig(
            max_batch=1, journal_dir=str(tmp_path)))
        rep = eng.recover()
        fins = {f.uid: f for f in rep["completed_at_replay"]}
        assert fins[0].finish_reason == FINISH_PREEMPT_TIMEOUT
        assert fins[0].tokens.tolist() == [5]  # partial tokens kept
        assert fins[1].finish_reason == FINISH_TIMEOUT
        assert fins[1].tokens.size == 0
        assert rep["resumed"] == 1
        entry = eng.queue.peek()
        remaining = entry.deadline_t - time.perf_counter()
        assert 3500.0 < remaining < 3600.0  # 10 s of downtime billed
        stats = eng.stats()
        assert stats["requests_recovered"] == 3
        assert stats["requests_preempt_timed_out"] == 1
        assert stats["requests_timed_out"] == 1
        eng.journal.shutdown()

    def test_submit_withdraws_when_journal_append_fails(self, lm,
                                                        prompts,
                                                        tmp_path):
        """Acceptance is journal-backed: when the durable admission
        record cannot be written, submit() must raise AND leave the
        queue empty — an accepted-but-unjournaled request would decode
        anyway and duplicate the caller's retry."""
        model, params = lm
        eng = Engine(model, params, ServeConfig(
            max_batch=1, journal_dir=str(tmp_path)))
        eng.recover()
        eng.journal.shutdown()  # appends now refuse typed
        with pytest.raises(JournalCorruptError):
            eng.submit(prompts[2], max_new_tokens=8)
        assert len(eng.queue) == 0 and eng.idle

    def test_phase_counters_and_reset_preservation(self, lm, prompts,
                                                   tmp_path,
                                                   monkeypatch):
        """/healthz evidence: phase reads 'recovering' during replay,
        health() carries the journal counters, and reset_stats (the
        bench warm-up reset) preserves the recovery evidence."""
        model, params = lm
        cfg = ServeConfig(max_batch=1, max_new_tokens=4,
                          prefill_chunk=4, journal_dir=str(tmp_path))
        eng = Engine(model, params, cfg)
        eng.recover()
        eng.submit(prompts[2], max_new_tokens=4)
        eng.step()
        eng.journal.persist()
        eng.journal.crash()

        eng2 = Engine(model, params, cfg)
        seen = {}
        orig = eng2.journal.recover

        def spy():
            seen["phase"] = eng2.phase
            return orig()

        monkeypatch.setattr(eng2.journal, "recover", spy)
        assert eng2.phase != "recovering"
        eng2.recover()
        assert seen["phase"] == "recovering"
        assert eng2.phase != "recovering"
        health = eng2.health()
        for key in ("requests_recovered", "journal_records_written",
                    "journal_fsyncs"):
            assert key in health, key
        assert health["requests_recovered"] == 1
        eng2.reset_stats()
        assert eng2.stats()["requests_recovered"] == 1
        eng2.journal.shutdown()


class TestServeBenchJournalCli:
    def test_journal_run_then_idempotent_restart(self, monkeypatch,
                                                 capsys, tmp_path):
        """serve_bench with --journal-dir: the SLA line carries the
        journal keys with zero recovery on a clean run; restarting on
        the same journal after a clean (fully acked) run recovers
        nothing, submits nothing (the submission cursor says the
        scenario is done), and delivers nothing twice."""
        from conftest import load_cli_module

        bench = load_cli_module("tools/serve_bench.py")
        jd = str(tmp_path / "j")
        comp = str(tmp_path / "completions.json")
        argv = ["serve_bench.py", "--requests", "6", "--rate", "400",
                "--max-batch", "2", "--num-layers", "1",
                "--num-heads", "2", "--hidden-dim", "32",
                "--model-max-len", "64", "--prompt-len", "8",
                "--max-new-tokens", "8", "--prefill-chunk", "8",
                "--virtual-dt", "2", "--journal-dir", jd,
                "--completions-out", comp]
        monkeypatch.setattr("sys.argv", argv)
        assert bench.main() == 0
        stats = json.loads(
            capsys.readouterr().out.strip().splitlines()[-1])
        assert stats["requests_finished"] == 6
        assert stats["requests_recovered"] == 0
        assert stats["tokens_recomputed_on_recovery"] == 0
        assert stats["journal_records_written"] > 0
        first = {c["uid"]: c for c in json.load(open(comp))}
        assert len(first) == 6

        monkeypatch.setattr("sys.argv", argv)
        assert bench.main() == 0
        stats2 = json.loads(
            capsys.readouterr().out.strip().splitlines()[-1])
        assert stats2["requests_finished"] == 0
        assert stats2["requests_recovered"] == 0
        assert json.load(open(comp)) == []
