"""Span-level tracing (observability/trace.py) + its report tool.

The trace is a forensic artifact: its value is that a file written by a
crashed run 3 weeks ago still opens in Perfetto and still answers
"what overlapped what". So the tests pin the FORMAT, not just behavior:
every event carries name/ph/ts/pid/tid, per-track timestamps are
monotonic, the file is strict JSON — and trace-derived latencies agree
with the telemetry EXACTLY (same clock, same arithmetic), so the two
observability surfaces can never tell an on-call two different stories.
"""

import collections
import json
import threading
import time

import numpy as np
import pytest

from distributed_training_tpu.observability.trace import (
    TraceSession,
    load_trace,
)

REQUIRED_KEYS = ("name", "ph", "ts", "pid", "tid")


def assert_valid_trace(obj):
    """Every event has the required keys; ts monotonic per (pid, tid)."""
    events = obj["traceEvents"]
    assert events, "empty trace"
    last = collections.defaultdict(lambda: float("-inf"))
    for ev in events:
        for key in REQUIRED_KEYS:
            assert key in ev, (key, ev)
        if ev["ph"] == "M":
            continue
        track = (ev["pid"], ev["tid"])
        assert ev["ts"] >= last[track], (ev, last[track])
        last[track] = ev["ts"]


class TestTraceSession:
    def test_span_instant_counter_round_trip(self, tmp_path):
        tr = TraceSession(pid=3, process_name="host 3 test")
        with tr.span("step", track="train", step=1):
            time.sleep(0.002)
        tr.instant("fault", track="chaos", step=1)
        tr.counter("depth", 4.0)
        path = tr.save(str(tmp_path / "t.json"))
        obj = load_trace(path)  # parses as strict JSON + validates keys
        assert_valid_trace(obj)
        by_ph = collections.Counter(e["ph"] for e in obj["traceEvents"])
        assert by_ph["X"] == 1 and by_ph["i"] == 1 and by_ph["C"] == 1
        span = next(e for e in obj["traceEvents"] if e["ph"] == "X")
        assert span["name"] == "step" and span["dur"] >= 2000  # µs
        assert span["args"]["step"] == 1
        # Track metadata names every lane for the viewer.
        names = {e["args"]["name"] for e in obj["traceEvents"]
                 if e["ph"] == "M" and e["name"] == "thread_name"}
        assert {"train", "chaos", "counters"} <= names

    def test_nested_and_retroactive_spans_sort_monotonic(self, tmp_path):
        tr = TraceSession()
        with tr.span("outer", track="t"):
            with tr.span("inner", track="t"):
                pass
        # A retroactive span (emitted late, starts earliest of all).
        tr.complete("retro", tr.now() - 1.0, tr.now(), track="t")
        obj = load_trace(tr.save(str(tmp_path / "t.json")))
        assert_valid_trace(obj)  # export sorts by ts

    def test_bounded_buffer_drops_and_counts(self, tmp_path):
        tr = TraceSession(max_events=3)
        for i in range(10):
            tr.instant(f"e{i}")
        obj = load_trace(tr.save(str(tmp_path / "t.json")))
        assert sum(1 for e in obj["traceEvents"] if e["ph"] != "M") == 3
        assert obj["otherData"]["dropped_events"] == 7

    def test_thread_safety_smoke(self, tmp_path):
        tr = TraceSession()

        def emit(track):
            for i in range(200):
                tr.instant("e", track=track, i=i)

        threads = [threading.Thread(target=emit, args=(f"t{j}",))
                   for j in range(4)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        obj = load_trace(tr.save(str(tmp_path / "t.json")))
        assert_valid_trace(obj)
        assert sum(1 for e in obj["traceEvents"] if e["ph"] == "i") == 800

    def test_load_trace_rejects_malformed(self, tmp_path):
        bad = tmp_path / "bad.json"
        bad.write_text('{"traceEvents": [{"ph": "X"}]}')
        with pytest.raises(ValueError, match="missing required key"):
            load_trace(str(bad))
        truncated = tmp_path / "torn.json"
        truncated.write_text('{"traceEvents": [')
        with pytest.raises(json.JSONDecodeError):
            load_trace(str(truncated))


class TestWallClockTrace:
    def test_phases_emit_inclusive_spans(self, tmp_path):
        from distributed_training_tpu.utils.profiling import WallClock

        tr = TraceSession()
        clock = WallClock(True, trace=tr)
        with clock.phase("step"):
            with clock.phase("data"):
                time.sleep(0.001)
        obj = load_trace(tr.save(str(tmp_path / "t.json")))
        spans = {e["name"]: e for e in obj["traceEvents"]
                 if e["ph"] == "X"}
        assert set(spans) == {"train.step", "train.data"}
        step, data = spans["train.step"], spans["train.data"]
        # Trace spans are INCLUSIVE (enclosing extent), even though the
        # totals attribute exclusively: step's span contains data's.
        assert step["ts"] <= data["ts"]
        assert step["ts"] + step["dur"] >= data["ts"] + data["dur"]
        # The TOTALS still partition (exclusive attribution unchanged).
        assert clock.lifetime["step"] + clock.lifetime["data"] \
            == pytest.approx(step["dur"] / 1e6, rel=0.2)

    def test_disabled_clock_emits_nothing(self):
        from distributed_training_tpu.utils.profiling import WallClock

        tr = TraceSession()
        clock = WallClock(False, trace=tr)
        with clock.phase("step"):
            pass
        assert len(tr) == 0


@pytest.fixture(scope="module")
def traced_engine():
    """A tiny served workload with tracing on: 4 requests through 2
    slots (oversubscribed, so the slot-refill path traces too)."""
    import jax

    from distributed_training_tpu.config import ServeConfig
    from distributed_training_tpu.models import get_model
    from distributed_training_tpu.serving import Engine

    model = get_model("transformer_lm", num_classes=64, num_layers=1,
                      num_heads=2, hidden_dim=32, max_len=64)
    params = model.init(jax.random.PRNGKey(0),
                        np.zeros((1, 8), np.int32))["params"]
    tr = TraceSession(process_name="serve-test")
    eng = Engine(model, params,
                 ServeConfig(max_batch=2, max_new_tokens=4), trace=tr)
    rng = np.random.RandomState(0)
    for _ in range(4):
        eng.submit(rng.randint(0, 64, size=5).astype(np.int32))
    done = eng.run()
    return eng, tr, done


class TestServingTrace:
    def test_trace_file_valid_and_lifecycle_complete(self, traced_engine,
                                                     tmp_path):
        eng, tr, done = traced_engine
        obj = load_trace(tr.save(str(tmp_path / "serve.json")))
        assert_valid_trace(obj)
        events = obj["traceEvents"]
        names = collections.Counter(
            e["name"] for e in events if e["ph"] != "M")
        # Every request leaves a full lifecycle on its slot track.
        assert names["serve.queued"] == 4
        assert names["serve.prefill"] == 4
        assert names["first_token"] == 4
        assert names["decode"] == 4  # first → last token, per slot
        # The engine track: one span per iteration, its phases inside.
        assert names["serve.iteration"] >= 4
        assert names["serve.device_step"] == names["serve.token_wait"] > 0
        # Chunked prefill (paged engine default): each prompt fits one
        # chunk here, so exactly one chunk span per request rides a
        # slot track — the prefill/decode interleaving view.
        assert names["serve.prefill_chunk"] == 4
        assert names["request.arrival"] == 4
        assert names["finish:length"] == 4
        tracks = {e["args"]["name"] for e in events
                  if e["ph"] == "M" and e["name"] == "thread_name"}
        assert {"queue", "engine", "slot 0", "slot 1"} <= tracks

    def test_span_derived_ttft_equals_telemetry_exactly(self,
                                                        traced_engine,
                                                        tmp_path):
        """The acceptance bar: both surfaces use the one perf_counter
        clock, so (t_first_token - t_arrival)*1e3 from the trace IS the
        telemetry's ttft_ms — bitwise, not approximately."""
        eng, tr, done = traced_engine
        obj = load_trace(tr.save(str(tmp_path / "serve2.json")))
        first = {e["args"]["uid"]: e["args"] for e in obj["traceEvents"]
                 if e["ph"] == "i" and e["name"] == "first_token"}
        assert len(first) == len(done) == 4
        for fin in done:
            derived = (first[fin.uid]["t_first_token"]
                       - first[fin.uid]["t_arrival"]) * 1e3
            assert derived == fin.ttft_ms

    def test_trace_report_summarizes(self, traced_engine, tmp_path,
                                     capsys):
        from conftest import load_cli_module

        eng, tr, done = traced_engine
        path = tr.save(str(tmp_path / "serve3.json"))
        report = load_cli_module("tools/trace_report.py")
        assert report.main([path]) == 0
        out = capsys.readouterr().out
        assert "slot 0" in out and "longest spans" in out
        assert report.main(["--json", path]) == 0
        summary = json.loads(capsys.readouterr().out)
        assert summary["dropped_events"] == 0
        slot_rows = [r for r in summary["tracks"]
                     if r["track"].startswith("slot")]
        assert slot_rows and all(r["spans"] > 0 for r in slot_rows)
        for row in summary["tracks"]:
            if "utilization" in row:
                assert 0.0 <= row["utilization"] <= 1.0

    def test_trace_report_exits_nonzero_on_malformed(self, tmp_path,
                                                     capsys):
        from conftest import load_cli_module

        report = load_cli_module("tools/trace_report.py")
        torn = tmp_path / "torn.json"
        torn.write_text('{"traceEvents": [{"na')
        assert report.main([str(torn)]) == 2
        err = capsys.readouterr().err
        assert err.startswith("trace_report: error:")
        assert "\n" == err[err.index("\n"):]  # exactly one line
        assert report.main([str(tmp_path / "missing.json")]) == 2

    def test_journal_writer_track(self, tmp_path):
        """The round-17 background writer is visible on the timeline:
        per-batch write/fsync spans plus the journal-queue-depth
        counter on a 'journal-writer' track (serving/journal.py gains
        the wiring; empty writer ticks draw nothing)."""
        from distributed_training_tpu.serving import RequestJournal
        from distributed_training_tpu.serving.request import Request

        tr = TraceSession(process_name="journal-test")
        j = RequestJournal(str(tmp_path / "wal"), trace=tr,
                           flush_interval_s=60.0)  # we drive persist()
        j.recover()
        j.log_admit(Request(uid=0,
                            prompt=np.arange(1, 4, dtype=np.int32),
                            max_new_tokens=4,
                            arrival_t=time.perf_counter()))
        j.pause()
        n_after_write = len(tr)
        j.persist()  # empty flush: no span, no counter
        assert len(tr) == n_after_write
        obj = tr.to_json()
        spans = [e for e in obj["traceEvents"]
                 if e.get("name") == "journal.write" and e["ph"] == "X"]
        assert spans and spans[0]["args"]["records"] >= 1
        assert spans[0]["args"]["fsyncs"] >= 1  # fsync='batch' default
        counters = [e for e in obj["traceEvents"]
                    if e.get("name") == "journal_queue_depth"
                    and e["ph"] == "C"]
        assert counters
        track_tids = {e["args"]["name"]: e["tid"]
                      for e in obj["traceEvents"]
                      if e.get("name") == "thread_name"}
        assert "journal-writer" in track_tids
        assert spans[0]["tid"] == track_tids["journal-writer"]
        j.shutdown()


class TestTrainerTrace:
    def test_lm_trainer_traced_run_end_to_end(self, tmp_path):
        """1-epoch tiny LM fit with tracing on: the trace file lands
        (written by obs.close()), validates, and carries the train
        phases, the async ckpt writer's OWN track, and the chaos
        slow-step instant — the cross-component timeline the round is
        for."""
        from distributed_training_tpu.config import (
            ChaosConfig,
            CheckpointConfig,
            DataConfig,
            LMConfig,
            ObservabilityConfig,
            TraceConfig,
            TrainConfig,
        )
        from distributed_training_tpu.train.lm_trainer import LMTrainer

        cfg = TrainConfig(
            model="transformer_lm", num_epochs=1, log_interval=3,
            eval_every=0,
            lm=LMConfig(seq_len=16, num_layers=1, num_heads=2,
                        hidden_dim=32, max_len=32, train_sequences=64,
                        eval_sequences=64),
            data=DataConfig(batch_size=1, max_steps_per_epoch=6),
            checkpoint=CheckpointConfig(
                directory=str(tmp_path / "ckpt"), interval=1),
            observability=ObservabilityConfig(
                trace=TraceConfig(enabled=True)),
            chaos=ChaosConfig(slow_step_every=5, slow_step_ms=60.0))
        trainer = LMTrainer(cfg)
        trainer.fit()
        path = tmp_path / "ckpt" / "flight" / "trace" / "trace.json"
        assert path.exists(), "obs.close() must write the trace"
        obj = load_trace(str(path))
        assert_valid_trace(obj)
        names = collections.Counter(
            e["name"] for e in obj["traceEvents"] if e["ph"] != "M")
        assert names["train.step"] == 6
        assert names["train.dispatch"] == 6
        assert names["ckpt.persist"] == 1  # the writer thread's track
        assert names["chaos.slow_step"] == 1
        tracks = {e["args"]["name"] for e in obj["traceEvents"]
                  if e["ph"] == "M" and e["name"] == "thread_name"}
        assert {"train", "ckpt-writer", "chaos"} <= tracks

    def test_tracing_off_by_default_no_session(self, tmp_path):
        """The zero-overhead surface: at the default config the trainers
        hold trace=None everywhere — no TraceSession exists, no span
        body can run (the transfer-guard test separately pins the flush
        window)."""
        from distributed_training_tpu.config import (
            CheckpointConfig,
            DataConfig,
            LMConfig,
            TrainConfig,
        )
        from distributed_training_tpu.train.lm_trainer import LMTrainer

        cfg = TrainConfig(
            model="transformer_lm", num_epochs=1, eval_every=0,
            lm=LMConfig(seq_len=16, num_layers=1, num_heads=2,
                        hidden_dim=32, max_len=32, train_sequences=32,
                        eval_sequences=32),
            data=DataConfig(batch_size=4, max_steps_per_epoch=1),
            checkpoint=CheckpointConfig(
                directory=str(tmp_path / "ckpt"), interval=0))
        trainer = LMTrainer(cfg)
        assert trainer.trace is None
        assert trainer.clock.trace is None
        assert trainer.obs.trace is None
        trainer.fit()
        assert not (tmp_path / "ckpt" / "flight" / "trace").exists()
