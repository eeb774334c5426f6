"""Cross-host straggler attribution + fixed-bucket SLO histograms.

The headline claim (ISSUE round 10): with the round-9 chaos slow-step
injector stalling a KNOWN (host, step), the flight dump's aggregated
``hosts`` section attributes exactly that host and step. The fast tests
pin the pure aggregation math and the single-process trainer round trip;
the 2-process drill (slow) runs the real injector on a real multi-process
CPU mesh through the real all-gather, twice, and asserts the attribution
is identical both times.
"""

import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from distributed_training_tpu.observability import aggregate as agg
from distributed_training_tpu.observability.flight_recorder import (
    FlightRecorder,
)
from distributed_training_tpu.observability.histogram import FixedHistogram

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class TestFixedHistogram:
    def test_observe_quantile_interpolates(self):
        h = FixedHistogram(bounds=(10.0, 20.0, 40.0))
        for v in (5.0, 15.0, 15.0, 30.0):
            h.observe(v)
        assert h.total == 4 and h.sum == 65.0
        assert h.counts == [1, 2, 1, 0]
        assert h.cumulative() == [1, 3, 4, 4]
        # Median rank lands mid-bucket (10, 20]: linear interpolation.
        assert 10.0 < h.quantile(0.5) <= 20.0
        assert h.quantile(1.0) == 40.0
        assert h.quantile(0.0) == 0.0 or h.quantile(0.0) <= 10.0

    def test_overflow_and_negative_clamp(self):
        h = FixedHistogram(bounds=(1.0, 2.0))
        h.observe(100.0)   # +Inf bucket
        h.observe(-5.0)    # clamps into the first bucket
        assert h.counts == [1, 0, 1]
        assert h.quantile(0.99) == 2.0  # +Inf reports the last bound

    def test_empty_histogram_quantiles_are_zero_no_div(self):
        """Audit pin: an empty histogram's quantile must be 0.0 at every
        q — not a ZeroDivisionError from the rank/count interpolation."""
        h = FixedHistogram(bounds=(1.0, 2.0))
        for q in (0.0, 0.5, 0.99, 1.0):
            assert h.quantile(q) == 0.0
        with pytest.raises(ValueError, match="must be in"):
            h.quantile(1.5)
        with pytest.raises(ValueError, match="must be in"):
            h.quantile(-0.1)

    def test_all_mass_in_inf_bucket_clamps_to_last_bound(self):
        """Audit pin: quantiles landing in the +Inf bucket clamp to the
        last FINITE bound (there is no upper edge to interpolate
        toward) — at every q, not just the tail."""
        h = FixedHistogram(bounds=(1.0, 2.0, 4.0))
        for _ in range(5):
            h.observe(1e9)
        assert h.counts == [0, 0, 0, 5]
        for q in (0.01, 0.25, 0.5, 0.99, 1.0):
            assert h.quantile(q) == 4.0

    def test_bucket_boundary_interpolation_exact(self):
        """Audit pin: interpolation endpoints at bucket boundaries —
        rank == bucket's full cumulative mass gives the bucket's UPPER
        edge, half the mass gives the midpoint, and the first bucket
        interpolates up from 0 (latencies have no negative edge)."""
        h = FixedHistogram(bounds=(1.0, 2.0))
        for _ in range(4):
            h.observe(1.5)  # all mass in bucket (1, 2]
        assert h.quantile(1.0) == 2.0
        assert h.quantile(0.5) == 1.5
        assert h.quantile(0.25) == 1.25
        first = FixedHistogram(bounds=(10.0,))
        first.observe(5.0)
        assert first.quantile(0.5) == 5.0  # 0 → 10 edge, half rank
        assert first.quantile(1.0) == 10.0

    def test_quantile_skips_empty_leading_buckets(self):
        """Audit pin: a tiny q with empty leading buckets lands at the
        first OCCUPIED bucket's lower edge — interpolation never places
        mass in a zero-count bucket."""
        h = FixedHistogram(bounds=(1.0, 2.0, 4.0, 8.0))
        h.observe(3.0)  # only bucket (2, 4] occupied
        assert h.quantile(0.0) == 2.0
        assert h.quantile(0.001) > 2.0
        assert h.quantile(1.0) == 4.0

    def test_merge_and_round_trip(self):
        a, b = FixedHistogram(), FixedHistogram()
        for v in (3.0, 30.0):
            a.observe(v)
        b.observe(300.0)
        a.merge(b)
        assert a.total == 3 and a.sum == 333.0
        c = FixedHistogram.from_dict(json.loads(json.dumps(a.to_dict())))
        assert c.counts == a.counts and c.sum == a.sum
        with pytest.raises(ValueError, match="different bounds"):
            a.merge(FixedHistogram(bounds=(1.0, 2.0)))

    def test_recorder_feeds_step_histogram_gap_excluded(self):
        rec = FlightRecorder(8)
        t = 0.0
        for i in range(1, 5):
            rec.record_step(i, t)
            t += 0.010
        rec.mark_gap()          # epoch boundary pause...
        rec.record_step(5, t + 5.0)  # ...must NOT become a 5s sample
        assert rec.step_hist.total == 3
        assert rec.step_hist.sum == pytest.approx(30.0)
        snap = rec.snapshot()
        assert snap["histograms"]["step_time_ms"]["count"] == 3


def _recorder(deltas_ms, t0=0.0):
    rec = FlightRecorder(max(len(deltas_ms) + 2, 4))
    t = t0
    rec.record_step(1, t)
    for i, dt in enumerate(deltas_ms, start=2):
        t += dt / 1e3
        rec.record_step(i, t)
    return rec


class TestAggregation:
    def test_four_host_skew_attributes_injected_cell(self):
        """Synthetic 4-host gather: host 2 stalls at step 7; everything
        else is uniform 10 ms. The summary must name (2, 7)."""
        payloads = []
        for h in range(4):
            deltas = [10.0] * 9
            if h == 2:
                deltas[5] = 250.0  # step 7 (deltas start at step 2)
            payloads.append(agg.local_payload(_recorder(deltas), None,
                                              window=16))
        summary = agg.summarize_hosts(np.stack(payloads), window=16)
        assert summary["num_hosts"] == 4
        assert summary["baseline"] == "cross-host median"
        assert summary["straggler"]["host"] == 2
        assert summary["straggler"]["step"] == 7
        assert summary["straggler"]["excess_ms"] == pytest.approx(
            240.0, rel=0.01)
        scores = [ph["straggler_score"]
                  for ph in summary["per_host"]]
        assert max(range(4), key=lambda h: scores[h]) == 2

    def test_deterministic_re_summarization(self):
        payloads = np.stack([
            agg.local_payload(_recorder([10.0, 80.0, 10.0]), None,
                              window=8)
            for _ in range(2)])
        payloads[1, 3] += 70.0  # host 1's step-3 delta... inflate
        one = agg.summarize_hosts(payloads, window=8)
        two = agg.summarize_hosts(payloads.copy(), window=8)
        assert one == two  # pure function of the gathered matrix

    def test_single_host_falls_back_to_temporal_baseline(self):
        deltas = [10.0] * 6
        deltas[2] = 200.0  # step 4
        summary = agg.aggregate(_recorder(deltas), None, num_processes=1,
                                window=16)
        assert summary["baseline"] == "within-host median"
        assert summary["straggler"] == {
            "host": 0, "step": 4,
            "excess_ms": pytest.approx(190.0),
            "score": pytest.approx(19.0),
        }

    def test_empty_recorder_degrades(self):
        summary = agg.aggregate(FlightRecorder(4), None, num_processes=1)
        assert summary["common_steps"] == 0
        assert "straggler" not in summary

    def test_phase_totals_ride_the_payload(self):
        class Clock:
            def snapshot(self):
                return {"step": 4.0, "ckpt": 1.0}

        summary = agg.aggregate(_recorder([10.0, 10.0]), Clock(),
                                num_processes=1)
        ph = summary["per_host"][0]["phase_seconds"]
        assert ph["step"] == 4.0 and ph["ckpt"] == 1.0 and ph["eval"] == 0.0


class TestTrainerStragglerPin:
    def test_chaos_slow_step_attributed_in_flight_dump(self, tmp_path):
        """Single-process tier-1 variant of the drill: the injected step
        is named in the dump's hosts section (host 0 — there is only
        one), and re-aggregating the same recorder reproduces it."""
        from distributed_training_tpu.config import (
            ChaosConfig,
            CheckpointConfig,
            DataConfig,
            LMConfig,
            TrainConfig,
        )
        from distributed_training_tpu.train.lm_trainer import LMTrainer

        cfg = TrainConfig(
            model="transformer_lm", num_epochs=1, log_interval=4,
            eval_every=0,
            lm=LMConfig(seq_len=16, num_layers=1, num_heads=2,
                        hidden_dim=32, max_len=32, train_sequences=64,
                        eval_sequences=64),
            data=DataConfig(batch_size=1, max_steps_per_epoch=8),
            checkpoint=CheckpointConfig(
                directory=str(tmp_path / "ckpt"), interval=0),
            # A second long: under six xdist workers a step of this toy
            # has been seen to hiccup for 255 ms on its own, which
            # out-scored a 250 ms injection.
            chaos=ChaosConfig(slow_step_every=5, slow_step_ms=1000.0))
        trainer = LMTrainer(cfg)
        trainer.fit()
        snap = json.load(open(trainer.obs.dump(
            str(tmp_path / "flight.json"))))
        strag = snap["hosts"]["straggler"]
        assert (strag["host"], strag["step"]) == (0, 5), strag
        assert strag["excess_ms"] > 500.0
        again = agg.aggregate(trainer.obs.recorder, trainer.clock,
                              num_processes=1)
        assert (again["straggler"]["host"],
                again["straggler"]["step"]) == (0, 5)
        # The injected stall also lands in the run-lifetime histogram.
        hist = snap["histograms"]["step_time_ms"]
        assert hist["count"] == 7  # 8 steps -> 7 consecutive deltas


class TestFlightReportTool:
    def test_exits_nonzero_one_line_on_malformed(self, tmp_path, capsys):
        from conftest import load_cli_module

        report = load_cli_module("tools/flight_report.py")
        torn = tmp_path / "torn.json"
        torn.write_text('{"format_version": 1, "steps": [')
        assert report.main([str(torn)]) == 2
        err = capsys.readouterr().err
        assert err.startswith("flight_report: error:")
        assert err.count("\n") == 1
        assert report.main([str(tmp_path / "missing.json")]) == 2
        wrong = tmp_path / "wrong.json"
        wrong.write_text('{"format_version": 99}')
        assert report.main([str(wrong)]) == 2

    def test_prometheus_exposition(self, tmp_path, capsys):
        from conftest import load_cli_module

        rec = _recorder([10.0, 20.0, 30.0])
        rec.record_flush(4, {"loss": 1.5})
        path = str(tmp_path / "f.json")
        rec.dump(path, phase_totals={"step": 3.0, "data": 1.0})
        report = load_cli_module("tools/flight_report.py")
        assert report.main(["--prometheus", path]) == 0
        out = capsys.readouterr().out
        assert "flight_steps_recorded_total 4" in out
        assert 'flight_phase_seconds{phase="step"} 3' in out
        assert 'flight_step_time_ms_bucket{le="+Inf"} 3' in out
        assert "flight_step_time_ms_count 3" in out
        assert "flight_goodput 0.75" in out
        # Text-exposition shape: every non-comment line is `name value`.
        for line in out.splitlines():
            if line.startswith("#"):
                continue
            name, value = line.rsplit(" ", 1)
            float(value)

    def test_prometheus_includes_serving_histograms(self, tmp_path,
                                                    capsys):
        from conftest import load_cli_module

        from distributed_training_tpu.serving.metrics import ServeTelemetry
        from distributed_training_tpu.serving.request import (
            FinishedRequest,
        )

        tel = ServeTelemetry(16)
        tel.on_iteration(0, queue_depth=0, active=1)
        tel.on_finished(FinishedRequest(
            uid=0, prompt=np.zeros(2, np.int32),
            tokens=np.zeros(3, np.int32), finish_reason="length",
            ttft_ms=12.0, tpot_ms=7.0, arrival_t=0.0, first_token_t=0.012))
        path = str(tmp_path / "s.json")
        tel.dump(path)
        report = load_cli_module("tools/flight_report.py")
        assert report.main(["--prometheus", path]) == 0
        out = capsys.readouterr().out
        assert "serving_ttft_ms_count 1" in out
        assert "serving_tpot_ms_count 1" in out
        assert "serving_ttft_hist_p99_ms" in out


# The multi-process drill. Deliberately XLA-free, like the path it
# tests: the aggregation exchanges payloads over the coordination-
# service KV store instead of an XLA collective, so a meter flush never
# puts work on the accelerators. The worker drives the real round-9
# injector (ChaosMonkey.on_step, host-gated, real sleep) through the
# real recorder and the real cross-process gather, then writes the
# aggregated flight dump each rank would dump.
DRILL_WORKER = textwrap.dedent("""
    import json, os, time
    import jax

    from distributed_training_tpu.runtime.distributed import (
        initialize_distributed)
    initialize_distributed()
    assert jax.process_count() == 2, jax.process_count()

    from distributed_training_tpu.config import ChaosConfig
    from distributed_training_tpu.observability import aggregate as agg
    from distributed_training_tpu.observability.flight_recorder import (
        FlightRecorder)
    from distributed_training_tpu.resilience.chaos import ChaosMonkey

    me = jax.process_index()
    # --chaos-slow-step surface: ONLY host 1 stalls, at step 5 (the next
    # multiple, 10, is past the run) — attribution must name (1, 5).
    monkey = ChaosMonkey(
        ChaosConfig(slow_step_every=5, slow_step_ms=250.0,
                    slow_step_host=1),
        process_index=me)
    rec = FlightRecorder(64)
    for step in range(1, 9):
        time.sleep(0.012)       # the "step"
        monkey.on_step(step)    # injected stall lands in THIS step's
        rec.record_step(step)   # delta (the trainers order identically)
    summary = agg.aggregate(rec, None, num_processes=2)
    path = os.path.join(os.environ["OUT_DIR"], f"flight_r{me}.json")
    rec.dump(path, extra={"hosts": summary})
    strag = json.load(open(path))["hosts"]["straggler"]
    assert monkey.counters["slow_steps"] == (1 if me == 1 else 0)
    print(f"OK rank={me} host={strag['host']} step={strag['step']} "
          f"excess={strag['excess_ms']:.1f}", flush=True)
""")


def _run_drill(tmp_path, tag):
    from test_multihost import _free_port

    port = _free_port()
    out_dir = tmp_path / tag
    out_dir.mkdir()
    procs = []
    for rank in range(2):
        env = dict(os.environ)
        env.update(
            PYTHONPATH=REPO,
            JAX_PLATFORMS="cpu",
            XLA_FLAGS="--xla_force_host_platform_device_count=4",
            MASTER_ADDR="127.0.0.1",
            MASTER_PORT=str(port),
            RANK=str(rank),
            WORLD_SIZE="2",
            OUT_DIR=str(out_dir),
        )
        procs.append(subprocess.Popen(
            [sys.executable, "-c", DRILL_WORKER],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            env=env))
    outs = []
    try:
        for p in procs:
            out, err = p.communicate(timeout=240)
            outs.append((p.returncode, out, err))
    finally:
        # A crashed rank leaves its peer blocked on the KV read: kill
        # the survivors so the real failure surfaces, not a timeout.
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.communicate()
    for rc, out, err in outs:
        assert rc == 0, err[-2000:]
    return [o.strip().splitlines()[-1] for _, o, _ in outs]


def test_multihost_straggler_drill_attributes_injected_host(tmp_path):
    """The acceptance pin, on a REAL 2-process CPU mesh: chaos slow-step
    on host 1 at step 5 only; the replicated aggregation names (1, 5) in
    both ranks' flight dumps, identically (the summary is replicated)."""
    lines = _run_drill(tmp_path, "run1")
    assert all("host=1 step=5" in line for line in lines), lines
    assert (lines[0].split("host=")[1] == lines[1].split("host=")[1]), lines
    for rank in range(2):
        snap = json.load(open(tmp_path / "run1" / f"flight_r{rank}.json"))
        strag = snap["hosts"]["straggler"]
        assert (strag["host"], strag["step"]) == (1, 5)
        assert strag["excess_ms"] > 100.0


@pytest.mark.slow
def test_multihost_straggler_drill_deterministic_across_runs(tmp_path):
    """Second half of the acceptance bar: an identical second run
    attributes the same (host, step) — the injected 250 ms dwarfs
    CPU-step noise, so the argmax is stable run to run."""
    first = _run_drill(tmp_path, "run1")
    second = _run_drill(tmp_path, "run2")
    assert all("host=1 step=5" in line for line in first), first
    assert all("host=1 step=5" in line for line in second), second
