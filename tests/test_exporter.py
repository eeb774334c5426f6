"""Live telemetry plane tests (observability/exporter.py).

Load-bearing properties:

1. **Scrape-vs-dump parity** (the one-implementation satellite): a live
   ``/metrics`` scrape and ``flight_report.py --prometheus`` over a dump
   of the SAME run agree family-for-family — byte-for-byte, in fact,
   since both render through ``observability/prometheus.py``.
2. **Bitwise telemetry equality** (acceptance): the TTFT/TPOT histogram
   bucket counts a live scrape reports equal the end-of-run
   ``ServeTelemetry`` state exactly.
3. **Liveness semantics**: /healthz tracks the engine's
   serving→draining→drained phase and the trainers' clock phase;
   a port already in use fails construction loudly; close() releases
   the port; a broken snapshot provider returns 500 without killing the
   server.
4. **Live-run integration**: both a real 1-epoch LM train and an
   in-process serving run are scrapeable while alive, through the same
   ``ObservabilityConfig.metrics_port`` / ``Engine.flight_snapshot``
   surfaces the CLIs use.
"""

import json
import threading
import time
import urllib.error
import urllib.request

import jax
import numpy as np
import pytest

from distributed_training_tpu.config import (
    CheckpointConfig,
    DataConfig,
    LMConfig,
    ObservabilityConfig,
    ServeConfig,
    TrainConfig,
)
from distributed_training_tpu.models import get_model
from distributed_training_tpu.observability.exporter import MetricsExporter
from distributed_training_tpu.observability.flight_recorder import (
    FlightRecorder,
)
from distributed_training_tpu.observability.prometheus import (
    families,
    prometheus_text,
    sample_value,
)
from distributed_training_tpu.serving import Engine


def _get(url: str, timeout: float = 10.0):
    with urllib.request.urlopen(url, timeout=timeout) as resp:
        return (resp.status, resp.headers.get("Content-Type", ""),
                resp.read().decode("utf-8"))


CANNED = {
    "format_version": 1,
    "reason": "scrape",
    "steps_recorded_total": 7,
    "step_time_stats": {"step_time_p50_ms": 3.5, "step_time_p95_ms": 9.0,
                        "step_time_max_ms": 12.0},
    "histograms": {"step_time_ms": {"bounds": [1.0, 10.0],
                                    "counts": [2, 3, 1],
                                    "count": 6, "sum": 31.0}},
}


class TestExporterUnit:
    def test_all_three_endpoints(self):
        exp = MetricsExporter(lambda: dict(CANNED), port=0,
                              phase_provider=lambda: "train").start()
        try:
            code, ctype, text = _get(exp.url("/metrics"))
            assert code == 200 and ctype.startswith("text/plain")
            fams = families(text)
            assert fams["flight_steps_recorded_total"] == "gauge"
            assert fams["flight_step_time_ms"] == "histogram"
            # Cumulative-le rendering of the canned counts [2, 3, 1].
            assert sample_value(text, 'flight_step_time_ms_bucket'
                                      '{le="1"}') == 2
            assert sample_value(text, 'flight_step_time_ms_bucket'
                                      '{le="+Inf"}') == 6
            assert sample_value(text, "flight_step_time_ms_count") == 6

            code, ctype, body = _get(exp.url("/healthz"))
            assert code == 200 and ctype.startswith("application/json")
            health = json.loads(body)
            assert health["status"] == "ok"
            assert health["phase"] == "train"
            assert health["scrapes"] == 1  # the /metrics GET above
            assert health["uptime_seconds"] >= 0

            code, ctype, body = _get(exp.url("/vars"))
            assert code == 200 and ctype.startswith("application/json")
            assert json.loads(body)["steps_recorded_total"] == 7
        finally:
            exp.close()

    def test_unknown_path_404(self):
        exp = MetricsExporter(lambda: dict(CANNED), port=0).start()
        try:
            with pytest.raises(urllib.error.HTTPError) as ei:
                _get(exp.url("/nope"))
            assert ei.value.code == 404
            body = json.loads(ei.value.read().decode())
            assert "/metrics" in body["endpoints"]
        finally:
            exp.close()

    def test_port_in_use_raises_at_construction(self):
        first = MetricsExporter(lambda: {}, port=0).start()
        try:
            with pytest.raises(OSError):
                MetricsExporter(lambda: {}, port=first.port)
        finally:
            first.close()

    def test_close_releases_port_and_stops_serving(self):
        exp = MetricsExporter(lambda: dict(CANNED), port=0).start()
        port = exp.port
        assert _get(exp.url("/healthz"))[0] == 200
        exp.close()
        exp.close()  # idempotent
        with pytest.raises(OSError):
            _get(f"http://127.0.0.1:{port}/healthz", timeout=1.0)
        # The port is actually free again: a new exporter can bind it.
        again = MetricsExporter(lambda: {}, port=port).start()
        try:
            assert _get(again.url("/healthz"))[0] == 200
        finally:
            again.close()

    def test_broken_provider_returns_500_server_survives(self):
        calls = {"n": 0}

        def flaky():
            calls["n"] += 1
            if calls["n"] == 1:
                raise RuntimeError("torn snapshot")
            return dict(CANNED)

        exp = MetricsExporter(flaky, port=0).start()
        try:
            with pytest.raises(urllib.error.HTTPError) as ei:
                _get(exp.url("/metrics"))
            assert ei.value.code == 500
            assert "torn snapshot" in ei.value.read().decode()
            assert _get(exp.url("/metrics"))[0] == 200  # still alive
        finally:
            exp.close()

    def test_train_observability_recorder_off_minimal_snapshot(self):
        """metrics_port with the flight recorder disabled still serves:
        the minimal snapshot keeps /metrics and /vars parseable."""
        from distributed_training_tpu.observability.hooks import (
            TrainObservability,
        )

        obs = TrainObservability(ObservabilityConfig(
            flight_recorder=False, metrics_port=0,
            straggler_attribution=False))
        try:
            assert obs.exporter is not None
            code, _, text = _get(obs.exporter.url("/metrics"))
            assert code == 200
            assert "flight_steps_recorded_total 0" in text
            json.loads(_get(obs.exporter.url("/vars"))[2])  # strict JSON
        finally:
            obs.close()


# -- serving integration ------------------------------------------------------

VOCAB = 32
N_NEW = 5
MIXED_LENS = (2, 7, 13, 5, 9)  # mixed-length workload (acceptance)


@pytest.fixture(scope="module")
def served():
    """One engine run over a mixed-length workload with the exporter
    attached, kept ALIVE for the scrape tests (drained by the last
    test in TestServingScrape, closed at teardown)."""
    model = get_model("transformer_lm", num_classes=VOCAB, num_layers=1,
                      num_heads=2, hidden_dim=32, max_len=48)
    params = model.init(jax.random.PRNGKey(0),
                        np.zeros((1, 8), np.int32))["params"]
    eng = Engine(model, params, ServeConfig(
        max_batch=2, max_new_tokens=N_NEW, flush_every=2))
    exp = MetricsExporter(eng.flight_snapshot, port=0,
                          phase_provider=lambda: eng.phase).start()
    rng = np.random.RandomState(3)
    prompts = [rng.randint(0, VOCAB, size=n).astype(np.int32)
               for n in MIXED_LENS]
    for p in prompts:
        eng.submit(p)
    done = eng.run()
    assert len(done) == len(prompts)
    yield eng, exp
    exp.close()


class TestServingScrape:
    def test_live_scrape_ttft_tpot_bitwise_equals_telemetry(self, served):
        """Acceptance: a live /metrics scrape and the end-of-run
        telemetry report IDENTICAL TTFT/TPOT histogram bucket counts
        for the completed requests."""
        eng, exp = served
        _, _, text = _get(exp.url("/metrics"))
        for name, hist in (("serving_ttft_ms", eng.telemetry.ttft_hist),
                           ("serving_tpot_ms", eng.telemetry.tpot_hist)):
            cum = hist.cumulative()
            bounds = [f"{b:g}" for b in hist.bounds] + ["+Inf"]
            for le, want in zip(bounds, cum):
                got = sample_value(text, f'{name}_bucket{{le="{le}"}}')
                assert got == want, (name, le, got, want)
            assert sample_value(text, f"{name}_count") == hist.total
        # The SLA-line percentiles and the scraped gauges agree too
        # (same %g rendering of the same float).
        stats = eng.stats()
        for key in ("ttft_hist_p50_ms", "ttft_hist_p95_ms",
                    "ttft_hist_p99_ms", "tpot_hist_p99_ms"):
            assert sample_value(text, f"serving_{key}") == float(
                f"{stats[key]:g}")

    def test_scrape_does_not_mutate_telemetry(self, served):
        """A scrape observes; it must not add flush entries or touch
        counters (dump_flight does flush — flight_snapshot must not)."""
        eng, exp = served
        before = len(eng.telemetry.recorder.flushes)
        finished = eng.telemetry.requests_finished
        _get(exp.url("/metrics"))
        _get(exp.url("/vars"))
        assert len(eng.telemetry.recorder.flushes) == before
        assert eng.telemetry.requests_finished == finished

    def test_golden_parity_live_scrape_vs_flight_report(self, served,
                                                        tmp_path):
        """Satellite: one exposition implementation — the live scrape
        and flight_report.py --prometheus over a dump of the same run
        agree family-for-family (byte-identical here: both render via
        observability/prometheus.py and the engine is quiescent)."""
        from conftest import load_cli_module

        eng, exp = served
        _, _, scrape_text = _get(exp.url("/metrics"))
        path = str(tmp_path / "serve_flight.json")
        eng.dump_flight(path)
        report = load_cli_module("tools/flight_report.py")
        report_text = "\n".join(
            report.prometheus_lines(FlightRecorder.load(path))) + "\n"
        assert families(scrape_text) == families(report_text)
        assert scrape_text == report_text
        # And the same text the module-level helper would produce.
        assert scrape_text == prometheus_text(eng.flight_snapshot())

    def test_vars_is_strict_json_with_serving_section(self, served):
        eng, exp = served
        snap = json.loads(_get(exp.url("/vars"))[2])
        srv = snap["serving"]
        assert srv["requests_finished"] == len(MIXED_LENS)
        # The fixed SLA histograms, plus one ledger_<cause>_ms family
        # per latency-ledger cause that actually appeared in this run
        # (serving/ledger.py; a clean serve shows the three lifecycle
        # causes and nothing else).
        assert set(srv["histograms"]) == {
            "ttft_ms", "tpot_ms", "queue_wait_ms", "prefill_ms",
            "ledger_queue_wait_ms", "ledger_prefill_ms",
            "ledger_decode_ms"}
        assert srv["kv_reserved_vs_written"] > 1.0
        assert srv["ledger_conservation_violations"] == 0
        assert srv["ledger_requests"] == len(MIXED_LENS)

    def test_drained_engine_phase(self, served):
        """Engine-drained behavior: /healthz keeps answering 200 and
        names the phase, so an LB can distinguish alive-but-drained
        from dead. (Runs last: drain closes admission for good.)"""
        eng, exp = served
        health = json.loads(_get(exp.url("/healthz"))[2])
        assert health["phase"] == "idle"
        eng.drain()
        health = json.loads(_get(exp.url("/healthz"))[2])
        assert health["status"] == "ok"
        assert health["phase"] == "drained"


# -- trainer integration ------------------------------------------------------

class TestTrainerLiveScrape:
    def test_scrape_during_live_1_epoch_train(self, mesh, tmp_path):
        """A real 1-epoch LM train with metrics_port: the endpoint
        answers DURING fit() (scraper thread) and is closed by
        obs.close() afterwards."""
        from distributed_training_tpu.train.lm_trainer import LMTrainer

        cfg = TrainConfig(
            model="transformer_lm", num_epochs=1, log_interval=4,
            eval_every=0,
            data=DataConfig(batch_size=2, max_steps_per_epoch=40,
                            prefetch=0),
            lm=LMConfig(seq_len=16, vocab_size=32, num_layers=1,
                        num_heads=2, hidden_dim=32, max_len=32,
                        train_sequences=128, eval_sequences=16),
            checkpoint=CheckpointConfig(directory=str(tmp_path / "ckpt"),
                                        interval=0),
            observability=ObservabilityConfig(metrics_port=0),
        )
        trainer = LMTrainer(cfg, mesh=mesh)
        exp = trainer.obs.exporter
        assert exp is not None, "metrics_port should attach an exporter"
        port = exp.port

        got: dict = {}
        stop = threading.Event()

        def scraper():
            while not stop.is_set():
                try:
                    _, _, text = _get(exp.url("/metrics"), timeout=2.0)
                    health = json.loads(
                        _get(exp.url("/healthz"), timeout=2.0)[2])
                except Exception:
                    time.sleep(0.005)
                    continue
                got["metrics"], got["health"] = text, health
                return

        th = threading.Thread(target=scraper, daemon=True)
        th.start()
        try:
            trainer.fit()
        finally:
            stop.set()
            th.join(timeout=30)
        assert "metrics" in got, "no successful scrape during the train"
        assert "flight_steps_recorded_total" in families(got["metrics"])
        assert got["health"]["status"] == "ok"
        assert got["health"]["phase"]  # step/log/data/... or "train"
        # close() (in fit's finally) released the port.
        with pytest.raises(OSError):
            _get(f"http://127.0.0.1:{port}/healthz", timeout=1.0)
