"""The program's own spans (observability/trace.py::span / record /
spanned): the ring, the nesting, the forwarding to a TraceSession, and
the names the engine and the trainer put on the profiler's clock."""

import collections
import sys
import threading
import time

import jax
import numpy as np
import pytest

from distributed_training_tpu.observability import trace as trace_lib
from distributed_training_tpu.observability.flight_recorder import (
    FlightRecorder,
    host_span_stats,
)
from distributed_training_tpu.observability.trace import TraceSession

SERVE_PHASES = ["serve.admit", "serve.assemble", "serve.device_step",
                "serve.commit", "serve.finish"]
# parent -> the names that must lie inside it, on the profiler's clock
NESTING = {
    "serve.iteration": SERVE_PHASES,
    "serve.device_step": ["serve.dispatch", "serve.token_wait"],
    "train.step": ["train.dispatch"],
    "train.log": ["train.metrics_fetch"],
    "setup.trainer_init": ["setup.model_init", "setup.step_build",
                           "setup.state_init"],
    "setup.engine_init": ["setup.cache_alloc", "setup.program_build"],
}
TOP_LEVEL = ["train.batch_wait", "data.next", "data.place"]
ANNOTATED = sorted(set(NESTING) | {c for cs in NESTING.values() for c in cs}
                   | set(TOP_LEVEL))


def _engine(max_new_tokens=12, **model_kw):
    from distributed_training_tpu.config import ServeConfig
    from distributed_training_tpu.models import get_model
    from distributed_training_tpu.serving import Engine

    kw = dict(num_classes=64, num_layers=2, num_heads=2, hidden_dim=64,
              max_len=64)
    kw.update(model_kw)
    model = get_model("transformer_lm", **kw)
    params = model.init(jax.random.PRNGKey(0),
                        np.zeros((1, 8), np.int32))["params"]
    return Engine(model, params,
                  ServeConfig(max_batch=4, max_new_tokens=max_new_tokens,
                              prefill_chunk=8))


def _submit(eng, n):
    rng = np.random.RandomState(0)
    for _ in range(n):
        eng.submit(rng.randint(0, 64, size=5).astype(np.int32))


def _seat_all(eng):
    """Step until every request decodes: what is left is the decode-only
    iteration, the engine's hot loop."""
    while len(eng.queue) or any(s.prefilling
                                for s in eng.scheduler.active()):
        eng.step()


def _trainer(tmp_path, steps):
    from distributed_training_tpu.config import (
        CheckpointConfig,
        DataConfig,
        LMConfig,
        TrainConfig,
    )
    from distributed_training_tpu.train.lm_trainer import LMTrainer

    return LMTrainer(TrainConfig(
        model="transformer_lm", num_epochs=1, log_interval=2, eval_every=0,
        lm=LMConfig(seq_len=16, num_layers=1, num_heads=2, hidden_dim=32,
                    max_len=32, train_sequences=64, eval_sequences=64),
        data=DataConfig(batch_size=1, max_steps_per_epoch=steps),
        checkpoint=CheckpointConfig(directory=str(tmp_path / "ckpt"),
                                    interval=0)))


class TestRing:
    def test_parent_ids_and_keys_nest_per_thread(self):
        seen = {}

        def work(tag):
            with trace_lib.span(f"{tag}.outer", key=tag) as outer:
                with trace_lib.span(f"{tag}.inner") as inner:
                    time.sleep(0.001)
                late = trace_lib.record(f"{tag}.late", 1.0, 2.0, key="own")
            seen[tag] = (outer, inner, late)

        threads = [threading.Thread(target=work, args=(t,), name=f"w-{t}")
                   for t in ("a", "b")]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=10)
            assert not th.is_alive()
        for tag, (outer, inner, late) in seen.items():
            assert outer.parent is None and outer.thread == f"w-{tag}"
            assert inner.parent == outer.id == late.parent
            assert inner.key == tag           # handed down by the parent
            assert late.key == "own" and late.seconds == 1.0
            assert outer.t0 <= inner.t0 <= inner.t1 <= outer.t1
        ids = [s.id for trio in seen.values() for s in trio]
        assert len(set(ids)) == 6
        names = {s.name for s in trace_lib.host_spans()}
        assert {"a.outer", "a.inner", "a.late", "b.outer"} <= names

    def test_bounds_select_spans_wholly_inside(self):
        trace_lib.record("edge", 10.0, 20.0)
        inside = lambda lo, hi: any(           # noqa: E731
            s.name == "edge" for s in trace_lib.host_spans(lo, hi))
        assert inside(10.0, 20.0) and inside(None, 20.0)
        assert not inside(10.5, 20.0) and not inside(10.0, 19.5)

    def test_ring_is_bounded_and_loses_nothing_under_threads(
            self, monkeypatch):
        workers, each = 16, 1500
        monkeypatch.setattr(trace_lib, "_ring", collections.deque(
            maxlen=workers * each + 100))
        stop = threading.Event()

        def emit(j):
            for i in range(each):
                with trace_lib.span("stress", key=(j, i)):
                    pass

        def scrape():
            while not stop.is_set():
                trace_lib.host_spans()
                host_span_stats()

        old = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            reader = threading.Thread(target=scrape)
            reader.start()
            threads = [threading.Thread(target=emit, args=(j,))
                       for j in range(workers)]
            for th in threads:
                th.start()
            for th in threads:
                th.join(timeout=60)
                assert not th.is_alive()
            stop.set()
            reader.join(timeout=10)
            assert not reader.is_alive()
        finally:
            sys.setswitchinterval(old)
        got = [s for s in trace_lib.host_spans() if s.name == "stress"]
        assert len(got) == workers * each          # no append was lost
        assert len({s.key for s in got}) == workers * each
        assert len({s.id for s in got}) == workers * each
        assert all(s.parent is None for s in got)  # stacks are per thread

        monkeypatch.setattr(trace_lib, "_ring",
                            collections.deque(maxlen=64))
        for i in range(200):
            trace_lib.record("small", 0.0, 1.0, key=i)
        kept = trace_lib.host_spans()
        assert len(kept) == 64 and kept[-1].key == 199
        assert host_span_stats()["small"]["count"] == 64
        assert trace_lib.RING_SPANS >= 16384

    def test_a_span_reaches_an_attached_session_unchanged(self):
        tr = TraceSession()
        with trace_lib.span("unit", key=7, session=tr, track="lane",
                            program="fused") as outer:
            with trace_lib.span("part") as part:     # session handed down
                pass
            outer.attrs["live"] = 3
        late = trace_lib.record("late", outer.t0, outer.t1, key=9,
                                session=tr, track="slot 1", uid=9)
        with trace_lib.span("elsewhere"):            # no session: ring only
            pass
        events = {e["name"]: e for e in tr.to_json()["traceEvents"]
                  if e["ph"] == "X"}
        assert set(events) == {"unit", "part", "late"}
        tracks = {e["args"]["name"]: e["tid"]
                  for e in tr.to_json()["traceEvents"]
                  if e["ph"] == "M" and e["name"] == "thread_name"}
        for sp, track, args in [
                (outer, "lane", {"program": "fused", "live": 3, "key": 7}),
                (part, "lane", {"key": 7}),
                (late, "slot 1", {"uid": 9, "key": 9})]:
            ev = events[sp.name]
            assert ev["tid"] == tracks[track] and ev["args"] == args
            assert ev["ts"] == pytest.approx((sp.t0 - tr._t0) * 1e6)
            assert ev["dur"] == pytest.approx((sp.t1 - sp.t0) * 1e6)

    def test_spanned_times_every_wait_of_a_loop(self):
        step = [10]
        got = list(trace_lib.spanned(iter("abc"), "wait.src"))
        keyed = list(trace_lib.spanned(iter("ab"), "wait.keyed",
                                       key=lambda: step[0]))
        assert got == list("abc") and keyed == list("ab")
        spans = trace_lib.host_spans()
        # the wait that ends the loop is a wait too
        assert [s.key for s in spans if s.name == "wait.src"] == [0, 1, 2, 3]
        assert [s.key for s in spans if s.name == "wait.keyed"] == [10] * 3

    def test_a_decorated_function_opens_a_fresh_span_per_call(self):
        @trace_lib.span("deco.call")
        def f(x):
            return x + 1

        assert f(1) == 2 and f(2) == 3
        calls = [s for s in trace_lib.host_spans() if s.name == "deco.call"]
        assert len(calls) == 2 and calls[0].id != calls[1].id
        assert calls[0].t1 <= calls[1].t0


@pytest.fixture(scope="module")
def profiled(tmp_path_factory):
    """One CPU profiler trace over a toy engine (built, three requests
    served) and a toy trainer (built, one epoch of four steps, a fetch
    every second)."""
    from benchmark import tracereduce

    tmp = tmp_path_factory.mktemp("spans")
    t0 = time.perf_counter()
    jax.profiler.start_trace(str(tmp / "prof"))
    try:
        eng = _engine(max_new_tokens=4)
        _submit(eng, 3)
        eng.run()
        trainer = _trainer(tmp, steps=4)
        train_loader, _ = trainer.make_loaders()
        trainer.train_epoch(0, train_loader)
    finally:
        jax.profiler.stop_trace()
    t1 = time.perf_counter()
    loaded = tracereduce.load(
        tracereduce.find_xplane(str(tmp / "prof")), set(ANNOTATED))
    return {"host": loaded["host"], "ring": trace_lib.host_spans(t0, t1)}


class TestProgramSpans:
    def test_the_profilers_trace_holds_every_name(self, profiled):
        names = collections.Counter(n for n, _, _ in profiled["host"])
        assert set(ANNOTATED) <= set(names), set(ANNOTATED) - set(names)
        assert names["train.step"] == names["train.dispatch"] == 4
        assert names["train.batch_wait"] == 5      # four batches, the end
        assert names["train.metrics_fetch"] == 2
        assert names["serve.iteration"] >= 3
        assert names["serve.device_step"] == names["serve.token_wait"] >= 3
        # one dispatch span a call for the step(s) it launches ahead; the
        # call that lands a stream's last step launches nothing
        assert 3 <= names["serve.dispatch"] <= names["serve.device_step"]

    @pytest.mark.parametrize("parent", sorted(NESTING))
    def test_children_lie_inside_their_parents_on_that_clock(
            self, profiled, parent):
        outer = [(s, s + d) for n, s, d in profiled["host"] if n == parent]
        for child in NESTING[parent]:
            inner = [(s, s + d) for n, s, d in profiled["host"]
                     if n == child]
            assert inner
            for a, b in inner:
                assert any(lo <= a and b <= hi for lo, hi in outer), child

    def test_the_ring_holds_the_same_spans_with_parents_and_keys(
            self, profiled):
        ring = profiled["ring"]
        by_id = {s.id: s for s in ring}
        for parent, children in NESTING.items():
            for s in ring:
                if s.name in children:
                    assert by_id[s.parent].name == parent
        its = [s for s in ring if s.name == "serve.iteration"]
        assert [s.key for s in its] == sorted(s.key for s in its)
        assert {s.attrs["program"] for s in its} <= {
            "fused", "decode", "idle"}
        assert {"fused", "decode"} <= {s.attrs["program"] for s in its}
        for s in ring:
            if s.name in SERVE_PHASES + ["serve.dispatch"]:
                assert s.key == by_id[s.parent].key
            if s.name == "serve.device_step":
                assert s.attrs["program"] == by_id[s.parent].attrs["program"]
        # the request's two spans, after the fact, keyed by its uid
        queued = {s.key: s for s in ring if s.name == "serve.queued"}
        prefill = {s.key: s for s in ring if s.name == "serve.prefill"}
        assert len(queued) == len(prefill) == 3
        assert all(queued[u].t1 == prefill[u].t0 for u in queued)
        # a step's spans share its number; the worker's share the ordinal
        assert [s.key for s in ring if s.name == "train.dispatch"] \
            == [1, 2, 3, 4]
        assert [s.key for s in ring if s.name == "train.batch_wait"] \
            == [1, 2, 3, 4, 5]
        assert [s.key for s in ring if s.name == "train.metrics_fetch"] \
            == [2, 4]
        worker = {s.thread for s in ring
                  if s.name in ("data.next", "data.place")}
        assert len(worker) == 1 and "MainThread" not in worker
        assert [s.key for s in ring if s.name == "data.place"] \
            == [0, 1, 2, 3]


def test_the_phases_cover_the_iteration():
    eng = _engine(num_layers=4, hidden_dim=256, max_len=256)
    _submit(eng, 4)
    _seat_all(eng)                  # warm: both programs compiled
    t0 = time.perf_counter()
    for _ in range(8):
        eng.step()
    ring = trace_lib.host_spans(t0, time.perf_counter())
    its = {s.id: s for s in ring if s.name == "serve.iteration"}
    assert len(its) == 8
    covered = sum(s.seconds for s in ring if s.parent in its)
    assert covered >= 0.95 * sum(s.seconds for s in its.values())


class TestHotLoopsStayTransferFree:
    def test_engine_decode_iterations(self):
        eng = _engine()
        _submit(eng, 4)
        _seat_all(eng)
        t0 = time.perf_counter()
        with jax.transfer_guard("disallow"):
            for _ in range(4):
                eng.step()
        ring = trace_lib.host_spans(t0, time.perf_counter())
        assert sum(s.name == "serve.token_wait" for s in ring) == 4

    def test_trainer_epoch(self, tmp_path):
        trainer = _trainer(tmp_path, steps=4)
        train_loader, _ = trainer.make_loaders()
        trainer.train_epoch(0, train_loader)      # compiles
        t0 = time.perf_counter()
        with jax.transfer_guard("disallow"):
            trainer.train_epoch(1, train_loader)
        ring = trace_lib.host_spans(t0, time.perf_counter())
        assert sum(s.name == "train.dispatch" for s in ring) == 4
        assert sum(s.name == "train.metrics_fetch" for s in ring) == 2


def test_speculation_adds_its_two_records_to_the_iteration():
    from distributed_training_tpu.config import ServeConfig
    from distributed_training_tpu.models import get_model
    from distributed_training_tpu.serving import Engine

    model = get_model("transformer_lm", num_classes=64, num_layers=1,
                      num_heads=2, hidden_dim=32, max_len=64)
    params = model.init(jax.random.PRNGKey(0),
                        np.zeros((1, 8), np.int32))["params"]
    eng = Engine(model, params, ServeConfig(
        max_batch=2, max_new_tokens=6, prefill_chunk=8, spec_k=2))
    t0 = time.perf_counter()
    _submit(eng, 2)
    eng.run()
    ring = trace_lib.host_spans(t0, time.perf_counter())
    by_id = {s.id: s for s in ring}
    drafts = [s for s in ring if s.name == "serve.draft"]
    verifies = [s for s in ring if s.name == "serve.verify"]
    assert drafts and len(drafts) == len(verifies)
    for d in drafts:
        outer = by_id[d.parent]
        assert outer.name == "serve.assemble" and d.key == outer.key
        assert outer.t0 == d.t0 and d.t1 <= outer.t1
        assert d.attrs["slots"] >= 1 and d.attrs["tokens"] >= 0
    for v in verifies:
        assert by_id[v.parent].name == "serve.commit"
        assert 0 <= v.attrs["accepted"] <= v.attrs["drafted"]


def test_the_flight_dump_carries_the_ring(tmp_path, capsys):
    from conftest import load_cli_module

    for _ in range(3):
        trace_lib.record("dump.me", 0.0, 0.004)
    rec = FlightRecorder(8)
    snap = rec.snapshot()
    assert snap["host_spans"]["dump.me"]["count"] >= 3
    assert snap["host_spans"]["dump.me"]["p50_ms"] == pytest.approx(4.0)
    assert set(snap["host_spans"]["dump.me"]) == {
        "count", "p50_ms", "p95_ms", "max_ms"}
    path = str(tmp_path / "flight.json")
    rec.dump(path)
    report = load_cli_module("tools/flight_report.py")
    assert report.main([path]) == 0
    out = capsys.readouterr().out
    assert "host spans" in out and "dump.me" in out
