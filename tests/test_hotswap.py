"""Live weight hot-swap tests (serving/hotswap.py + engine barrier).

Load-bearing properties, in order of importance:

1. **Determinism**: two engines fed the same requests with the swap
   forced at the same iteration produce bitwise-identical outputs —
   the swap is a pure params substitution at a boundary, nothing else
   moves. With no swap armed, the greedy oracle (sequential Generator
   equivalence) is untouched.
2. **Refusal safety**: a torn/corrupt candidate is quarantined and the
   engine keeps serving its old weights (typed ``SwapError`` +
   ``swaps_rejected``); I/O faults mid-staging and tree mismatches are
   rejected the same way. An UNCOMMITTED dir is invisible (it may be a
   save still in flight — quarantining it would destroy good bytes).
3. **Attribution**: the barrier pause lands in ``swap_blocked_s``, is
   compensated out of in-flight requests' TPOT, and its iteration delta
   is gap-excluded from the decode step-time percentiles — pinned the
   way ``admission_blocked_s`` is.
4. **Resource hygiene**: a swap under 2×+ page-pool oversubscription
   leaves the allocator balanced (no leak, no stranded commitment).

The fixtures share one tiny compiled model; swaps never retrace (same
shapes/dtypes), so the per-test cost is host logic, not XLA.
"""

import json
import os
import time
import urllib.request

import jax
import numpy as np
import pytest

from distributed_training_tpu import checkpoint as ckpt_lib
from distributed_training_tpu.config import ChaosConfig, ServeConfig
from distributed_training_tpu.inference import Generator, SampleConfig
from distributed_training_tpu.models import get_model
from distributed_training_tpu.resilience import chaos as chaos_lib
from distributed_training_tpu.resilience.chaos import (
    ChaosMonkey,
    corrupt_committed_checkpoint,
    tear_checkpoint,
)
from distributed_training_tpu.serving import (
    Engine,
    HotSwapper,
    SwapError,
    committed_epochs,
)

VOCAB = 61
MAX_LEN = 64
N_NEW = 6
PROMPT_LENS = [3, 5, 9, 5]


@pytest.fixture(scope="module")
def lm():
    model = get_model("transformer_lm", num_classes=VOCAB, num_layers=2,
                      num_heads=2, hidden_dim=32, max_len=MAX_LEN)
    p1 = model.init(jax.random.PRNGKey(0),
                    np.zeros((2, 16), np.int32))["params"]
    p2 = model.init(jax.random.PRNGKey(1),
                    np.zeros((2, 16), np.int32))["params"]
    return model, p1, p2


@pytest.fixture(scope="module")
def prompts():
    rng = np.random.RandomState(1)
    return [rng.randint(0, VOCAB, size=l).astype(np.int32)
            for l in PROMPT_LENS]


def _run(model, params, prompts, *, swap_at=None, swap_params=None,
         swap_epoch=7, **cfg_kw):
    """Drive one engine over ``prompts``, optionally arming a swap
    before iteration ``swap_at``; returns (engine, {uid: tokens})."""
    eng = Engine(model, params, ServeConfig(
        max_batch=2, max_new_tokens=N_NEW, **cfg_kw))
    for p in prompts:
        eng.submit(p)
    done, it = [], 0
    while not eng.idle:
        if swap_at is not None and it == swap_at:
            eng.arm_swap(swap_params, epoch=swap_epoch)
        done.extend(eng.step())
        it += 1
    assert len(done) == len(prompts)
    return eng, {f.uid: f for f in done}


class TestSwapDeterminism:
    def test_swap_at_iteration_k_bitwise_across_runs(self, lm, prompts):
        """Acceptance: same requests + swap forced at the same iteration
        ⇒ bitwise-identical outputs on both runs — and the swap really
        changed the weights (outputs differ from the no-swap run)."""
        model, p1, p2 = lm
        _, base = _run(model, p1, prompts)
        ea, a = _run(model, p1, prompts, swap_at=3, swap_params=p2)
        _, b = _run(model, p1, prompts, swap_at=3, swap_params=p2)
        for uid in a:
            np.testing.assert_array_equal(a[uid].tokens, b[uid].tokens)
        assert any((a[u].tokens != base[u].tokens).any() for u in a), \
            "swap to different weights changed no output token"
        stats = ea.stats()
        assert stats["swaps_completed"] == 1
        assert stats["weights_epoch"] == 7
        assert stats["swaps_rejected"] == 0

    def test_no_swap_greedy_oracle_untouched(self, lm, prompts, tmp_path):
        """A watcher attached to an empty directory (polling mid-run)
        must not perturb a single token: greedy stays identical to the
        sequential Generator."""
        model, p1, _ = lm
        eng = Engine(model, p1, ServeConfig(max_batch=2,
                                            max_new_tokens=N_NEW))
        swapper = HotSwapper(eng, str(tmp_path / "empty"),
                             lambda e: None, printer=lambda m: None)
        for p in prompts:
            eng.submit(p)
        done = []
        while not eng.idle:
            assert swapper.poll_once() is None
            done.extend(eng.step())
        by_uid = {f.uid: f for f in done}
        gen = Generator(model, p1, SampleConfig(max_new_tokens=N_NEW,
                                                temperature=0.0))
        for uid, p in enumerate(prompts):
            np.testing.assert_array_equal(by_uid[uid].tokens, gen(p)[0])
        assert eng.stats()["swaps_completed"] == 0
        assert eng.weights_epoch == -1

    def test_swap_under_pool_oversubscription_leak_free(self, lm,
                                                        prompts):
        """Swap mid-flight with the pool at 2×+ oversubscription (3
        pages serve one request's commitment at a time): every request
        completes, tokens are deterministic across two runs, and the
        allocator drains balanced — no page leak, no stranded
        commitment."""
        model, p1, p2 = lm
        ea, a = _run(model, p1, prompts * 2, swap_at=4, swap_params=p2,
                     kv_pages=3)
        eb, b = _run(model, p1, prompts * 2, swap_at=4, swap_params=p2,
                     kv_pages=3)
        for uid in a:
            np.testing.assert_array_equal(a[uid].tokens, b[uid].tokens)
            assert a[uid].tokens.size == N_NEW
        ea.pool.check_balanced()
        eb.pool.check_balanced()
        assert ea.stats()["swaps_completed"] == 1


class TestRefusalSafety:
    def test_torn_candidate_quarantined_engine_unharmed(self, lm,
                                                        prompts,
                                                        tmp_path):
        """Tear-after-commit: the candidate carries a COMMITTED marker
        but fails the checksum pass — the watcher quarantines it, the
        engine keeps serving the old weights, and the rejection is a
        typed SwapError counted in swaps_rejected."""
        model, p1, p2 = lm
        watch = str(tmp_path / "ckpt")
        eng = Engine(model, p1, ServeConfig(max_batch=2,
                                            max_new_tokens=N_NEW))
        swapper = HotSwapper(eng, watch, lambda e: p2,
                             printer=lambda m: None)
        ckpt_lib.save_checkpoint(watch, 1,
                                 {"x": np.arange(64, dtype=np.float32)})
        corrupt_committed_checkpoint(os.path.join(watch, "epoch_1"))
        assert swapper.poll_once() is None
        with pytest.raises(SwapError, match="verification"):
            # the quarantine already happened; re-dropping the same
            # fault re-raises through raise_on_error for the caller
            ckpt_lib.save_checkpoint(
                watch, 2, {"x": np.arange(64, dtype=np.float32)})
            corrupt_committed_checkpoint(os.path.join(watch, "epoch_2"))
            swapper.poll_once(raise_on_error=True)
        assert os.path.isdir(os.path.join(watch, "epoch_1.corrupt"))
        assert os.path.isdir(os.path.join(watch, "epoch_2.corrupt"))
        err = eng.last_swap_error
        assert isinstance(err, SwapError) and err.stage == "verify"
        assert err.epoch == 2
        stats = eng.stats()
        assert stats["swaps_rejected"] == 2
        assert stats["swaps_completed"] == 0
        assert eng.weights_epoch == -1
        # The engine still serves (old weights) after the refusals.
        _, by_uid = _run(model, p1, prompts[:1])
        eng.submit(prompts[0])
        done = eng.run()
        np.testing.assert_array_equal(done[0].tokens, by_uid[0].tokens)

    def test_quarantined_epoch_redropped_good_deploys(self, lm,
                                                      tmp_path):
        """A quarantine is a verdict on BYTES, not on the epoch number:
        after a torn epoch_1 is renamed to epoch_1.corrupt, a fresh
        valid epoch_1 dropped later is a new candidate and deploys —
        the blacklist only pins epochs whose bad dir is still visible
        (quarantine disabled or the rename failed)."""
        model, p1, p2 = lm
        watch = str(tmp_path / "ckpt")
        eng = Engine(model, p1, ServeConfig(max_batch=1,
                                            max_new_tokens=2))
        swapper = HotSwapper(eng, watch, lambda e: p2,
                             printer=lambda m: None)
        ckpt_lib.save_checkpoint(watch, 1,
                                 {"x": np.arange(64, dtype=np.float32)})
        corrupt_committed_checkpoint(os.path.join(watch, "epoch_1"))
        assert swapper.poll_once() is None
        assert os.path.isdir(os.path.join(watch, "epoch_1.corrupt"))
        # The re-drop (e.g. the trainer re-saving the epoch after the
        # first copy bit-rotted in transit).
        ckpt_lib.save_checkpoint(watch, 1,
                                 {"x": np.arange(64, dtype=np.float32)})
        assert swapper.poll_once() == 1
        eng.submit(np.arange(3, dtype=np.int32))
        eng.run()
        assert eng.weights_epoch == 1
        assert eng.stats()["swaps_rejected"] == 1

    def test_uncommitted_candidate_invisible_not_quarantined(self, lm,
                                                             tmp_path):
        """A torn UNCOMMITTED dir is a save that may still be flushing:
        the swap plane must neither deploy nor quarantine it (the
        trainer-side fallback owns dead saves)."""
        model, p1, p2 = lm
        watch = str(tmp_path / "ckpt")
        ckpt_lib.save_checkpoint(watch, 1,
                                 {"x": np.arange(64, dtype=np.float32)})
        tear_checkpoint(os.path.join(watch, "epoch_1"))
        assert committed_epochs(watch) == []
        eng = Engine(model, p1, ServeConfig(max_batch=1))
        swapper = HotSwapper(eng, watch, lambda e: p2,
                             printer=lambda m: None)
        assert swapper.poll_once() is None
        assert eng.stats()["swaps_rejected"] == 0
        assert os.path.isdir(os.path.join(watch, "epoch_1"))

    def test_staging_io_fault_rejected_then_next_poll_succeeds(
            self, lm, tmp_path):
        """Chaos staging-read fault (swap_error_rate=1): the attempt is
        rejected with stage='stage' and the engine keeps its weights;
        the fault is one-shot, so the next poll deploys the epoch."""
        model, p1, p2 = lm
        watch = str(tmp_path / "ckpt")
        ckpt_lib.save_checkpoint(watch, 1,
                                 {"x": np.arange(64, dtype=np.float32)})
        eng = Engine(model, p1, ServeConfig(max_batch=1,
                                            max_new_tokens=2))
        swapper = HotSwapper(eng, watch, lambda e: p2,
                             printer=lambda m: None)
        monkey = ChaosMonkey(ChaosConfig(swap_error_rate=1.0))
        chaos_lib.install(monkey)
        try:
            assert swapper.poll_once() is None
            assert eng.last_swap_error.stage == "stage"
            assert eng.stats()["swaps_rejected"] == 1
            assert eng.weights_epoch == -1
            assert monkey.counters["io_faults"] == 1
            # One-shot: the retry (next poll) stages clean. The failed
            # attempt must not have blacklisted a healthy save.
            assert swapper.poll_once() == 1
        finally:
            chaos_lib.uninstall()
        eng.submit(np.arange(3, dtype=np.int32))
        eng.run()
        assert eng.weights_epoch == 1

    def test_tree_mismatch_rejected_at_validate(self, lm, tmp_path):
        """A restored tree that doesn't match the serving model's
        abstract tree (here: wrong depth) dies at the validate stage —
        never reaching the compiled programs."""
        model, p1, _ = lm
        other = get_model("transformer_lm", num_classes=VOCAB,
                          num_layers=1, num_heads=2, hidden_dim=32,
                          max_len=MAX_LEN)
        bad = other.init(jax.random.PRNGKey(0),
                         np.zeros((2, 16), np.int32))["params"]
        watch = str(tmp_path / "ckpt")
        ckpt_lib.save_checkpoint(watch, 1,
                                 {"x": np.arange(64, dtype=np.float32)})
        eng = Engine(model, p1, ServeConfig(max_batch=1))
        swapper = HotSwapper(eng, watch, lambda e: bad,
                             printer=lambda m: None)
        with pytest.raises(SwapError, match="parameter tree") as exc:
            swapper.poll_once(raise_on_error=True)
        assert exc.value.stage == "validate"
        assert eng.stats()["swaps_rejected"] == 1
        assert eng.weights_epoch == -1
        # The rejected dir stays on disk (not quarantined — the bytes
        # verified clean, they just don't fit THIS model) and is pinned
        # by marker identity: the unchanged dir is skipped silently...
        assert swapper.poll_once() is None
        assert eng.stats()["swaps_rejected"] == 1
        # ...but an in-place re-save (fresh COMMITTED marker, now
        # restoring a matching tree) is a NEW candidate and deploys.
        p2 = lm[2]
        swapper.restore_fn = lambda e: p2
        marker = os.path.join(watch, "epoch_1", "COMMITTED")
        st = os.stat(marker)
        os.utime(marker, ns=(st.st_atime_ns, st.st_mtime_ns + 1_000_000))
        assert swapper.poll_once() == 1

    def test_rollback_rearms_previous_weights(self, lm, prompts):
        """After a swap, rollback() re-arms the predecessor: outputs
        return to the original weights' tokens. With no completed swap
        there is nothing to re-arm — typed stage='rollback'."""
        model, p1, p2 = lm
        fresh = Engine(model, p1, ServeConfig(max_batch=1))
        with pytest.raises(SwapError, match="roll back") as exc:
            fresh.rollback()
        assert exc.value.stage == "rollback"

        _, base = _run(model, p1, prompts[:2])
        eng, _ = _run(model, p1, prompts[:2], swap_at=2, swap_params=p2)
        assert eng.weights_epoch == 7
        assert eng.rollback() == -1
        for i, p in enumerate(prompts[:2]):
            eng.submit(p)
        done = {f.uid - len(prompts[:2]): f for f in eng.run()}
        assert eng.weights_epoch == -1
        for i in range(2):
            np.testing.assert_array_equal(done[i].tokens, base[i].tokens)
        assert eng.stats()["swaps_completed"] == 2  # swap + rollback

    def test_swap_error_typing(self):
        err = SwapError("boom", stage="verify", epoch=3)
        assert isinstance(err, RuntimeError)
        assert err.stage == "verify" and err.epoch == 3
        assert SwapError("x").stage == "swap"
        from distributed_training_tpu.resilience import (
            SwapError as FromResilience,
        )
        assert FromResilience is SwapError


class TestSwapPauseAccounting:
    def test_pause_lands_in_swap_blocked_not_tpot_or_step_times(
            self, lm, prompts, monkeypatch):
        """The satellite pin, admission_blocked_s-style: an artificially
        slow barrier (300 ms install) must (a) land in swap_blocked_s,
        (b) be compensated out of in-flight requests' TPOT, and (c) be
        gap-excluded from the decode step-time series — the delta of
        the swap iteration contributes no step-time sample."""
        model, p1, p2 = lm
        pause = 0.3
        orig = Engine._install_params

        def slow_install(self, params):
            time.sleep(pause)
            orig(self, params)

        monkeypatch.setattr(Engine, "_install_params", slow_install)
        swap_at = 3
        eng = Engine(model, p1, ServeConfig(max_batch=2,
                                            max_new_tokens=N_NEW))
        # Warm both compiled programs OFF the measured window — a cold
        # engine's XLA compiles land inside token intervals and would
        # drown the pause this test attributes.
        eng.submit(np.arange(2, dtype=np.int32), max_new_tokens=2)
        eng.run()
        eng.reset_stats()
        for p in prompts:
            eng.submit(p)
        done, it = [], 0
        while not eng.idle:
            if it == swap_at:
                eng.arm_swap(p2, epoch=7)
            done.extend(eng.step())
            it += 1
        by_uid = {f.uid: f for f in done}
        stats = eng.stats()
        assert stats["swap_blocked_s"] >= pause
        # TPOT compensation: every multi-token request's decode span
        # (tpot × intervals) excludes the pause entirely.
        for f in by_uid.values():
            assert f.tpot_ms is not None
            assert f.tpot_ms * (f.tokens.size - 1) < pause * 1e3
        # Step-time exclusion: the delta attributed to the swap
        # iteration is gap-marked out of the recorder's series.
        # The engine runs one device step ahead: the call after the
        # arming lands the step in flight, the barrier runs in the next.
        deltas = dict(eng.telemetry.recorder.step_deltas_ms())
        assert swap_at + 1 not in deltas, (
            "swap-iteration delta leaked into step-time percentiles")
        assert swap_at in deltas and swap_at + 2 in deltas  # neighbors still counted

    def test_phase_and_healthz_reflect_swap(self, lm):
        """The drive-by satellite: phase gains 'swapping', and /healthz
        carries weights_epoch + swap counters (the rollout driver's
        confirmation surface)."""
        from distributed_training_tpu.observability.exporter import (
            attach_engine,
        )

        model, p1, p2 = lm
        eng = Engine(model, p1, ServeConfig(max_batch=1,
                                            max_new_tokens=2))
        exporter = attach_engine(eng, 0, printer=lambda m: None)
        try:
            def healthz():
                with urllib.request.urlopen(exporter.url("/healthz"),
                                            timeout=10) as resp:
                    return json.loads(resp.read())

            h = healthz()
            assert h["phase"] == "idle"
            assert h["weights_epoch"] == -1
            assert h["swaps_completed"] == 0
            eng.arm_swap(p2, epoch=5)
            assert eng.phase == "swapping"
            assert healthz()["phase"] == "swapping"
            eng.submit(np.arange(3, dtype=np.int32))
            eng.run()
            h = healthz()
            assert h["phase"] == "idle"
            assert h["weights_epoch"] == 5
            assert h["swaps_completed"] == 1
        finally:
            exporter.close()

    def test_trace_carries_swap_marks_and_staging_span(self, lm,
                                                       tmp_path):
        """Swap observability on the timeline: armed/applied/rejected
        instants on the engine track, the staging pipeline as a span on
        its own 'hotswap' track."""
        from distributed_training_tpu.observability.trace import (
            TraceSession,
        )

        model, p1, p2 = lm
        trace = TraceSession()
        eng = Engine(model, p1, ServeConfig(max_batch=1,
                                            max_new_tokens=2),
                     trace=trace)
        watch = str(tmp_path / "ckpt")
        ckpt_lib.save_checkpoint(watch, 1,
                                 {"x": np.arange(64, dtype=np.float32)})
        swapper = HotSwapper(eng, watch, lambda e: p2,
                             printer=lambda m: None)
        assert swapper.poll_once() == 1
        eng.submit(np.arange(3, dtype=np.int32))
        eng.run()
        eng.note_swap_rejected(SwapError("x", stage="verify", epoch=2))
        names = [e["name"] for e in trace.to_json()["traceEvents"]]
        for want in ("swap.stage", "swap.armed", "swap.applied",
                     "swap.rejected"):
            assert want in names, (want, names)


class TestWatcherLifecycle:
    def test_background_thread_trigger_and_close(self, lm, tmp_path):
        """The serve.py wiring shape: a long-interval watcher thread,
        woken early by trigger() (the SIGHUP path), deploys a freshly
        committed epoch; close() joins the thread."""
        model, p1, p2 = lm
        watch = str(tmp_path / "ckpt")
        eng = Engine(model, p1, ServeConfig(max_batch=1,
                                            max_new_tokens=2))
        swapper = HotSwapper(eng, watch, lambda e: p2,
                             printer=lambda m: None)
        swapper.start(interval_s=60.0)
        deadline = time.time() + 20
        while swapper.counters["polls"] < 1 and time.time() < deadline:
            time.sleep(0.01)
        assert swapper.counters["polls"] >= 1, "watcher never polled"
        ckpt_lib.save_checkpoint(watch, 1,
                                 {"x": np.arange(64, dtype=np.float32)})
        swapper.trigger()
        deadline = time.time() + 20
        while swapper.counters["armed"] < 1 and time.time() < deadline:
            time.sleep(0.01)
        assert swapper.counters["armed"] == 1, "trigger() never woke it"
        swapper.close()
        assert eng.phase == "swapping"  # armed, awaiting the barrier
        eng.submit(np.arange(3, dtype=np.int32))
        eng.run()
        assert eng.weights_epoch == 1

    def test_request_rollback_serviced_on_watcher_thread(self, lm,
                                                         tmp_path):
        """The SIGUSR1 path: request_rollback() only sets events (a
        signal handler must not take the engine's swap lock — the
        serving loop holds it around the barrier on the same thread);
        the WATCHER thread performs the rollback on its next wake."""
        model, p1, p2 = lm
        watch = str(tmp_path / "ckpt")  # stays empty: polls find nothing
        eng, _ = _run(model, p1, [np.arange(3, dtype=np.int32)],
                      swap_at=0, swap_params=p2)
        assert eng.weights_epoch == 7
        swapper = HotSwapper(eng, watch, lambda e: p2,
                             printer=lambda m: None)
        swapper.start(interval_s=60.0)
        swapper.request_rollback()
        deadline = time.time() + 20
        while eng.phase != "swapping" and time.time() < deadline:
            time.sleep(0.01)
        swapper.close()
        assert eng.phase == "swapping", "rollback never serviced"
        eng.submit(np.arange(3, dtype=np.int32))
        eng.run()
        assert eng.weights_epoch == -1  # back on the original weights

    def test_restore_fn_reuses_template_without_rebuild(self, tmp_path):
        """The build_lm_and_restorer closure IS the staging read: a
        checkpoint saved from a differently-valued state restores
        through restore_fn bitwise, with no model rebuild."""
        from distributed_training_tpu.config import (
            OptimizerConfig,
            PrecisionConfig,
            SchedulerConfig,
        )
        from distributed_training_tpu.inference.restore import (
            build_lm_and_restorer,
        )
        from distributed_training_tpu.train.optim import make_optimizer
        from distributed_training_tpu.train.precision import (
            LossScaleState,
            Policy,
        )
        from distributed_training_tpu.train.train_state import (
            init_train_state,
        )

        ckdir = str(tmp_path / "ck")
        kw = dict(vocab_size=VOCAB, num_layers=1, num_heads=2,
                  hidden_dim=32, max_len=MAX_LEN, checkpoint=ckdir,
                  printer=lambda m: None)
        model, params, epoch, restore_fn = build_lm_and_restorer(**kw)
        assert epoch == -1  # nothing saved yet

        # Save a state with shifted params (the "newly trained" epoch),
        # built exactly the way the restorer's template was.
        tx = make_optimizer(OptimizerConfig(), SchedulerConfig(),
                            world_size=1)
        state = init_train_state(
            model, jax.random.PRNGKey(0), (1, 8), tx,
            loss_scale=LossScaleState.create(PrecisionConfig()),
            input_dtype=jax.numpy.int32)
        shifted = jax.tree.map(lambda a: a + 1.0, state.params)
        state = state.replace(params=shifted)
        ckpt_lib.save_checkpoint(ckdir, 0, state)

        got = restore_fn(0)
        jax.tree.map(
            lambda a, b: np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b)), got, shifted)

    def test_serve_bench_swap_mode_sla_line(self, monkeypatch, capsys):
        """tools/serve_bench.py --swap-at-request: the SLA line carries
        the swap counters the bench gate consumes (exactly one
        completed swap, zero rejected, the bumped weights epoch)."""
        from conftest import load_cli_module

        bench = load_cli_module("tools/serve_bench.py")
        monkeypatch.setattr("sys.argv", [
            "serve_bench.py", "--requests", "6", "--rate", "500",
            "--max-batch", "2", "--num-layers", "1", "--num-heads", "2",
            "--hidden-dim", "32", "--model-max-len", "64",
            "--prompt-len", "6", "--max-new-tokens", "4",
            "--swap-at-request", "3"])
        assert bench.main() == 0
        line = capsys.readouterr().out.strip().splitlines()[-1]
        stats = json.loads(line)
        assert stats["swaps_completed"] == 1
        assert stats["swaps_rejected"] == 0
        assert stats["swap_blocked_s"] >= 0.0
        assert stats["weights_epoch"] == 0  # -1 (random init) + 1
        assert stats["requests_finished"] == 6
