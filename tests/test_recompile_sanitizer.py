"""Compiled-program sanitizer: the XLA inventory pins hold and trip.

The runtime half of the static-shape discipline (the AST half is
``tools/lint``'s ``static-shape`` rule): the serving engine's documented
inventory — 2 compiled programs, one shape per program
(docs/SERVING.md "compiled-program inventory") — is pinned through
``Engine.compiled_programs()`` + ``check_engine_inventory``, and a warm
steady state must not compile at all (``CompileWatch``). The growth
case forces a retrace the way a real leak would appear (a step input
whose width varies) and asserts the sanitizer trips.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_training_tpu.config import ServeConfig
from distributed_training_tpu.models import get_model
from distributed_training_tpu.observability.sanitizer import (
    CompileWatch,
    RecompileError,
    check_engine_inventory,
    compile_count,
    jit_cache_size,
)
from distributed_training_tpu.serving import Engine

VOCAB = 32
MAX_LEN = 32


@pytest.fixture(scope="module")
def lm():
    model = get_model("transformer_lm", num_classes=VOCAB, num_layers=1,
                      num_heads=2, hidden_dim=16, max_len=MAX_LEN)
    params = model.init(jax.random.PRNGKey(0),
                        np.zeros((1, 8), np.int32))["params"]
    return model, params


def _submit(engine, lens, seed=0):
    rng = np.random.RandomState(seed)
    for l in lens:
        engine.submit(rng.randint(0, VOCAB, size=l).astype(np.int32))


class TestCompileWatch:
    def test_counts_backend_compiles_and_cache_hits_dont(self):
        x = jnp.arange(8, dtype=jnp.float32)  # materialized pre-watch
        f = jax.jit(lambda v: v * 2 + 1)
        with CompileWatch() as watch:
            f(x)
        assert watch.compiles >= 1
        with pytest.raises(RecompileError, match="must not retrace"):
            watch.check_no_growth("test window")
        watch.mark()
        f(x)  # same shape: cache hit
        assert watch.compiles == 0
        watch.check_no_growth("warm window")  # no raise
        watch.expect(0, "warm window")  # no raise
        assert jit_cache_size(f) == 1
        x9 = jnp.arange(9, dtype=jnp.float32)  # arange compiles too —
        watch.mark()                           # keep it outside the pin
        f(x9)  # new shape: retrace
        assert jit_cache_size(f) == 2
        assert watch.compiles == 1
        watch.expect(1, "one forced retrace")  # no raise
        with pytest.raises(RecompileError, match="expected exactly"):
            watch.expect(2, "wrong pin")

    def test_compile_count_monotonic(self):
        a = compile_count()
        jax.jit(lambda v: v - 3)(jnp.float32(1.0))
        b = compile_count()
        assert b > a >= 0


def _gpt_engine(lm):
    model, params = lm
    eng = Engine(model, params, ServeConfig(
        max_batch=2, max_new_tokens=4, temperature=0.0,
        prefill_chunk=4))
    _submit(eng, [3, 5, 7])
    assert len(eng.run()) == 3
    return eng


def _prefix_hit_engine(lm):
    """The same prompt twice: the second seat aliases the first's two
    full pages and its chunk lane starts past them."""
    model, params = lm
    eng = Engine(model, params, ServeConfig(
        max_batch=2, max_new_tokens=4, temperature=0.0,
        prefill_chunk=4, kv_page_size=4, prefix_cache=True))
    for _ in range(2):
        _submit(eng, [9])
        assert len(eng.run()) == 1
    assert eng.stats()["prefix_cache_hit_tokens"] == 8
    return eng


def _deepseek_engine(lm):
    """The second model family's toy (latent and index pools, experts'
    step counters as one more output of both programs)."""
    from test_deepseek_v32 import run_engine

    return run_engine(5, (24, 31), max_new=4)[0]


class TestEngineInventory:
    @pytest.mark.parametrize("build", [
        _gpt_engine, _prefix_hit_engine, _deepseek_engine],
        ids=["gpt", "prefix_hit", "deepseek_v32"])
    def test_engine_pins_two_programs_one_shape(self, lm, build):
        eng = build(lm)
        progs = eng.compiled_programs()
        # Both programs ran (chunked prefill rode the fused step; the
        # post-prefill iterations were decode-only) and each holds
        # exactly one trace.
        assert progs == {"fused": 1, "decode": 1}
        assert check_engine_inventory(eng) == progs

    def test_warm_paged_steady_state_never_compiles(self, lm):
        model, params = lm
        eng = Engine(model, params, ServeConfig(
            max_batch=2, max_new_tokens=4, temperature=0.0,
            prefill_chunk=4))
        _submit(eng, [3, 5])
        eng.run()  # warm-up: both programs compiled
        with CompileWatch() as watch:
            _submit(eng, [3, 5, 7], seed=1)  # same shapes, new uids
            assert len(eng.run()) == 3
        watch.check_no_growth("warm paged serving")  # no raise
        check_engine_inventory(eng)

    def test_forced_extra_shape_trips_the_sanitizer(self, lm):
        model, params = lm
        eng = Engine(model, params, ServeConfig(
            max_batch=2, max_new_tokens=4, temperature=0.0,
            prefill_chunk=4))
        _submit(eng, [3, 5])
        eng.run()  # warm: both programs, one shape each
        check_engine_inventory(eng)
        # A decode lane two tokens wide where the engine's is one, every
        # row invalid (it writes the null page), handed over as the
        # launch hands it: a packed buffer of another width, as a leak
        # would have it.
        wide = np.zeros((2, 2), np.int32)
        eng._layout = dataclasses.replace(eng._layout, width=2)
        with CompileWatch() as watch:
            eng._decode(eng.params, eng._cache,
                        jnp.asarray(eng._layout.pack(
                            wide, wide, wide.astype(bool), wide[:, 0],
                            eng._slot_rng, eng._tables)),
                        jnp.asarray(wide), eng._no_tokens[1])
        # The forced retrace is visible on both surfaces: the window
        # compiled, and the decode program now holds two shapes.
        assert watch.compiles >= 1
        with pytest.raises(RecompileError, match="must not retrace"):
            watch.check_no_growth("window with a second lane width")
        assert eng.compiled_programs()["decode"] == 2
        with pytest.raises(RecompileError, match="decode"):
            check_engine_inventory(eng)

    def test_fixture_hands_out_a_marked_watch(self, lm, compile_watch):
        # The conftest fixture arms a watch before the test body; a
        # test that only touches warm code can assert silence.
        assert compile_watch.compiles == 0
        compile_watch.check_no_growth("fixture smoke")


class TestSpeculationInventory:
    """Speculation-on counts (docs/SERVING.md): the verify window IS
    the decode program at a wider fixed shape — the n-gram drafter
    changes NO count, a GPT drafter adds exactly one single-shape
    'draft' program, and the warm speculative steady state compiles
    nothing (varying accept lengths and proposal widths are masks,
    never shapes)."""

    def test_paged_spec_ngram_keeps_two_programs(self, lm):
        model, params = lm
        eng = Engine(model, params, ServeConfig(
            max_batch=2, max_new_tokens=6, temperature=0.0,
            prefill_chunk=4, spec_k=2))
        _submit(eng, [3, 5, 7])
        assert len(eng.run()) == 3
        progs = eng.compiled_programs()
        assert progs == {"fused": 1, "decode": 1}
        assert check_engine_inventory(eng) == progs
        # Warm speculative serving: accept lengths vary per iteration,
        # shapes never do.
        with CompileWatch() as watch:
            _submit(eng, [3, 5, 7], seed=1)
            assert len(eng.run()) == 3
        watch.check_no_growth("warm speculative serving")

    def test_gpt_drafter_adds_one_draft_program(self, lm):
        model, params = lm
        eng = Engine(model, params, ServeConfig(
            max_batch=2, max_new_tokens=6, temperature=0.0,
            prefill_chunk=4, spec_k=2, spec_drafter="gpt",
            spec_draft_window=8))
        _submit(eng, [3, 5])
        assert len(eng.run()) == 2
        progs = eng.compiled_programs()
        assert progs == {"fused": 1, "decode": 1, "draft": 1}
        assert check_engine_inventory(eng) == progs
        with CompileWatch() as watch:
            _submit(eng, [3, 5], seed=1)
            assert len(eng.run()) == 2
        watch.check_no_growth("warm gpt-drafted serving")
