"""What PR 21 put between the program and the chip: no fallback that hides
the device, a compile cache that can be placed from outside, a native
library keyed on its source, and a smoke with no CPU mode. CPU-only and
seconds in total — the chip run itself is ``python chip_smoke.py``."""

import os
import shutil
import subprocess
import sys
import types

import jax
import pytest

from distributed_training_tpu.ops.native import native
from distributed_training_tpu.runtime import backend
from distributed_training_tpu.utils import compat

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- compile cache -----------------------------------------------------------

def test_cache_env_var_set_means_code_sets_nothing(monkeypatch):
    monkeypatch.setenv(backend.CACHE_DIR_ENV, "/placed/from/outside")

    def refuse(*a, **k):
        raise AssertionError(f"jax.config.update{a} with the env var set")

    monkeypatch.setattr(jax.config, "update", refuse)
    assert backend.enable_compile_cache() == "/placed/from/outside"


def test_cache_default_is_one_fixed_path_in_the_checkout(monkeypatch):
    monkeypatch.delenv(backend.CACHE_DIR_ENV, raising=False)
    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: updates.append(a))
    want = os.path.join(REPO, ".jax_cache")
    assert backend.enable_compile_cache() == want
    assert updates == [("jax_compilation_cache_dir", want)]
    # ... and another process, started elsewhere, lands on the same path
    # and really has it set in jax's config.
    env = {k: v for k, v in os.environ.items()
           if k not in (backend.CACHE_DIR_ENV,
                        "JAX_ENABLE_COMPILATION_CACHE")}
    # (backend.py loaded by path: importing the whole package would cost
    # this test more seconds than the rest of the file together.)
    out = subprocess.run(
        [sys.executable, "-c",
         "import importlib.util, sys, jax\n"
         "spec = importlib.util.spec_from_file_location('b', sys.argv[1])\n"
         "backend = importlib.util.module_from_spec(spec)\n"
         "spec.loader.exec_module(backend)\n"
         "print(backend.enable_compile_cache())\n"
         "print(jax.config.jax_compilation_cache_dir)", backend.__file__],
        cwd="/", env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.split() == [want, want]


# -- no interpreter on the chip ----------------------------------------------

def _fake_devices(platform):
    return lambda *a, **k: [types.SimpleNamespace(platform=platform)]


def test_on_tpu_raises_when_the_backend_query_fails(monkeypatch):
    def broken(*a, **k):
        raise RuntimeError("Unable to initialize backend 'tpu'")

    monkeypatch.setattr(compat.jax, "devices", broken)
    with pytest.raises(RuntimeError, match="Unable to initialize"):
        compat.on_tpu()
    with pytest.raises(RuntimeError, match="Unable to initialize"):
        compat.pallas_interpret(None)


def test_platform_tpu_never_selects_interpret(monkeypatch):
    monkeypatch.setattr(compat.jax, "devices", _fake_devices("tpu"))
    assert compat.pallas_interpret(None) is False
    assert compat.pallas_interpret(False) is False
    with pytest.raises(RuntimeError, match="interpret mode"):
        compat.pallas_interpret(True)
    monkeypatch.setattr(compat.jax, "devices", _fake_devices("cpu"))
    assert compat.pallas_interpret(None) is True
    assert compat.pallas_interpret(True) is True


def test_require_tpu_exits_on_cpu():
    with pytest.raises(SystemExit, match="needs a TPU"):
        backend.require_tpu("some bench")


def test_front_door_refuses_unplaceable_replicas_on_tpu(monkeypatch, capsys):
    """More replica processes than chips-it-can-assign (it assigns none):
    refused before anything is spawned, and with no backend touched."""
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    from tools import serve_net

    monkeypatch.setattr(serve_net, "ReplicaProc", lambda *a: pytest.fail(
        "spawned a replica"))
    assert serve_net.main(["--smoke", "--replicas", "2"]) == 2
    assert "refusing --replicas 2" in capsys.readouterr().err


# -- native library keyed on its source --------------------------------------

@pytest.mark.skipif(shutil.which("g++") is None, reason="no g++")
def test_native_rebuilds_when_source_hash_differs(tmp_path, monkeypatch):
    src = tmp_path / "augment.cpp"
    shutil.copy(native._SRC, src)
    monkeypatch.setattr(native, "_SRC", str(src))
    monkeypatch.setattr(native, "_HERE", str(tmp_path))
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_build_failed", False)
    # A library left behind by ANOTHER revision of the source (here:
    # garbage that would not even load) ...
    stale = native.lib_path()
    with open(stale, "wb") as fh:
        fh.write(b"not a shared object")
    # ... is not the one this source builds to, so it is never loaded:
    with open(src, "a") as fh:
        fh.write("\n// a later revision\n")
    fresh = native.lib_path()
    assert fresh != stale and not os.path.exists(fresh)
    assert native.available()
    assert os.path.exists(fresh)


# -- the smoke has no CPU mode -----------------------------------------------

def test_chip_smoke_refuses_cpu_without_starting_a_phase():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                         env=env, capture_output=True, text=True, timeout=60)
    assert out.returncode != 0
    assert out.stdout == ""          # no result line
    assert "nothing started" in out.stderr
    assert "phase" not in out.stderr  # no child was spawned


# -- a limit found on real chips, said before the first compile --------------

def test_flash_under_tensor_dp_refused_on_multichip_tpu(monkeypatch, mesh):
    """XLA cannot partition a Mosaic kernel under plain jit (seen on a 2x2
    v5e host); the CPU interpreter hides that, so the trainer says it."""
    from distributed_training_tpu.config import LMConfig, TrainConfig
    from distributed_training_tpu.train import lm_trainer

    cfg = TrainConfig(model="transformer_lm").replace(
        lm=LMConfig(attn_impl="flash"))
    monkeypatch.setattr(lm_trainer, "on_tpu", lambda: True)
    with pytest.raises(NotImplementedError, match="more than one TPU chip"):
        lm_trainer.LMTrainer(cfg, mesh=mesh)
