"""Test harness: a virtual 8-device CPU mesh.

Multi-device-without-hardware strategy per SURVEY.md §4. Tests run on CPU
whatever the machine holds (a chip belongs to one process at a time):
``JAX_PLATFORMS=cpu`` is set here before ``import jax``, which is enough.
``xla_force_host_platform_device_count`` is read at CPU client creation;
setting it here (before the first device use) is early enough.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
# The CLIs' mains turn on the persistent compile cache; tests call them
# in-process and spawn them, and tier-1 must leave no cache behind in the
# checkout (the chip tool copies the tree as it stands on disk).
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402
import pytest  # noqa: E402

from distributed_training_tpu.runtime.mesh import MeshConfig, create_mesh  # noqa: E402


@pytest.fixture
def compile_watch():
    """Compiled-program sanitizer hook (observability/sanitizer.py): a
    CompileWatch marked at test start. Tests exercising warm paths call
    ``compile_watch.check_no_growth(...)`` to pin that nothing retraced;
    the first use installs the process-global jax.monitoring listener."""
    from distributed_training_tpu.observability.sanitizer import CompileWatch

    with CompileWatch() as watch:
        yield watch


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 virtual CPU devices, got {len(devs)}"
    return devs


@pytest.fixture(scope="session")
def mesh(devices):
    return create_mesh(MeshConfig(data=-1))


@pytest.fixture(scope="session")
def mesh2x4(devices):
    """data=2 × fsdp=4 mesh for ZeRO/FSDP tests."""
    return create_mesh(MeshConfig(data=2, fsdp=4))


def load_cli_module(relpath, name=None):
    """Import a per-backend CLI script (e.g. ``resnet/jax_tpu/train.py``)
    as a module; the backend dirs are script-style, not packages."""
    import importlib.util

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = os.path.join(repo, relpath)
    name = name or relpath.replace("/", "_").removesuffix(".py")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
