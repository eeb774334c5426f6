"""Per-process accelerator set-up every entry point shares: where the
compile cache lives, which device the process got, and the refusal to
measure without a TPU.

Nothing here runs at import: each CLI's ``main`` calls
:func:`enable_compile_cache` before its first jit (tests never do, so
tier-1 leaves no cache behind in the checkout).
"""

from __future__ import annotations

import os

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"

# One fixed directory inside the checkout (git-ignored), derived from the
# package's own location: the path is part of the persistent cache's key,
# so a directory that moves between processes (tempfile, pid, timestamp)
# would never hit.
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns the directory.

    Where ``$JAX_COMPILATION_CACHE_DIR`` is set nothing is set in code —
    JAX reads the variable itself, and whoever placed the cache there
    finds it again. Otherwise the cache is :data:`DEFAULT_CACHE_DIR`.
    Call before the first compile: JAX latches the setting then.
    """
    placed = os.environ.get(CACHE_DIR_ENV)
    if placed:
        return placed
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR


def expected_platform() -> str:
    """The platform a NEW process would get from JAX, worked out without
    initializing a backend — for a parent that must stay off the chip its
    children need. ``$JAX_PLATFORMS``' first entry when set; else ``tpu``
    when TPU chips are attached over PCI (the sysfs probe JAX itself runs
    before warning about a missing libtpu); else ``cpu``."""
    requested = os.environ.get("JAX_PLATFORMS", "").split(",")[0].strip()
    if requested:
        return requested.lower()
    from jax._src import hardware_utils

    chips, _ = hardware_utils.num_available_tpu_chips_and_device_id()
    return "tpu" if chips else "cpu"


def device_summary() -> dict:
    """``{"platform", "kind", "count"}`` of the devices JAX gave this
    process (initializes the backend)."""
    import jax

    devices = jax.devices()
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices)}


def device_banner() -> str:
    """The device part of a start-up banner line (stderr only: no SLA row
    or flight dump carries these keys)."""
    d = device_summary()
    return (f"platform={d['platform']} device_kind={d['kind']!r} "
            f"devices={d['count']}")


def require_tpu(what: str) -> dict:
    """Exit non-zero unless this process runs on a TPU — for tools whose
    output is a device number (benches, kernel timings, profiles). A CPU
    timing is never printed under a device metric's name, so there is no
    fallback: no chip, no result line. Returns :func:`device_summary`."""
    d = device_summary()
    if d["platform"] != "tpu":
        raise SystemExit(
            f"{what}: needs a TPU, but JAX gave platform "
            f"{d['platform']!r} ({d['kind']}); nothing measured")
    return d
