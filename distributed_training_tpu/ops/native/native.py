"""ctypes binding for the native augmentation library.

Lazy-builds the library with g++ on first use (no pybind11 in this image;
plain C ABI + ctypes per the environment's binding guidance) and falls back
to the pure-numpy implementations in ``data/transforms.py`` when no compiler
is available — the native path is an accelerator, never a hard dependency.
Which of the two is in use is said once on stderr.

The library's file name carries a hash of ``augment.cpp``, the compiler
flags and (because of ``-march=native``) this machine's CPU flags: a library
left in the tree by another source revision or another machine — file
mtimes mean nothing after a copy — is never loaded; it is rebuilt here.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import platform
import subprocess
import sys
import threading

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "augment.cpp")
_FLAGS = ("-O3", "-fPIC", "-shared", "-pthread", "-march=native")

_lock = threading.Lock()
_lib: ctypes.CDLL | None = None
_build_failed = False


def _cpu_identity() -> str:
    """What ``-march=native`` resolved against: the ISA-extension list of
    this machine's CPU (Linux), else just the architecture name."""
    try:
        with open("/proc/cpuinfo") as fh:
            for line in fh:
                if line.startswith(("flags", "Features")):
                    return platform.machine() + " " + line.split(":", 1)[1]
    except OSError:
        pass
    return platform.machine()


def lib_path() -> str:
    """The library this source + flags + CPU builds to."""
    h = hashlib.sha256()
    with open(_SRC, "rb") as fh:
        h.update(fh.read())
    h.update(" ".join(_FLAGS).encode())
    h.update(_cpu_identity().encode())
    return os.path.join(_HERE, f"libaugment-{h.hexdigest()[:16]}.so")


def _build(out: str) -> bool:
    # Build beside the target and rename: a concurrent process never
    # loads a half-written library.
    tmp = f"{out}.{os.getpid()}.tmp"
    cmd = ["g++", *_FLAGS, "-o", tmp, _SRC]
    try:
        res = subprocess.run(cmd, capture_output=True, text=True, timeout=120)
        if res.returncode != 0:
            # Retry without -march=native (unsupported on some toolchains).
            cmd.remove("-march=native")
            res = subprocess.run(
                cmd, capture_output=True, text=True, timeout=120)
        if res.returncode != 0:
            return False
        os.replace(tmp, out)
        return True
    except (OSError, subprocess.TimeoutExpired):
        return False
    finally:
        if os.path.exists(tmp):
            os.remove(tmp)


def _numpy_path(reason: str) -> None:
    global _build_failed
    _build_failed = True
    print(f"[native] augment: numpy reference path ({reason})",
          file=sys.stderr)


def get_lib() -> ctypes.CDLL | None:
    """Load (building if needed) the native library; None if unavailable."""
    global _lib
    if _lib is not None:
        return _lib
    if _build_failed:
        return None
    with _lock:
        if _lib is not None:
            return _lib
        if _build_failed:
            return None
        path = lib_path()
        built = not os.path.exists(path)
        if built and not _build(path):
            _numpy_path("g++ build failed")
            return None
        try:
            lib = ctypes.CDLL(path)
        except OSError as e:
            _numpy_path(f"{os.path.basename(path)} did not load: {e}")
            return None
        print(f"[native] augment: native library {os.path.basename(path)} "
              f"({'built now' if built else 'found'})", file=sys.stderr)
        lib.pad_crop_flip_u8.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p,
            ctypes.c_int64, ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
            ctypes.c_int64,
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
        ]
        lib.pad_crop_flip_u8.restype = None
        lib.u8_to_f32_affine.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
            ctypes.c_float, ctypes.c_float,
        ]
        lib.u8_to_f32_affine.restype = None
        lib.gather_crop_flip_u8.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
            ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
            ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
        ]
        lib.gather_crop_flip_u8.restype = None
        _lib = lib
        return _lib


def available() -> bool:
    return get_lib() is not None


def pad_crop_flip(images: np.ndarray, ys: np.ndarray, xs: np.ndarray,
                  flips: np.ndarray, pad: int) -> np.ndarray:
    """Native Pad(pad)+Crop+Flip; semantics identical to the numpy path."""
    lib = get_lib()
    assert lib is not None, "native lib unavailable — check available() first"
    images = np.ascontiguousarray(images, dtype=np.uint8)
    n, h, w, c = images.shape
    out = np.empty_like(images)
    # Bind converted index arrays to locals: `ascontiguousarray(x).ctypes
    # .data` would free the converted copy before the call (the int address
    # does not keep the array alive) — dangling pointer when dtypes differ.
    ys = np.ascontiguousarray(ys, np.int32)
    xs = np.ascontiguousarray(xs, np.int32)
    flips = np.ascontiguousarray(flips, np.uint8)
    lib.pad_crop_flip_u8(
        images.ctypes.data, out.ctypes.data,
        n, h, w, c, pad,
        ys.ctypes.data, xs.ctypes.data, flips.ctypes.data)
    return out


def gather_crop_flip(dataset: np.ndarray, lidx: np.ndarray, ys: np.ndarray,
                     xs: np.ndarray, flips: np.ndarray,
                     size: int) -> np.ndarray:
    """Fused gather+crop+flip straight out of a [N, bh, bw, c] uint8
    dataset (works on a memmap WITHOUT materializing it — no
    ascontiguousarray on the dataset, which would copy the whole file)."""
    lib = get_lib()
    assert lib is not None, "native lib unavailable — check available() first"
    if dataset.dtype != np.uint8 or not dataset.flags["C_CONTIGUOUS"]:
        raise ValueError("dataset must be C-contiguous uint8")
    _, bh, bw, c = dataset.shape
    n = len(lidx)
    out = np.empty((n, size, size, c), np.uint8)
    lidx = np.ascontiguousarray(lidx, np.int64)
    ys = np.ascontiguousarray(ys, np.int32)
    xs = np.ascontiguousarray(xs, np.int32)
    flips = np.ascontiguousarray(flips, np.uint8)
    lib.gather_crop_flip_u8(
        dataset.ctypes.data, out.ctypes.data, lidx.ctypes.data,
        n, bh, bw, size, size, c,
        ys.ctypes.data, xs.ctypes.data, flips.ctypes.data)
    return out


def u8_to_f32(images: np.ndarray, scale: float, bias: float) -> np.ndarray:
    """Native fused uint8→float32 affine (ToTensor [+ Normalize])."""
    lib = get_lib()
    assert lib is not None, "native lib unavailable — check available() first"
    images = np.ascontiguousarray(images, dtype=np.uint8)
    out = np.empty(images.shape, np.float32)
    lib.u8_to_f32_affine(
        images.ctypes.data, out.ctypes.data, images.size,
        ctypes.c_float(scale), ctypes.c_float(bias))
    return out
