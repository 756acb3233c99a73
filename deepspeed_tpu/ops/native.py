"""ctypes loader for the host-side native library (csrc/ → libdstpu.so).

Reference parity: ``op_builder/builder.py:436-497`` (``OpBuilder.load`` JIT
compile + import). Here the native code is torch-free C++ with a C ABI: built
once with ``make`` and loaded with ctypes; each op-family binding module
declares its own argtypes on top of the handle returned by :func:`get_lib`.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import platform
import subprocess
import threading
from typing import Optional

from deepspeed_tpu.utils.logging import logger

_LIB: Optional[ctypes.CDLL] = None
_LOCK = threading.Lock()


def _repo_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def lib_path() -> str:
    return os.path.join(_repo_root(), "csrc", "build", "libdstpu.so")


def _build_stamp() -> str:
    """What an on-disk library must have been built from to be loaded: the
    current ``csrc`` sources and this host's CPU. The Makefile compiles with
    ``-march=native`` and ``csrc/build/`` is not in git, so a binary copied
    in from another machine (or left from older sources) must not be used."""
    h = hashlib.sha256()
    csrc = os.path.join(_repo_root(), "csrc")
    for name in sorted(os.listdir(csrc)):
        if name.endswith(".cpp") or name == "Makefile":
            h.update(name.encode())
            with open(os.path.join(csrc, name), "rb") as f:
                h.update(f.read())
    cpu = platform.machine()
    try:
        with open("/proc/cpuinfo") as f:
            cpu += next((line for line in f if line.startswith("flags")), "")
    except OSError:
        pass
    h.update(cpu.encode())
    return h.hexdigest()


def build_library(verbose: bool = False) -> str:
    """Rebuild ``csrc/build/libdstpu.so`` from the current sources on this
    host and stamp it (see :func:`_build_stamp`)."""
    csrc = os.path.join(_repo_root(), "csrc")
    result = subprocess.run(["make", "-C", csrc, "-B", "-j"],
                            capture_output=True, text=True)
    if result.returncode != 0:
        # -march=native can fail under qemu/exotic hosts; retry portable.
        result = subprocess.run(["make", "-C", csrc, "-B", "-j", "ARCHFLAGS="],
                                capture_output=True, text=True)
    if result.returncode != 0:
        raise RuntimeError(f"native build failed:\n{result.stderr[-2000:]}")
    with open(lib_path() + ".stamp", "w") as f:
        f.write(_build_stamp())
    if verbose:
        logger.info(f"built native library at {lib_path()}")
    return lib_path()


def ensure_library() -> str:
    """Path of a library built from the current sources on this host,
    rebuilding when the file or its stamp is missing or stale."""
    path = lib_path()
    try:
        with open(path + ".stamp") as f:
            fresh = os.path.exists(path) and f.read() == _build_stamp()
    except OSError:
        fresh = False
    return path if fresh else build_library()


def get_lib() -> ctypes.CDLL:
    """Load (building if necessary) the shared library. Thread-safe."""
    global _LIB
    if _LIB is not None:
        return _LIB
    with _LOCK:
        if _LIB is None:
            _LIB = ctypes.CDLL(ensure_library())
    return _LIB


def available() -> bool:
    try:
        get_lib()
        return True
    except Exception as e:  # pragma: no cover - env specific
        logger.warning(f"native library unavailable: {e}")
        return False


# Common ctypes aliases used by binding modules
c_f32p = ctypes.POINTER(ctypes.c_float)
c_u16p = ctypes.POINTER(ctypes.c_uint16)
c_i64 = ctypes.c_int64
c_f32 = ctypes.c_float
c_int = ctypes.c_int


def as_f32_ptr(arr):
    return arr.ctypes.data_as(c_f32p)


def as_u16_ptr(arr):
    return arr.ctypes.data_as(c_u16p)


def check_buffer(arr, dtype, name: str, expect_size: int | None = None) -> None:
    """Validate a host buffer before handing its raw pointer to native code.

    ctypes ``data_as`` returns the base pointer of strided views, so anything
    non-contiguous (or of the wrong dtype/size) would silently corrupt memory.
    """
    import numpy as np
    if not isinstance(arr, np.ndarray):
        raise TypeError(f"{name} must be a numpy array, got {type(arr)}")
    if arr.dtype != np.dtype(dtype):
        raise TypeError(f"{name} must be {np.dtype(dtype)}, got {arr.dtype}")
    if not arr.flags["C_CONTIGUOUS"]:
        raise ValueError(f"{name} must be C-contiguous")
    if expect_size is not None and arr.size != expect_size:
        raise ValueError(f"{name} has {arr.size} elements, expected {expect_size}")
