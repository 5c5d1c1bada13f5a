"""ctypes loader for the native batch-gather library (csrc/batchgen.cpp).

Compiles the shared library on first use with g++, next to the source,
under a name that carries the hash of the source and of the build flags
— so a library is only ever loaded if it was built from THIS
batchgen.cpp with THESE flags, whatever the mtimes say and whichever
machine the tree was copied from. The flags name no CPU (-march): a copy
of the tree, .so included, runs on another host. Every entry point has a
pure-numpy path for machines without a toolchain; it draws DIFFERENT
batches from the same seed, so which path is active is said once on
stderr and recorded by the loader (BatchLoader.native). pybind11 is not
in the image, so the binding is plain ctypes over an ``extern "C"``
surface.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import sys
import threading

import numpy as np

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
_SRC = os.path.join(_REPO_ROOT, "csrc", "batchgen.cpp")
_FLAGS = ("-O3", "-fPIC", "-shared", "-fopenmp")

_lock = threading.Lock()
_lib: ctypes.CDLL | None = None
_load_failed = False


def _lib_path() -> str:
    """csrc/libbatchgen-<hash of source + flags>.so (csrc/*.so is
    gitignored: the library is always built by the run, never committed)."""
    h = hashlib.sha256(" ".join(_FLAGS).encode())
    with open(_SRC, "rb") as f:
        h.update(f.read())
    return os.path.join(_REPO_ROOT, "csrc",
                        f"libbatchgen-{h.hexdigest()[:16]}.so")


def _build(path: str) -> None:
    # Build to a private name, then rename: a concurrent process never
    # loads a half-written library.
    tmp = f"{path}.{os.getpid()}.tmp"
    try:
        subprocess.run(["g++", *_FLAGS, _SRC, "-o", tmp], check=True,
                       capture_output=True, text=True, timeout=120)
        os.replace(tmp, path)
    finally:
        if os.path.exists(tmp):
            os.remove(tmp)


def _load() -> ctypes.CDLL:
    path = _lib_path()
    if not os.path.exists(path):
        _build(path)
    lib = ctypes.CDLL(path)
    lib.gather_windows_u16.argtypes = [
        ctypes.c_void_p, ctypes.c_int64, ctypes.c_void_p,
        ctypes.c_int64, ctypes.c_int64, ctypes.c_void_p]
    lib.sample_offsets.argtypes = [
        ctypes.c_uint64, ctypes.c_uint64, ctypes.c_int64,
        ctypes.c_int64, ctypes.c_int64, ctypes.c_void_p]
    return lib


def get_lib() -> ctypes.CDLL | None:
    global _lib, _load_failed
    if _lib is not None or _load_failed:
        return _lib
    with _lock:
        if _lib is not None or _load_failed:
            return _lib
        try:
            # lockcheck: disable=blocking-under-lock -- build-once by
            # design: the double-checked _lock exists precisely so ONE
            # thread compiles the .so while every other caller waits
            # rather than racing g++ over the same output file; cold
            # path, runs at most once per process.
            _lib = _load()
            print(f"[native] batch gather: csrc/{os.path.basename(_lib._name)}"
                  " (xorshift128+ offsets)", file=sys.stderr)
        except (OSError, subprocess.SubprocessError) as e:
            _load_failed = True
            why = (getattr(e, "stderr", None) or str(e)).strip()
            print("[native] batch gather: numpy path (Philox offsets — NOT "
                  "the batches the native path draws from the same seed); "
                  f"csrc/batchgen.cpp did not build or load: "
                  f"{type(e).__name__}: {why[-500:]}", file=sys.stderr)
    return _lib


def gather_windows(data: np.ndarray, offsets: np.ndarray, width: int) -> np.ndarray:
    """Gather ``len(offsets)`` windows of ``width`` uint16 tokens from data."""
    assert data.dtype == np.uint16
    offsets = np.ascontiguousarray(offsets, dtype=np.int64)
    B = len(offsets)
    out = np.empty((B, width), dtype=np.uint16)
    lib = get_lib()
    if lib is not None:
        lib.gather_windows_u16(
            data.ctypes.data_as(ctypes.c_void_p), data.shape[0],
            offsets.ctypes.data_as(ctypes.c_void_p), B, width,
            out.ctypes.data_as(ctypes.c_void_p))
        return out
    # numpy fallback: fancy-index a window per row
    idx = offsets[:, None] + np.arange(width)[None, :]
    np.take(data, idx, out=out)
    return out


def sample_offsets(seed: int, stream: int, n_tokens: int, width: int,
                   batch: int) -> np.ndarray:
    """Deterministic offsets in [0, n_tokens - width]; native or numpy path.

    Note: the two paths use different RNGs, so determinism holds per-path.
    The loader records which path is active (BatchLoader.native).
    """
    lib = get_lib()
    if lib is not None:
        out = np.empty(batch, dtype=np.int64)
        lib.sample_offsets(seed, stream, n_tokens, width, batch,
                           out.ctypes.data_as(ctypes.c_void_p))
        return out
    # stream goes into the 128-bit Philox KEY (not the counter): adjacent
    # stream ids get unrelated keystreams, so per-host/per-step draws never
    # overlap the way nearby counter offsets would.
    key = (int(seed) << 64) | (int(stream) & ((1 << 64) - 1))
    rng = np.random.Generator(np.random.Philox(key=key))
    return rng.integers(0, n_tokens - width + 1, size=batch, dtype=np.int64)
