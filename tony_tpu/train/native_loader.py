"""ctypes binding for the native token loader (tony_tpu/native/tonyloader.cpp).

The C++ loader prefetches shuffled (seq_len+1)-token windows from a
memory-mapped corpus on a real thread, off the GIL — the trainer's host step
overlaps with input IO. Built on demand with g++ (pybind11 is not in the
image; the C ABI + ctypes needs no build-time Python headers).

``available()`` is False when no compiler exists or the build fails;
train/data.py then takes its pure-numpy path and says so. The library is
built from the source file as it stands and from nothing else: its name
carries the source's hash, so a binary of any other source is never loaded.
"""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import subprocess
import threading
from typing import Iterator

import numpy as np

log = logging.getLogger(__name__)

_SRC = os.path.join(os.path.dirname(__file__), "..", "native", "tonyloader.cpp")
_lock = threading.Lock()
_lib: ctypes.CDLL | None = None


def _build_dir() -> str:
    # default: inside the checkout (listed in .gitignore), not under ~
    d = os.environ.get("TONY_NATIVE_DIR") or os.path.join(
        os.path.dirname(os.path.abspath(_SRC)), "..", "..", ".native_build"
    )
    d = os.path.abspath(d)
    os.makedirs(d, exist_ok=True)
    return d


def _load() -> ctypes.CDLL | None:
    global _lib
    if _lib is not None:
        return _lib
    src = os.path.abspath(_SRC)
    try:
        with open(src, "rb") as f:
            digest = hashlib.sha1(f.read()).hexdigest()[:12]
    except OSError:
        return None
    # the lock's purpose is to serialize the ONE-TIME native build across
    # threads racing the first loader construction; after that it guards a
    # cached-handle read. Holding it across the compile is the design.
    with _lock:
        if _lib is not None:
            return _lib
        lib_path = os.path.join(_build_dir(), f"libtonyloader-{digest}.so")  # graft-lint: disable=GL004
        if not os.path.exists(lib_path):
            tmp_path = f"{lib_path}.{os.getpid()}.tmp"
            try:
                subprocess.run(  # graft-lint: disable=GL004
                    ["g++", "-O3", "-std=c++17", "-shared", "-fPIC", "-pthread",
                     src, "-o", tmp_path],
                    check=True, capture_output=True, timeout=120,
                )
                os.replace(tmp_path, lib_path)  # graft-lint: disable=GL004
            except (OSError, subprocess.SubprocessError) as e:
                log.warning("native loader build failed: %s", e)
                return None
        try:
            lib = ctypes.CDLL(lib_path)
        except OSError as e:
            log.warning("native loader load failed: %s", e)
            return None
        lib.tl_open.restype = ctypes.c_void_p
        lib.tl_open.argtypes = [ctypes.c_char_p, ctypes.c_long, ctypes.c_long,
                                ctypes.c_long, ctypes.c_long, ctypes.c_ulonglong]
        lib.tl_next.restype = ctypes.c_long
        lib.tl_next.argtypes = [ctypes.c_void_p, ctypes.POINTER(ctypes.c_int32)]
        lib.tl_windows_per_epoch.restype = ctypes.c_long
        lib.tl_windows_per_epoch.argtypes = [ctypes.c_void_p]
        lib.tl_seek.argtypes = [ctypes.c_void_p, ctypes.c_long]
        lib.tl_close.argtypes = [ctypes.c_void_p]
        _lib = lib
        return _lib


def available() -> bool:
    return _load() is not None


class NativeTokenLoader:
    """Shuffled, prefetched batches from a flat int32 token file.

    Yields [batch, seq_len+1] int32 arrays; each epoch covers every window
    of this shard exactly once in a seeded order. ``seek(step)`` gives
    resume-exact positioning for elastic restart.
    """

    def __init__(self, path: str, seq_len: int, batch: int,
                 n_shards: int = 1, shard_id: int = 0, seed: int = 0):
        lib = _load()
        if lib is None:
            raise RuntimeError("native loader unavailable (no g++ or build failed)")
        self._lib = lib
        self._handle = lib.tl_open(
            path.encode(), seq_len, batch, n_shards, shard_id, seed
        )
        if not self._handle:
            raise ValueError(
                f"tl_open failed for {path!r} (missing file, too few windows "
                f"for batch={batch} x shards={n_shards}, or shard_id "
                f"{shard_id} outside [0, {n_shards}))"
            )
        self.batch = batch
        self.window = seq_len + 1
        self._buf = np.empty((batch, self.window), np.int32)

    @property
    def steps_per_epoch(self) -> int:
        return self._lib.tl_windows_per_epoch(self._handle)

    def seek(self, step: int) -> None:
        self._lib.tl_seek(self._handle, step)

    def next(self) -> np.ndarray:
        rc = self._lib.tl_next(
            self._handle, self._buf.ctypes.data_as(ctypes.POINTER(ctypes.c_int32))
        )
        if rc != 0:
            raise RuntimeError("native loader stopped")
        return self._buf.copy()

    def next_into(self, inputs: np.ndarray, targets: np.ndarray) -> None:
        """Read the next window's pre-shifted (inputs, targets) pair directly
        into caller-owned contiguous buffers — skips next()'s intermediate
        defensive copy (the data layer's single-contiguous-copy contract)."""
        rc = self._lib.tl_next(
            self._handle, self._buf.ctypes.data_as(ctypes.POINTER(ctypes.c_int32))
        )
        if rc != 0:
            raise RuntimeError("native loader stopped")
        np.copyto(inputs, self._buf[:, :-1])
        np.copyto(targets, self._buf[:, 1:])

    def __iter__(self) -> Iterator[np.ndarray]:
        while True:
            yield self.next()

    def close(self) -> None:
        if self._handle:
            self._lib.tl_close(self._handle)
            self._handle = None

    def __enter__(self) -> "NativeTokenLoader":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


__all__ = ["NativeTokenLoader", "available"]
