"""ctypes bindings for the native (C++) data loader.

The shared library builds lazily on first use (one g++ invocation,
cached next to the sources, keyed by the source's content hash); if the
toolchain is unavailable the caller (shellac_tpu/training/data.py)
falls back to the pure-Python reader with identical semantics, and says
so.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from typing import Iterator, Optional, Sequence

import numpy as np

_DIR = os.path.dirname(os.path.abspath(__file__))
_SO = os.path.join(_DIR, "libshellac_runtime.so")
_SRC = os.path.join(_DIR, "csrc", "dataloader.cpp")
# Hash of the source the binary was built from. The binary is ignored by
# git and a copied tree carries whatever was on disk with fresh mtimes,
# so only the content says whether it is stale.
_STAMP = _SO + ".srchash"
_build_lock = threading.Lock()


def _built_from() -> Optional[str]:
    try:
        with open(_STAMP) as f:
            return f.read().strip()
    except OSError:
        return None


def ensure_built() -> str:
    """Build the shared library unless it was built from this exact
    source; returns its path."""
    with _build_lock:
        with open(_SRC, "rb") as f:
            want = hashlib.sha256(f.read()).hexdigest()
        if os.path.exists(_SO) and _built_from() == want:
            return _SO
        cmd = [
            os.environ.get("CXX", "g++"), "-O2", "-std=c++17", "-fPIC",
            "-Wall", "-shared", "-pthread", "-o", _SO, _SRC,
        ]
        try:
            subprocess.run(cmd, check=True, capture_output=True, text=True)
        except (subprocess.CalledProcessError, FileNotFoundError) as e:
            detail = getattr(e, "stderr", str(e))
            raise OSError(f"native loader build failed: {detail}") from e
        with open(_STAMP, "w") as f:
            f.write(want + "\n")
        return _SO


def _load_lib() -> ctypes.CDLL:
    lib = ctypes.CDLL(ensure_built())
    lib.stsh_open.restype = ctypes.c_void_p
    lib.stsh_open.argtypes = [ctypes.c_uint64]
    lib.stsh_add_shard.restype = ctypes.c_int
    lib.stsh_add_shard.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
    lib.stsh_start.restype = ctypes.c_int
    lib.stsh_start.argtypes = [ctypes.c_void_p] + [ctypes.c_int] * 4
    lib.stsh_next.restype = ctypes.c_int
    lib.stsh_next.argtypes = [
        ctypes.c_void_p,
        ctypes.POINTER(ctypes.c_int32),
        ctypes.POINTER(ctypes.c_int32),
    ]
    lib.stsh_total_tokens.restype = ctypes.c_uint64
    lib.stsh_total_tokens.argtypes = [ctypes.c_void_p]
    lib.stsh_last_error.restype = ctypes.c_char_p
    lib.stsh_close.argtypes = [ctypes.c_void_p]
    return lib


class NativeShardReader:
    """Prefetching reader over binary token shards (C++ backend)."""

    def __init__(self, paths: Sequence[str], *, seed: int = 0):
        if not paths:
            raise ValueError("no shard paths given")
        self._lib = _load_lib()
        self._h = self._lib.stsh_open(ctypes.c_uint64(seed))
        self._started = False
        try:
            for p in paths:
                if self._lib.stsh_add_shard(self._h, os.fsencode(p)):
                    raise ValueError(
                        self._lib.stsh_last_error().decode(errors="replace")
                    )
        except Exception:
            self.close()
            raise

    @property
    def total_tokens(self) -> int:
        return int(self._lib.stsh_total_tokens(self._h))

    def batches(
        self,
        *,
        batch_size: int,
        seq_len: int,
        num_batches: Optional[int] = None,
        queue_depth: int = 4,
        num_threads: int = 2,
    ) -> Iterator[dict]:
        if self._h is None:
            raise RuntimeError("reader is closed")
        if self._started:
            raise RuntimeError("batches() may only be called once per reader")
        if self._lib.stsh_start(
            self._h, batch_size, seq_len, queue_depth, num_threads
        ):
            raise ValueError(
                self._lib.stsh_last_error().decode(errors="replace")
            )
        self._started = True
        produced = 0
        try:
            while num_batches is None or produced < num_batches:
                inputs = np.empty((batch_size, seq_len), np.int32)
                targets = np.empty((batch_size, seq_len), np.int32)
                rc = self._lib.stsh_next(
                    self._h,
                    inputs.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
                    targets.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
                )
                if rc:
                    return
                yield {"inputs": inputs, "targets": targets}
                produced += 1
        finally:
            self.close()

    def close(self) -> None:
        if getattr(self, "_h", None) is not None:
            self._lib.stsh_close(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass
