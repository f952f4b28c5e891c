"""ctypes bridge to the native data-pipeline library (native/src/).

Compiles ``data_native.cpp`` with g++ on first use (cached under
``native/build/`` by a hash of the source, so a copied tree can never
load a binary built from another version of it) and exposes:

  - ``permute_indices(n, seed, start, count)`` — a window of the seeded
    O(1)-memory Feistel permutation of [0, n),
  - ``gather_windows(tokens, offsets, block)`` — threaded host-side
    stride-1 window gather (train.py:104-107 semantics).

When no C++ toolchain is available the same Feistel construction runs as
vectorized numpy (bit-identical by design — the tests assert it), so
framework behavior never depends on the native build succeeding.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from pathlib import Path
from typing import Optional

import numpy as np

_REPO_ROOT = Path(__file__).resolve().parents[2]
_SRC = _REPO_ROOT / "native" / "src" / "data_native.cpp"
_BUILD_DIR = _REPO_ROOT / "native" / "build"

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_load_failed = False


def _lib_path() -> Path:
    """The library for THIS source: the name carries the source's hash
    (mtimes do not survive a copy of the tree in order)."""
    digest = hashlib.sha256(_SRC.read_bytes()).hexdigest()[:16]
    return _BUILD_DIR / f"libdata_native.{digest}.so"


def _compile(lib_path: Path) -> bool:
    _BUILD_DIR.mkdir(parents=True, exist_ok=True)
    # compile to a process-unique temp path and rename atomically: the
    # threading lock is per-process, and concurrent jobs on one checkout
    # must never dlopen a half-written .so
    tmp = _BUILD_DIR / f".libdata_native.{os.getpid()}.so"
    cmd = [
        "g++", "-O3", "-std=c++17", "-shared", "-fPIC", "-pthread",
        str(_SRC), "-o", str(tmp),
    ]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        os.replace(tmp, lib_path)
        return True
    except (OSError, subprocess.SubprocessError):
        tmp.unlink(missing_ok=True)
        return False


def _load() -> Optional[ctypes.CDLL]:
    """Compile (if not built for this source yet) and load the shared
    library; None on failure."""
    global _lib, _load_failed
    with _lock:
        if _lib is not None or _load_failed:
            return _lib
        try:
            lib_path = _lib_path()
            if not lib_path.exists() and not _compile(lib_path):
                _load_failed = True
                return None
            lib = ctypes.CDLL(str(lib_path))
            lib.permute_indices.argtypes = [
                ctypes.c_uint64, ctypes.c_uint64, ctypes.c_uint64,
                ctypes.c_uint64, ctypes.POINTER(ctypes.c_int64),
            ]
            lib.gather_windows.argtypes = [
                ctypes.POINTER(ctypes.c_int32), ctypes.c_uint64,
                ctypes.POINTER(ctypes.c_int64), ctypes.c_uint64,
                ctypes.c_uint64, ctypes.POINTER(ctypes.c_int32),
                ctypes.POINTER(ctypes.c_int32),
            ]
            _lib = lib
        except OSError:
            _load_failed = True
        return _lib


def native_available() -> bool:
    return _load() is not None


# ---------------------------------------------------------------------------
# numpy mirror of the C++ Feistel (bit-identical; tests assert parity)
# ---------------------------------------------------------------------------

_U64 = np.uint64


def _mix64(x: np.ndarray) -> np.ndarray:
    with np.errstate(over="ignore"):
        x = (x + _U64(0x9E3779B97F4A7C15)).astype(_U64)
        x = ((x ^ (x >> _U64(30))) * _U64(0xBF58476D1CE4E5B9)).astype(_U64)
        x = ((x ^ (x >> _U64(27))) * _U64(0x94D049BB133111EB)).astype(_U64)
        return x ^ (x >> _U64(31))


def _feistel_params(n: int):
    bits = 1
    while (1 << bits) < n and bits < 62:
        bits += 1
    half_bits = (bits + 1) // 2
    return half_bits, (1 << half_bits) - 1


def _cipher_np(x: np.ndarray, seed: int, half_bits: int, half_mask: int):
    l = x >> _U64(half_bits)
    r = x & _U64(half_mask)
    for rnd in range(4):
        f = _mix64(r ^ _U64(seed) ^ (_U64(rnd) << _U64(56))) & _U64(half_mask)
        l, r = r, l ^ f
    return (l << _U64(half_bits)) | r


def _permute_np(n: int, seed: int, start: int, count: int) -> np.ndarray:
    seed = int(_mix64(np.array(seed, _U64)))
    half_bits, half_mask = _feistel_params(n)
    x = np.arange(start, start + count, dtype=_U64)
    x = _cipher_np(x, seed, half_bits, half_mask)
    # cycle-walk stragglers back into [0, n)
    out = (x >= _U64(n))
    while out.any():
        x[out] = _cipher_np(x[out], seed, half_bits, half_mask)
        out = (x >= _U64(n))
    return x.astype(np.int64)


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------


def permute_indices(n: int, seed: int, start: int, count: int) -> np.ndarray:
    """``sigma(start : start+count)`` for the seeded permutation sigma of
    [0, n) — the epoch-exact shuffle at O(1) memory (vs the reference
    DataLoader's O(n) randperm, train.py:184-191)."""
    if count <= 0:
        return np.empty((0,), np.int64)
    if start + count > n:
        raise ValueError(f"window [{start}, {start + count}) exceeds domain {n}")
    lib = _load()
    if lib is None:
        return _permute_np(n, seed, start, count)
    out = np.empty(count, np.int64)
    lib.permute_indices(
        n, seed, start, count,
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
    )
    return out


def gather_windows(tokens: np.ndarray, offsets: np.ndarray, block: int) -> dict:
    """Host-side stride-1 window gather: x[b] = tokens[o:o+block],
    y[b] = tokens[o+1:o+block+1] (train.py:104-107). For corpora kept in
    host RAM; the device-resident path is data/sampler.py."""
    tokens = np.ascontiguousarray(tokens, np.int32)
    offsets = np.ascontiguousarray(offsets, np.int64)
    if offsets.size and (offsets.min() < 0 or offsets.max() + block + 1 > len(tokens)):
        raise ValueError("offsets out of range for the token stream")
    B = len(offsets)
    lib = _load()
    if lib is None:
        pos = offsets[:, None] + np.arange(block + 1)[None, :]
        grab = tokens[pos]
        return {"x": grab[:, :-1].copy(), "y": grab[:, 1:].copy()}
    x = np.empty((B, block), np.int32)
    y = np.empty((B, block), np.int32)
    lib.gather_windows(
        tokens.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)), len(tokens),
        offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)), B, block,
        x.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        y.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
    )
    return {"x": x, "y": y}


class EpochPermutation:
    """Exact epoch-shuffle semantics of the reference's shuffled DataLoader
    (train.py:184-191): every window index appears exactly once per epoch,
    a fresh permutation each epoch, O(1) memory. ``take(count)`` streams
    the next ``count`` indices, rolling epochs as needed."""

    def __init__(self, n: int, seed: int):
        if n <= 0:
            raise ValueError("empty index domain")
        self.n = n
        self.seed = seed
        self.epoch = 0
        self.cursor = 0

    def _epoch_seed(self) -> int:
        return int(_mix64(np.array(self.seed, _U64) ^ _U64(self.epoch)))

    def take(self, count: int) -> np.ndarray:
        parts = []
        remaining = count
        while remaining > 0:
            avail = self.n - self.cursor
            grab = min(avail, remaining)
            parts.append(
                permute_indices(self.n, self._epoch_seed(), self.cursor, grab)
            )
            self.cursor += grab
            remaining -= grab
            if self.cursor == self.n:
                self.cursor = 0
                self.epoch += 1
        return np.concatenate(parts) if len(parts) > 1 else parts[0]
