"""ctypes bindings for the native host components.

Builds ``libhgtpu_native.<hash>.so`` from the C++ sources on first use
(g++ -O3, cached next to the sources and keyed by a hash of their
contents, so a binary built from other sources is never loaded) and
exposes:

- ``radius_graph_native`` / ``radius_graph_pbc_native`` — cell-list
  neighbor builders (vesin replacement, see celllist.cpp);
- ``SampleStore`` — packed record store with optional POSIX shared
  memory (DDStore / Adios-shmem replacement, see samplestore.cpp).

``available()`` reports whether the native library could be built;
callers fall back to the numpy implementations in
hydragnn_tpu/ops/neighbors.py when it is False.
"""

from __future__ import annotations

import ctypes
import glob
import hashlib
import os
import subprocess
import threading
from typing import Optional, Tuple

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
_LOCK = threading.Lock()
_LIB: Optional[ctypes.CDLL] = None
_BUILD_FAILED = False

#: C++ sentinel: geometry unsupported by the native path (fall back).
UNSUPPORTED = -(2**63)


class NativeUnsupported(Exception):
    """The native kernel declined this input; use the numpy fallback."""


def _build() -> Optional[ctypes.CDLL]:
    sources = [
        os.path.join(_HERE, "celllist.cpp"),
        os.path.join(_HERE, "samplestore.cpp"),
    ]
    digest = hashlib.sha256()
    for src in sources:
        with open(src, "rb") as f:
            digest.update(f.read())
    out = os.path.join(
        _HERE, f"libhgtpu_native.{digest.hexdigest()[:16]}.so"
    )
    if not os.path.exists(out):
        # Compile to a per-process temp path and atomically rename so
        # concurrent processes never load a half-written library. No
        # -march=native: the cached .so may travel to a different CPU
        # (container image, NFS) where newer ISA extensions SIGILL.
        tmp = f"{out}.{os.getpid()}.tmp"
        cmd = [
            "g++",
            "-O3",
            "-shared",
            "-fPIC",
            "-std=c++17",
            *sources,
            "-o",
            tmp,
        ]
        try:
            subprocess.run(
                cmd, check=True, capture_output=True, timeout=120
            )
            os.replace(tmp, out)
        except (OSError, subprocess.SubprocessError):
            try:
                os.unlink(tmp)
            except OSError:
                pass
            return None
        # binaries of other sources are dead weight next to this one
        for stale in glob.glob(os.path.join(_HERE, "libhgtpu_native*.so")):
            if stale != out:
                try:
                    os.unlink(stale)
                except OSError:
                    pass
    try:
        lib = ctypes.CDLL(out)
    except OSError:
        return None

    i64 = ctypes.c_int64
    p_d = np.ctypeslib.ndpointer(np.float64, flags="C_CONTIGUOUS")
    p_i = np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS")
    p_u8 = np.ctypeslib.ndpointer(np.uint8, flags="C_CONTIGUOUS")

    lib.hgtpu_radius_graph.restype = i64
    lib.hgtpu_radius_graph.argtypes = [
        p_d, i64, ctypes.c_double, i64, p_i, p_i,
    ]
    lib.hgtpu_radius_graph_pbc.restype = i64
    lib.hgtpu_radius_graph_pbc.argtypes = [
        p_d, i64, p_d, p_u8, ctypes.c_double, i64, p_i, p_i, p_d,
    ]
    lib.hgtpu_store_create.restype = ctypes.c_void_p
    lib.hgtpu_store_create.argtypes = [i64, i64, ctypes.c_char_p]
    lib.hgtpu_store_attach.restype = ctypes.c_void_p
    lib.hgtpu_store_attach.argtypes = [ctypes.c_char_p]
    lib.hgtpu_store_put.restype = i64
    lib.hgtpu_store_put.argtypes = [
        ctypes.c_void_p, i64, ctypes.c_char_p, i64,
    ]
    lib.hgtpu_store_num_records.restype = i64
    lib.hgtpu_store_num_records.argtypes = [ctypes.c_void_p]
    lib.hgtpu_store_record_size.restype = i64
    lib.hgtpu_store_record_size.argtypes = [ctypes.c_void_p, i64]
    lib.hgtpu_store_get.restype = ctypes.c_void_p
    lib.hgtpu_store_get.argtypes = [
        ctypes.c_void_p, i64, ctypes.POINTER(i64),
    ]
    lib.hgtpu_store_close.restype = None
    lib.hgtpu_store_close.argtypes = [ctypes.c_void_p]
    return lib


def _lib() -> Optional[ctypes.CDLL]:
    global _LIB, _BUILD_FAILED
    if _LIB is None and not _BUILD_FAILED:
        with _LOCK:
            if _LIB is None and not _BUILD_FAILED:
                _LIB = _build()
                if _LIB is None:
                    _BUILD_FAILED = True
    return _LIB


def available() -> bool:
    return _lib() is not None


def radius_graph_native(
    pos: np.ndarray, radius: float, capacity_hint: int = 0
) -> np.ndarray:
    """edge_index [2, E] via the C++ cell list; grows capacity on demand."""
    lib = _lib()
    assert lib is not None
    pos = np.ascontiguousarray(pos, np.float64)
    n = pos.shape[0]
    cap = capacity_hint if capacity_hint > 0 else max(32 * n, 64)
    while True:
        snd = np.empty(cap, np.int64)
        rcv = np.empty(cap, np.int64)
        got = lib.hgtpu_radius_graph(pos, n, float(radius), cap, snd, rcv)
        if got == UNSUPPORTED:
            raise NativeUnsupported("geometry too sparse for dense bins")
        if got >= 0:
            return np.stack([snd[:got], rcv[:got]])
        cap = -got


def radius_graph_pbc_native(
    pos: np.ndarray,
    cell: np.ndarray,
    radius: float,
    pbc: Tuple[bool, bool, bool] = (True, True, True),
    capacity_hint: int = 0,
) -> Tuple[np.ndarray, np.ndarray]:
    """(edge_index [2, E], shift vectors [E, 3]) via the C++ cell list."""
    lib = _lib()
    assert lib is not None
    pos = np.ascontiguousarray(pos, np.float64)
    cell = np.ascontiguousarray(np.asarray(cell).reshape(3, 3), np.float64)
    flags = np.asarray([1 if p else 0 for p in pbc], np.uint8)
    n = pos.shape[0]
    cap = capacity_hint if capacity_hint > 0 else max(64 * n, 64)
    while True:
        snd = np.empty(cap, np.int64)
        rcv = np.empty(cap, np.int64)
        sh = np.empty((cap, 3), np.float64)
        got = lib.hgtpu_radius_graph_pbc(
            pos, n, cell, flags, float(radius), cap, snd, rcv, sh
        )
        if got == UNSUPPORTED:
            raise NativeUnsupported("degenerate cell / image explosion")
        if got >= 0:
            return np.stack([snd[:got], rcv[:got]]), sh[:got]
        cap = -got


class SampleStore:
    """Packed record store; optionally shared across local processes.

    Owner: ``SampleStore(sizes, shm_name=...)`` then ``put`` each record
    in order. Readers in sibling processes: ``SampleStore.attach(name)``.
    ``get`` returns the record bytes (copied out of the region).
    """

    def __init__(
        self,
        record_sizes,
        shm_name: Optional[str] = None,
    ):
        lib = _lib()
        if lib is None:
            raise RuntimeError("native library unavailable")
        self._lib = lib
        sizes = [int(s) for s in record_sizes]
        self._handle = lib.hgtpu_store_create(
            len(sizes),
            int(sum(sizes)),
            shm_name.encode() if shm_name else None,
        )
        if not self._handle:
            raise RuntimeError("store creation failed (name in use?)")

    @classmethod
    def attach(cls, shm_name: str) -> "SampleStore":
        obj = cls.__new__(cls)
        lib = _lib()
        if lib is None:
            raise RuntimeError("native library unavailable")
        obj._lib = lib
        obj._handle = lib.hgtpu_store_attach(shm_name.encode())
        if not obj._handle:
            raise RuntimeError(f"cannot attach shm store {shm_name!r}")
        return obj

    def put(self, i: int, data: bytes) -> None:
        got = self._lib.hgtpu_store_put(self._handle, i, data, len(data))
        if got < 0:
            raise ValueError(f"store_put failed for record {i}: {got}")

    def __len__(self) -> int:
        return int(self._lib.hgtpu_store_num_records(self._handle))

    def get(self, i: int) -> bytes:
        nbytes = ctypes.c_int64()
        ptr = self._lib.hgtpu_store_get(
            self._handle, i, ctypes.byref(nbytes)
        )
        if not ptr:
            raise IndexError(i)
        return ctypes.string_at(ptr, nbytes.value)

    def close(self) -> None:
        if getattr(self, "_handle", None):
            self._lib.hgtpu_store_close(self._handle)
            self._handle = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass
