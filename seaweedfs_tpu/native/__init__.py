"""ctypes loaders for the native libraries: the GF(2^8) SIMD library,
the LSM KV and the volume server's data plane (whose crc32c the EC
sidecar uses too).

Each builds lazily with make on first load (cached as lib*.so); callers
fall back to their Python paths when no C++ toolchain is available.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading

import numpy as np

_DIR = os.path.dirname(os.path.abspath(__file__))
_LIB_PATH = os.path.join(_DIR, "libgf256.so")
_lib = None


def _build() -> bool:
    try:
        subprocess.run(["make", "-s", "-C", _DIR], check=True,
                       capture_output=True, timeout=120)
        return os.path.exists(_LIB_PATH)
    except Exception:
        return False


def load():
    """Returns the ctypes lib or None if unavailable."""
    global _lib
    if _lib is not None:
        return _lib
    src = os.path.join(_DIR, "gf256_simd.cpp")
    if not os.path.exists(_LIB_PATH) or (
        os.path.exists(src) and os.path.getmtime(src) > os.path.getmtime(_LIB_PATH)
    ):
        if not _build():
            return None
    try:
        lib = ctypes.CDLL(_LIB_PATH)
    except OSError:
        return None
    lib.gf_init.argtypes = [ctypes.c_char_p]
    lib.gf_matmul.argtypes = [
        ctypes.POINTER(ctypes.c_uint8), ctypes.c_int, ctypes.c_int,
        ctypes.POINTER(ctypes.c_uint8), ctypes.POINTER(ctypes.c_uint8),
        ctypes.c_long,
    ]
    lib.gf_matmul_ptrs.argtypes = [
        ctypes.POINTER(ctypes.c_uint8), ctypes.c_int, ctypes.c_int,
        ctypes.POINTER(ctypes.c_void_p), ctypes.POINTER(ctypes.c_void_p),
        ctypes.c_long,
    ]
    lib.gf_has_avx2.restype = ctypes.c_int
    lib.gf_has_gfni.restype = ctypes.c_int

    from ..ec.gf256 import MUL_TABLE

    lib.gf_init(MUL_TABLE.tobytes())
    _lib = lib
    return lib


def has_avx2() -> bool:
    lib = load()
    return bool(lib and lib.gf_has_avx2())


def has_gfni() -> bool:
    lib = load()
    return bool(lib and lib.gf_has_gfni())


# --- data plane (dataplane.cpp) --------------------------------------------

_DP_LIB_PATH = os.path.join(_DIR, "libdataplane.so")
_dp_lib = None
_dp_lock = threading.Lock()


def load_dataplane_lib():
    """The data plane library, built where it is missing or older than
    its source, or None where it cannot be built or loaded.  One load a
    process, under a lock that serializes concurrent builders; callers
    declare the argtypes of the functions they call."""
    global _dp_lib
    with _dp_lock:
        if _dp_lib is None:
            src = os.path.join(_DIR, "dataplane.cpp")
            if not os.path.exists(_DP_LIB_PATH) or (
                    os.path.exists(src)
                    and os.path.getmtime(src) > os.path.getmtime(_DP_LIB_PATH)):
                if not _build():  # weedlint: lock-io one-time native build at first load; the lock exists precisely to serialize concurrent builders, and the make is timeout-bounded
                    return None
            try:
                _dp_lib = ctypes.CDLL(_DP_LIB_PATH)
            except OSError:
                return None
        return _dp_lib


# --- native LSM KV (lsmkv.cpp) ----------------------------------------------

_LSM_LIB_PATH = os.path.join(_DIR, "liblsmkv.so")
_lsm_lib = None


def load_lsm():
    """Returns the lsmkv ctypes lib or None if unavailable."""
    global _lsm_lib
    if _lsm_lib is not None:
        return _lsm_lib
    src = os.path.join(_DIR, "lsmkv.cpp")
    if not os.path.exists(_LSM_LIB_PATH) or (
        os.path.exists(src)
        and os.path.getmtime(src) > os.path.getmtime(_LSM_LIB_PATH)
    ):
        if not _build():
            return None
    try:
        lib = ctypes.CDLL(_LSM_LIB_PATH)
    except OSError:
        return None
    u8p = ctypes.POINTER(ctypes.c_uint8)
    # inputs are c_char_p: Python bytes pass by pointer with NO copy
    # (length travels separately, so embedded NULs are fine)
    lib.lsm_open.argtypes = [ctypes.c_char_p, ctypes.c_int, ctypes.c_int]
    lib.lsm_open.restype = ctypes.c_void_p
    lib.lsm_close.argtypes = [ctypes.c_void_p]
    lib.lsm_put.argtypes = [ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int,
                            ctypes.c_char_p, ctypes.c_long]
    lib.lsm_delete.argtypes = [ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int]
    lib.lsm_get.argtypes = [ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int,
                            ctypes.POINTER(u8p)]
    lib.lsm_get.restype = ctypes.c_long
    lib.lsm_free.argtypes = [u8p]
    lib.lsm_flush.argtypes = [ctypes.c_void_p]
    lib.lsm_scan.argtypes = [ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int]
    lib.lsm_scan.restype = ctypes.c_void_p
    lib.lsm_scan_next.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(u8p), ctypes.POINTER(ctypes.c_int),
        ctypes.POINTER(u8p), ctypes.POINTER(ctypes.c_long)]
    lib.lsm_scan_next.restype = ctypes.c_int
    lib.lsm_scan_close.argtypes = [ctypes.c_void_p]
    _lsm_lib = lib
    return lib


class NativeKv:
    """Thin pythonic handle over the C++ LSM (byte-format compatible with
    filer/lsm_store.py — the two engines open each other's directories)."""

    def __init__(self, directory: str, memtable_limit: int = 8192,
                 compact_trigger: int = 8):
        lib = load_lsm()
        if lib is None:
            raise RuntimeError("native lsmkv library unavailable")
        self._lib = lib
        self._db = lib.lsm_open(directory.encode(), memtable_limit,
                                compact_trigger)
        if not self._db:
            raise OSError(f"lsm_open failed for {directory!r}")

    def put(self, key: bytes, value: bytes) -> None:
        self._lib.lsm_put(self._db, key, len(key), value, len(value))

    def get(self, key: bytes):
        out = ctypes.POINTER(ctypes.c_uint8)()
        n = self._lib.lsm_get(self._db, key, len(key), ctypes.byref(out))
        if n < 0:
            return None
        try:
            return ctypes.string_at(out, n)
        finally:
            self._lib.lsm_free(out)

    def delete(self, key: bytes) -> None:
        self._lib.lsm_delete(self._db, key, len(key))

    def scan(self, prefix: bytes):
        it = self._lib.lsm_scan(self._db, prefix, len(prefix))
        try:
            kp = ctypes.POINTER(ctypes.c_uint8)()
            vp = ctypes.POINTER(ctypes.c_uint8)()
            klen = ctypes.c_int()
            vlen = ctypes.c_long()
            while self._lib.lsm_scan_next(it, ctypes.byref(kp),
                                          ctypes.byref(klen),
                                          ctypes.byref(vp),
                                          ctypes.byref(vlen)):
                yield (ctypes.string_at(kp, klen.value),
                       ctypes.string_at(vp, vlen.value))
        finally:
            self._lib.lsm_scan_close(it)

    def flush(self) -> None:
        self._lib.lsm_flush(self._db)

    def close(self) -> None:
        if self._db:
            self._lib.lsm_close(self._db)
            self._db = None


def gf_matmul_ptrs(mat: np.ndarray, in_addrs, out_addrs, n: int) -> None:
    """Row-pointer matmul: in_addrs/out_addrs are raw addresses (ints) of
    K input and R output rows of n bytes each — typically straight into
    mmap'd files, making the matmul itself the only data movement."""
    lib = load()
    if lib is None:
        raise RuntimeError("native gf256 library unavailable")
    mat = np.ascontiguousarray(mat, dtype=np.uint8)
    r, k = mat.shape
    ins = (ctypes.c_void_p * k)(*in_addrs)
    outs = (ctypes.c_void_p * r)(*out_addrs)
    lib.gf_matmul_ptrs(mat.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
                       r, k, ins, outs, ctypes.c_long(n))


def gf_matmul(mat: np.ndarray, data: np.ndarray) -> np.ndarray:
    """out[R, B] = mat[R, K] . data[K, B] over GF(2^8) via the native lib."""
    lib = load()
    if lib is None:
        raise RuntimeError("native gf256 library unavailable")
    mat = np.ascontiguousarray(mat, dtype=np.uint8)
    data = np.ascontiguousarray(data, dtype=np.uint8)
    r, k = mat.shape
    n = data.shape[1]
    out = np.empty((r, n), dtype=np.uint8)
    u8p = ctypes.POINTER(ctypes.c_uint8)
    lib.gf_matmul(mat.ctypes.data_as(u8p), r, k,
                  data.ctypes.data_as(u8p), out.ctypes.data_as(u8p),
                  ctypes.c_long(n))
    return out
