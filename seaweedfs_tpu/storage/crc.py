"""Needle checksum: CRC32-Castagnoli with the masked-value transform.

The reference computes crc32c over the needle Data and stores
``value = rotr15(crc) + 0xa282ead8`` (weed/storage/needle/crc.go:24-26) —
the same masking scheme leveldb/snappy use so that CRCs of CRCs stay
well-distributed.  Uses google_crc32c (hardware SSE4.2) with a pure-python
table fallback.

The EC sidecar's block crcs (block_crcs) run in the native data plane
library where it loads: one call a buffer, on its own address, with no
copy and the GIL released, so threads checksum in parallel.  This module
owns that choice; block_crc_path() names the path taken.
"""

from __future__ import annotations

import ctypes
from typing import Optional

import numpy as np

try:
    import google_crc32c

    _FALLBACK = "google_crc32c"

    def crc32c(data, initial: int = 0) -> int:
        return google_crc32c.extend(initial, bytes(data))

except ImportError:  # pragma: no cover - fallback for exotic environments
    _FALLBACK = "python"
    _POLY = 0x82F63B78  # reversed Castagnoli
    _TABLE = []
    for _i in range(256):
        _c = _i
        for _ in range(8):
            _c = (_c >> 1) ^ _POLY if _c & 1 else _c >> 1
        _TABLE.append(_c)

    def crc32c(data, initial: int = 0) -> int:
        c = initial ^ 0xFFFFFFFF
        for b in bytes(data):
            c = _TABLE[(c ^ b) & 0xFF] ^ (c >> 8)
        return c ^ 0xFFFFFFFF


def masked_value(crc: int) -> int:
    """crc.go:24-26: uint32(c>>15|c<<17) + 0xa282ead8."""
    crc &= 0xFFFFFFFF
    rot = ((crc >> 15) | (crc << 17)) & 0xFFFFFFFF
    return (rot + 0xA282EAD8) & 0xFFFFFFFF


def needle_checksum(data) -> int:
    """The u32 stored after the needle body."""
    return masked_value(crc32c(data))


_blocks = None  # the native crc32c_blocks, or False where it is missing


def load_block_crc() -> str:
    """Load the native block crc, once a process, building the data
    plane library where it is stale: at an encode's start, never inside
    one.  Returns block_crc_path()."""
    global _blocks
    if _blocks is None:
        from ..native import load_dataplane_lib

        lib = load_dataplane_lib()
        fn = getattr(lib, "crc32c_blocks", None)
        if fn is not None:
            fn.restype = ctypes.c_size_t
            fn.argtypes = [ctypes.POINTER(ctypes.c_uint32), ctypes.c_size_t,
                           ctypes.c_size_t, ctypes.c_void_p, ctypes.c_size_t,
                           ctypes.c_void_p]
        _blocks = fn or False
    return block_crc_path()


def block_crc_path() -> str:
    """The path block_crcs takes: "native", or where the library is not
    loaded the crc32c() its caller falls back to ("google_crc32c" or
    "python")."""
    return "native" if _blocks else _FALLBACK


def block_crcs(data: memoryview, run: int, fill: int,
               block: int) -> Optional[tuple[list[int], int]]:
    """Feed contiguous bytes into a stream of `block`-byte blocks whose
    open block holds `fill` bytes with crc32c `run`: the masked crc of
    every block the bytes close, and the crc32c of the block left open.
    None where the native library is not loaded."""
    fn = _blocks
    if not fn:
        return None
    out = np.empty((fill + len(data)) // block, np.uint32)
    crc = ctypes.c_uint32(run)
    closed = fn(ctypes.byref(crc), fill, block,
                np.frombuffer(data, np.uint8).ctypes.data, len(data),
                out.ctypes.data)
    return out[:closed].tolist(), crc.value
