"""Plain RS(10,4) over GF(2^8), with upstream SeaweedFS's shard layout.

The yardstick of the benchmark's checks.  It is written from the public
descriptions and imports nothing of ``seaweedfs_tpu``:

- the field: GF(2^8) with the polynomial x^8+x^4+x^3+x^2+1 (0x11d) and
  generator 2, as in klauspost/reedsolomon (galois.go);
- the code: klauspost's systematic matrix, a Vandermonde matrix times
  the inverse of its top square (matrix.go ``buildMatrix``), which
  upstream uses with 10 data and 4 parity shards
  (weed/storage/erasure_coding/ec_encoder.go:17-23);
- the layout: ``encodeDatFile`` (ec_encoder.go:194-231) stripes the
  ``.dat`` over 10 shards in rows of 1 GiB blocks while more than ten
  large blocks remain, then in rows of 1 MiB blocks, zero-padding the
  last row;
- the sorted index: ``WriteSortedFileFromIdx`` (ec_encoder.go:27-54)
  writes the ``.idx`` entries that are live at the end, in ascending
  key order, 16 bytes each.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

POLY = 0x11D
K, M = 10, 4
LARGE_BLOCK = 1 << 30
SMALL_BLOCK = 1 << 20


def _tables() -> tuple[np.ndarray, np.ndarray]:
    exp = np.zeros(512, dtype=np.int64)
    log = np.zeros(256, dtype=np.int64)
    x = 1
    for i in range(255):
        exp[i] = x
        log[x] = i
        x <<= 1
        if x & 0x100:
            x ^= POLY
    exp[255:510] = exp[:255]
    return exp, log


EXP, LOG = _tables()


def gf_mul(a: int, b: int) -> int:
    if a == 0 or b == 0:
        return 0
    return int(EXP[LOG[a] + LOG[b]])


def gf_inv(a: int) -> int:
    if a == 0:
        raise ZeroDivisionError("0 has no inverse in GF(2^8)")
    return int(EXP[255 - LOG[a]])


def gf_pow(a: int, n: int) -> int:
    if n == 0:
        return 1
    if a == 0:
        return 0
    return int(EXP[(LOG[a] * n) % 255])


# MUL[a, b] = a * b in the field
_nz = np.arange(1, 256)
MUL = np.zeros((256, 256), dtype=np.uint8)
MUL[1:, 1:] = EXP[(LOG[_nz][:, None] + LOG[_nz][None, :])]


def mat_mul(a: list[list[int]], b: list[list[int]]) -> list[list[int]]:
    out = []
    for row in a:
        acc = [0] * len(b[0])
        for k, v in enumerate(row):
            if v:
                for j, w in enumerate(b[k]):
                    acc[j] ^= gf_mul(v, w)
        out.append(acc)
    return out


def mat_inv(m: list[list[int]]) -> list[list[int]]:
    """Gauss-Jordan over GF(2^8); raises on a singular matrix."""
    n = len(m)
    a = [list(row) + [int(i == j) for j in range(n)] for i, row in enumerate(m)]
    for c in range(n):
        p = next((r for r in range(c, n) if a[r][c]), None)
        if p is None:
            raise ValueError("singular matrix")
        a[c], a[p] = a[p], a[c]
        inv = gf_inv(a[c][c])
        a[c] = [gf_mul(inv, v) for v in a[c]]
        for r in range(n):
            if r != c and a[r][c]:
                f = a[r][c]
                a[r] = [v ^ gf_mul(f, w) for v, w in zip(a[r], a[c])]
    return [row[n:] for row in a]


def encoding_matrix(k: int = K, n: int = K + M) -> list[list[int]]:
    """klauspost buildMatrix: vandermonde[r][c] = r^c, times the inverse
    of its top k x k square, so the top k rows are the identity."""
    vand = [[gf_pow(r, c) for c in range(k)] for r in range(n)]
    return mat_mul(vand, mat_inv(vand[:k]))


MATRIX = encoding_matrix()


def _tab16(c: int) -> np.ndarray:
    """c * x for both bytes of a little-endian uint16 pair."""
    lo = MUL[c].astype(np.uint16)
    return (lo[:, None] << 8 | lo[None, :]).reshape(-1)


_TAB16: dict[int, np.ndarray] = {}


def gf_matmul(m: list[list[int]], data: np.ndarray,
              chunk: int = 1 << 20) -> np.ndarray:
    """out[R, B] = m[R, K] . data[K, B] over GF(2^8), uint8.  Column
    chunks run on a few threads (numpy's take releases the GIL)."""
    data = np.asarray(data, dtype=np.uint8)
    k, b = data.shape
    if b % 2:
        data = np.concatenate([data, np.zeros((k, 1), np.uint8)], axis=1)
    out = np.zeros((len(m), data.shape[1]), dtype=np.uint8)
    for row in m:
        for c in row:
            if c not in _TAB16:
                _TAB16[c] = _tab16(c)

    def part(lo: int, hi: int) -> None:
        for r, row in enumerate(m):
            acc = out[r, lo:hi].view(np.uint16)
            for j, c in enumerate(row):
                if c:
                    acc ^= np.take(_TAB16[c], data[j, lo:hi].view(np.uint16))

    spans = [(lo, min(lo + chunk, data.shape[1]))
             for lo in range(0, data.shape[1], chunk)]
    if len(spans) == 1:
        part(*spans[0])
    else:
        with ThreadPoolExecutor(os.cpu_count() or 1) as ex:
            for f in [ex.submit(part, lo, hi) for lo, hi in spans]:
                f.result()
    return out[:, :b]


def encode(data: np.ndarray) -> np.ndarray:
    """data[10, B] -> parity[4, B]."""
    return gf_matmul(MATRIX[K:], data)


def reconstruct(shards: dict[int, np.ndarray], want: list[int]) -> dict:
    """{shard id: bytes} for the `want` ids, from any 10 survivors."""
    have = sorted(shards)[:K]
    if len(have) < K:
        raise ValueError("fewer than 10 survivors")
    decode = mat_inv([MATRIX[i] for i in have])
    rows = mat_mul([MATRIX[i] for i in want], decode)
    out = gf_matmul(rows, np.stack([shards[i] for i in have]))
    return {w: out[n] for n, w in enumerate(want)}


# --- layout -----------------------------------------------------------

def rows(dat_size: int, large: int = LARGE_BLOCK,
         small: int = SMALL_BLOCK) -> list[tuple[int, int, int]]:
    """(dat offset, block size, shard offset) of every row."""
    out, dat_off, shard_off, remaining = [], 0, 0, dat_size
    while remaining > large * K:
        out.append((dat_off, large, shard_off))
        dat_off += large * K
        shard_off += large
        remaining -= large * K
    while remaining > 0:
        out.append((dat_off, small, shard_off))
        dat_off += small * K
        shard_off += small
        remaining -= small * K
    return out


def shard_size(dat_size: int, large: int = LARGE_BLOCK,
               small: int = SMALL_BLOCK) -> int:
    r = rows(dat_size, large, small)
    return r[-1][2] + r[-1][1] if r else 0


def shard_of(dat_size: int, offset, large: int = LARGE_BLOCK,
             small: int = SMALL_BLOCK) -> np.ndarray:
    """The data shard that holds .dat byte `offset` (an int or array)."""
    n_large = sum(1 for r in rows(dat_size, large, small) if r[1] == large)
    region = n_large * large * K
    o = np.asarray(offset, dtype=np.int64)
    return np.where(o < region, (o % (large * K)) // large,
                    ((o - region) % (small * K)) // small)


def data_columns(dat: np.ndarray, offset: int, length: int,
                 large: int = LARGE_BLOCK,
                 small: int = SMALL_BLOCK) -> np.ndarray:
    """The 10 data shards' bytes at shard offset [offset, offset+length):
    [10, length] uint8, zero past the end of `dat` (a uint8 array)."""
    out = np.zeros((K, length), dtype=np.uint8)
    for dat_off, block, shard_off in rows(len(dat), large, small):
        lo, hi = max(offset, shard_off), min(offset + length, shard_off + block)
        if lo >= hi:
            continue
        for i in range(K):
            src = dat_off + i * block + (lo - shard_off)
            piece = dat[src:src + (hi - lo)]
            out[i, lo - offset:lo - offset + len(piece)] = piece
    return out


def shard_columns(dat: np.ndarray, offset: int, length: int,
                  large: int = LARGE_BLOCK,
                  small: int = SMALL_BLOCK) -> np.ndarray:
    """All 14 shards' bytes at [offset, offset+length): [14, length]."""
    data = data_columns(dat, offset, length, large, small)
    return np.concatenate([data, encode(data)])


def sorted_index(idx: bytes) -> bytes:
    """The .ecx that WriteSortedFileFromIdx makes from an .idx: entries
    (key u64 | offset u32 | size u32, big-endian) live at the end, in
    ascending key order.  A zero offset or a negative size deletes."""
    e = np.frombuffer(idx, dtype=[("key", ">u8"), ("off", ">u4"),
                                  ("size", ">i4")])
    live: dict[int, tuple[int, int]] = {}
    for key, off, size in e.tolist():
        if off == 0 or size < 0:
            live.pop(key, None)
        else:
            live[key] = (off, size)
    out = np.zeros(len(live), dtype=e.dtype)
    for n, key in enumerate(sorted(live)):
        out[n] = (key, *live[key])
    return out.tobytes()
