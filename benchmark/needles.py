"""A volume's needles made from the seed, and written in upstream's format.

Every run makes its volume anew.  The sizes are one fixed set for all
seeds (drawn from the config's own ``size_seed``), in an order the seed
shuffles; the payload bytes come from the seed.  So every seed gives
the same ``.dat`` size and the same work, and different bytes.

The writer puts the needles straight into ``<vid>.dat`` / ``<vid>.idx``
as upstream's volume server lays them out (needle version 3,
weed/storage/needle/needle_read_write.go; super block
weed/storage/super_block/super_block.go; index entries
weed/storage/idx), before the server starts.  A served write of a
million 1 KiB needles costs more set-up than the whole measured window;
reading every needle back through the server proves the format.
"""

from __future__ import annotations

import os
from dataclasses import dataclass

import google_crc32c
import numpy as np

VERSION = 3
HEADER = 16          # cookie u32 | id u64 | size u32
TAIL = 4 + 8         # masked crc32c u32 | append_at_ns u64
APPEND_AT_NS = 1_700_000_000_000_000_000


def rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([seed % (1 << 64), *stream])


@dataclass
class Volume:
    """The seed's needles: ids 1..n, their cookies, sizes and payload
    offsets into one byte stream, and where each record sits in .dat."""
    vid: int
    cookies: np.ndarray   # uint32 [n]
    sizes: np.ndarray     # int64 [n], payload bytes
    starts: np.ndarray    # int64 [n], offset of the payload in `payload`
    offsets: np.ndarray   # int64 [n], offset of the record in .dat
    payload: np.ndarray   # uint8, every needle's bytes back to back
    dat_size: int

    def __len__(self) -> int:
        return len(self.sizes)

    def body(self, i: int) -> memoryview:
        s = int(self.starts[i])
        return memoryview(self.payload)[s:s + int(self.sizes[i])]

    def fid(self, i: int) -> str:
        return "%d,%x%08x" % (self.vid, i + 1, int(self.cookies[i]))

    def idx_bytes(self) -> bytes:
        """The .idx: key u64 | offset/8 u32 | size u32, big-endian."""
        n = len(self)
        idx = np.zeros(n, dtype=[("key", ">u8"), ("off", ">u4"),
                                 ("size", ">u4")])
        idx["key"] = np.arange(1, n + 1)
        idx["off"] = self.offsets // 8
        idx["size"] = 4 + self.sizes + 1
        return idx.tobytes()


def needle_sizes(cfg: dict) -> np.ndarray:
    """The config's fixed set of payload sizes."""
    law = cfg["needle_size"]
    g = np.random.default_rng(cfg["size_seed"])
    if law["law"] == "fixed":
        return np.full(cfg["needles"], law["bytes"], dtype=np.int64)
    if law["law"] == "log_uniform":
        # as chip_smoke.py drew them: 2**U(log2 lo, log2 hi), until the
        # records fill the volume
        lo, hi = np.log2(law["min_bytes"]), np.log2(law["max_bytes"])
        out, total = [], 8
        while total < cfg["volume_bytes"]:
            s = np.exp2(g.uniform(lo, hi, 1024)).astype(np.int64)
            for v in s.tolist():
                out.append(v)
                total += record_size(v)
                if total >= cfg["volume_bytes"]:
                    break
        return np.array(out, dtype=np.int64)
    raise ValueError(f"unknown needle size law {law['law']!r}")


def record_size(size: int) -> int:
    """Bytes of one v3 record with data and no optional fields."""
    body = 4 + size + 1  # data_size u32 | data | flags u8
    pad = 8 - (HEADER + body + TAIL) % 8  # 1..8, never 0
    return HEADER + body + TAIL + pad


def make(cfg: dict, seed: int, vid: int = 1) -> Volume:
    sizes = needle_sizes(cfg)
    sizes = sizes[rng(seed, 1).permutation(len(sizes))]
    n = len(sizes)
    starts = np.concatenate([[0], np.cumsum(sizes)[:-1]]).astype(np.int64)
    rec = np.array([record_size(int(s)) for s in sizes], dtype=np.int64) \
        if len(np.unique(sizes)) > 1 else np.full(n, record_size(int(sizes[0])))
    offsets = 8 + np.concatenate([[0], np.cumsum(rec)[:-1]]).astype(np.int64)
    payload = rng(seed, 2).integers(0, 256, int(sizes.sum()), dtype=np.uint8)
    cookies = rng(seed, 3).integers(0, 1 << 32, n, dtype=np.uint32)
    return Volume(vid, cookies, sizes, starts, offsets, payload,
                  int(offsets[-1] + rec[-1]))


def be32(a) -> np.ndarray:
    return np.asarray(a, ">u4").reshape(-1).view(np.uint8).reshape(-1, 4)


def be64(a) -> np.ndarray:
    return np.asarray(a, ">u8").reshape(-1).view(np.uint8).reshape(-1, 8)


def write(vol: Volume, directory: str) -> np.ndarray:
    """<vid>.dat and <vid>.idx in `directory`; returns the .dat bytes."""
    n = len(vol)
    sizes = vol.sizes
    body = 4 + sizes + 1
    crc = np.fromiter((google_crc32c.value(vol.body(i).tobytes())
                       for i in range(n)), dtype=np.uint64, count=n)
    # crc.go: uint32(c>>15 | c<<17) + 0xa282ead8
    masked = ((((crc >> 15) | (crc << 17)) & 0xFFFFFFFF) + 0xA282EAD8) \
        & 0xFFFFFFFF
    dat_path = os.path.join(directory, f"{vol.vid}.dat")
    idx_path = os.path.join(directory, f"{vol.vid}.idx")
    out = np.zeros(vol.dat_size, dtype=np.uint8)
    out[0] = VERSION  # super block: version, replication 000, no ttl
    if len(np.unique(sizes)) == 1:
        # every record the same length: lay them out as one 2-D array
        rec = out[8:].reshape(n, -1)
        s = int(sizes[0])
        rec[:, 0:4] = be32(vol.cookies)
        rec[:, 4:12] = be64(np.arange(1, n + 1))
        rec[:, 12:16] = be32(body)
        rec[:, 16:20] = be32(sizes)
        rec[:, 20:20 + s] = vol.payload.reshape(n, s)
        t = 20 + s + 1
        rec[:, t:t + 4] = be32(masked)
        rec[:, t + 4:t + 12] = be64(APPEND_AT_NS + np.arange(n))
        # padding quirk (needle_read_write.go): the size bytes, then zeros
        pad = rec.shape[1] - (t + 12)
        rec[:, t + 12:t + 12 + min(pad, 4)] = be32(body)[:, :min(pad, 4)]
    else:
        for i in range(n):
            o, s = int(vol.offsets[i]), int(sizes[i])
            out[o:o + 4] = be32(vol.cookies[i])[0]
            out[o + 4:o + 12] = be64(i + 1)[0]
            out[o + 12:o + 16] = be32(body[i])[0]
            out[o + 16:o + 20] = be32(s)[0]
            out[o + 20:o + 20 + s] = vol.body(i)
            t = o + 20 + s + 1
            out[t:t + 4] = be32(masked[i])[0]
            out[t + 4:t + 12] = be64(APPEND_AT_NS + i)[0]
            pad = record_size(s) - (t + 12 - o)
            out[t + 12:t + 12 + min(pad, 4)] = be32(body[i])[0][:min(pad, 4)]
    out.tofile(dat_path)
    with open(idx_path, "wb") as f:
        f.write(vol.idx_bytes())
    return out
