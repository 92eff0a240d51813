"""Overlapped streaming EC encode/rebuild — the `-ec.engine=tpu` data path.

The reference's hot loop (ec_encoder.go:162-192 encodeDataOneBatch) is
read 10 buffers -> reedsolomon.Encode -> append 14 outputs, serially per
256KB batch.  A TPU pipeline that mimics that serial shape spends most of
its wall clock waiting on host<->device transfers.  This module instead
runs a depth-N asynchronous pipeline over fixed-shape dispatches:

  - the whole file is planned as a flat sequence of fill *entries*
    (n bytes per shard at computed offsets, packed side-by-side into a
    [data_shards, DISPATCH_B] host buffer — many small-block rows share
    one dispatch, large-block rows are chunked across dispatches);
  - on one device (the staged plane) a dispatch of whole small-block
    rows needs no fill: it goes up as a [rows, K, block] view of the
    read-only mapped `.dat`, which the kernel reads in file order
    (ops/gf_matmul.py) and whose parity comes back as a slot's would;
    a rebuild's whole chunk goes up the same way, as each survivor
    shard's own span of its map, stacked to [K, W] on the device;
  - every dispatch has the SAME width, so XLA compiles one kernel per
    input layout (tail dispatches are zero-padded, and parity-of-zeros
    is zeros, which is simply not written out);
  - dispatch d+1's host fill and the data-shard writes (data bytes are
    a host-side pass-through) overlap the device compute of dispatch d:
    `device_put` + the jitted kernel return immediately, and the parity
    fetch lags `depth` dispatches behind;
  - the data shards' writes and their `.eci` crc leave the producer for
    shard-owned write lanes (overlap.DataLanes), whose native crc holds
    no GIL; a slot goes back to its pool, and the resume checkpoint
    passes a dispatch, only once the lanes have written it;
  - the DRAIN is asynchronous and multi-buffered (overlap.AsyncDrainer):
    only the parity rows ever cross back over the link (r/k of the
    input — the data shards are already host bytes), the blocking fetch
    runs on a drainer thread (a small pool for device encodes, so
    several D2H copies ride the wire together), and a dedicated writer
    thread appends parity + its `.eci` crc stream in strict FIFO
    submission order — so checkpoint-resume and sidecar bytes are
    identical to the serial pipeline.  The critical thread only ever
    blocks on the slot pool (`pipeline.drain_wait` spans), while the
    wire time lands on the concurrent drain track
    (`drain_s`; `pipeline.drain` spans off-thread) — the split the
    trace analyzer uses to tell "link-bound" from "drain-blocked";
  - host buffers are recycled from a small pool once their parity has
    been fetched (fetch implies the kernel consumed the input, which
    also makes the zero-copy CPU-backend aliasing safe).

Striping semantics are identical to encoder.write_ec_files (strict-`>`
large rows, zero-padded tails, ec_encoder.go:194-231) — differential
tests enforce byte-identical shards against the CPU path, which is
`-ec.engine=cpu`'s own (encoder.write_ec_files / rebuild_ec_files).
"""

from __future__ import annotations

import os
import queue as queue_mod
import sys
import threading
import time
import traceback
from collections import OrderedDict, deque
from typing import Iterator, Optional

import numpy as np

from ..observability import get_tracer
from ..storage.crc import block_crc_path, load_block_crc
from ..utils import faultinject
from ..utils.ioutil import pread_padded, preadv_into
from .gf256 import mat_invert, mat_mul
from .integrity import (
    CorruptSurvivor,
    EciSidecar,
    ShardCorruptError,
    SidecarBuilder,
    note_corruption,
    sidecar_path,
    verify_shard_file,
)
from .overlap import AsyncDrainer, DataLanes, DrainerGroup
from .layout import (
    DATA_SHARDS_COUNT,
    LARGE_BLOCK_SIZE,
    PARITY_SHARDS_COUNT,
    SMALL_BLOCK_SIZE,
    to_ext,
)


def default_drain_pool(cores: Optional[int] = None) -> int:
    """Drainer fetch-pool width: one thread per spare core, bounded to
    [1, 4].  D2H fetches are I/O-bound (the GIL drops during the copy)
    so a few threads keep several transfers in flight on the wire
    without oversubscribing the host."""
    n = cores if cores is not None else (os.cpu_count() or 1)
    return max(1, min(4, n - 1))


def default_data_lanes(cores: Optional[int] = None) -> int:
    """Data-write lanes of an encode: one thread per spare core, bounded
    to [1, 5], two of RS(10,4)'s data shards a lane at the most.  A
    lane's write and crc drop the GIL, so the lanes run in parallel
    with each other and with the producer."""
    n = cores if cores is not None else (os.cpu_count() or 1)
    return max(1, min(5, n - 1))


def _plan_entries(file_size: int, k: int, large: int, small: int,
                  max_n: int) -> Iterator[tuple[int, int, int, int]]:
    """Flatten the row structure of encodeDatFile (ec_encoder.go:194-231)
    into (n, row_start, block_size, chunk_off) fill entries with n <= max_n.
    Shard i of an entry reads file bytes
    [row_start + i*block_size + chunk_off, +n)."""
    remaining = file_size
    start = 0
    while remaining > large * k:
        for off in range(0, large, max_n):
            yield (min(max_n, large - off), start, large, off)
        remaining -= large * k
        start += large * k
    while remaining > 0:
        for off in range(0, small, max_n):
            yield (min(max_n, small - off), start, small, off)
        remaining -= small * k
        start += small * k


class _FileMap:
    """A file mapped read-only for one device encode or rebuild attempt,
    so a dispatch goes to the device as a view of the file's own bytes
    with no fill: the `.dat`'s whole small-block rows, [rows, K, block]
    in file order (rows()), or one survivor shard's span of a rebuild
    chunk (span()).

    The device transfer may alias a view (zero-copy host buffers, always
    on the CPU backend), so the map closes only once every dispatch that
    viewed it was fetched and its views dropped: close() reports False
    while one lives, and the encoder closes it again later."""

    def __init__(self, path: str, size: int):
        import mmap as mmap_mod

        self._mod = mmap_mod
        self.size = size
        with open(path, "rb") as f:
            # the map holds its own descriptor
            self._mm = mmap_mod.mmap(f.fileno(), size,
                                     access=mmap_mod.ACCESS_READ)
        try:
            self._mm.madvise(mmap_mod.MADV_SEQUENTIAL)
        except OSError:
            pass
        self.arr = np.frombuffer(self._mm, dtype=np.uint8)

    def rows(self, start: int, g: int, k: int, block: int) -> np.ndarray:
        """Rows [start, start + g*k*block) as [g, k, block] u8, their
        pages pre-faulted in one call (MADV_POPULATE_READ, Linux 5.14+)
        so the device copy and the data writes take no fault a page."""
        n = g * k * block
        try:
            self._mm.madvise(getattr(self._mod, "MADV_POPULATE_READ", 22),
                             start, n)
        except (OSError, ValueError):
            pass
        return self.arr[start:start + n].reshape(g, k, block)

    def span(self, offset: int, n: int) -> np.ndarray:
        """Bytes [offset, offset + n) as [n] u8, pre-faulted as rows()
        pre-faults its rows (from the page the span starts in)."""
        lo = offset - offset % self._mod.PAGESIZE
        try:
            self._mm.madvise(getattr(self._mod, "MADV_POPULATE_READ", 22),
                             lo, offset + n - lo)
        except (OSError, ValueError):
            pass
        return self.arr[offset:offset + n]

    def close(self) -> bool:
        """Unmap unless a view still lives; True once unmapped."""
        self.arr = None
        if self._mm is None:
            return True
        try:
            self._mm.close()
        except BufferError:
            return False
        self._mm = None
        return True


def _shard_major(src: np.ndarray, used: int) -> np.ndarray:
    """A dispatch's input as [K, used] shard-major bytes, the CPU
    codec's layout: a slot's first columns, or a row group's rows side
    by side (a copy)."""
    if src.ndim == 3:
        g, k, block = src.shape
        return src.transpose(1, 0, 2).reshape(k, g * block)
    return src[:, :used]


class StreamingEncoder:
    """File-level EC encode/rebuild through the bit-plane TPU kernel with
    an overlapped host-IO / device-compute pipeline."""

    def __init__(self, data_shards: int = DATA_SHARDS_COUNT,
                 parity_shards: int = PARITY_SHARDS_COUNT,
                 matrix_kind: str = "vandermonde",
                 dispatch_mb: int = 8, depth: int = 3,
                 engine: str = "device", devices: Optional[str] = None,
                 tracer=None, drain_timeout_s: float = 30.0,
                 max_encode_retries: int = 2,
                 sidecar: bool = True,
                 sidecar_block_size: Optional[int] = None,
                 drain_pool: Optional[int] = None):
        """engine: 'device' (`-ec.engine=tpu`) or 'mesh'
        (`-ec.engine=mesh`), the same jax pipeline under two names: the
        label the spans and the `engine_fallback` event carry.  The CPU
        path (`-ec.engine=cpu`) is encoder.write_ec_files /
        rebuild_ec_files, not this class.

        devices: the device engines' slice, the `-ec.mesh.devices`
        vocabulary (parallel.mesh.parse_device_spec): ''/None/'all' =
        every visible device, 'N' = the first N, 'i,j,k' = exactly
        those indices.  Validated here so a bad flag fails at server
        start, not at first encode.  The slice picks the encode plane:
        one device runs the staged pipeline; more run the per-device
        dispatch-queue plane (_encode_file_mesh), whole dispatches
        round-robin across the slice, each device with its own slot
        pool and drain lane (overlap.DrainerGroup) and the same word
        kernel one device runs, nothing exchanged between devices.
        Rebuilds dispatch to the slice's first device.

        Self-healing knobs: drain_timeout_s sets the deadline for a
        producer waiting on a free slot (a stalled drain surfaces as a
        fault, never a hang); max_encode_retries bounds whole-call
        retries of the encode, each resuming from the last
        fully-drained-and-written dispatch checkpoint instead of byte 0.

        sidecar: encodes also write the `.eci` block-crc sidecar
        (ec/integrity.py) and rebuilds verify survivors against it,
        demoting crc-mismatching shards to erasures; sidecar_block_size
        overrides the crc block granularity (default 256KB).

        Encodes drain asynchronously (overlap.AsyncDrainer): up to
        depth+1 dispatches in flight while drainer threads pull parity
        back and a writer thread appends it in FIFO order.  drain_pool
        overrides the staged plane's fetch-thread count (default:
        default_drain_pool(), sized from os.cpu_count(), bounded
        [1, 4])."""
        import jax

        from ..ops.device import device_backend, ensure_compile_cache
        from ..ops.gf_matmul import DEFAULT_TILE_B, expand_matrix_bitplanes
        from ..parallel.mesh import device_encode_fn, parse_device_spec
        from .codec import ReedSolomon

        if engine not in ("device", "mesh"):
            # catch the -ec.engine vocabulary ("cpu"/"tpu") early rather
            # than silently taking the jax path
            raise ValueError(
                f"engine must be device/mesh, got {engine!r}; the CPU "
                "path is ec/encoder.write_ec_files")
        self.k = data_shards
        self.r = parity_shards
        self.engine = engine
        self.drain_timeout_s = drain_timeout_s
        self.max_encode_retries = max_encode_retries
        self._drain_pool = (max(1, int(drain_pool)) if drain_pool
                            else default_drain_pool())
        self._data_lanes = default_data_lanes()
        # the .eci block crc's path, chosen (and built) before any encode
        load_block_crc()
        # stats counters are bumped from the drainer/writer threads
        # too — and _st_lock also serializes the stale-map list, the
        # kept slots and the lazy fallback-engine init
        self._st_lock = threading.Lock()
        self._sidecar = sidecar
        self._sidecar_bs = sidecar_block_size
        # lazy CPU codec for per-dispatch fallback
        self._fb_engine = None  # guarded-by: _st_lock
        # maps of failed attempts whose views may still be alive;
        # closed once the encode call unwinds (_reap_stale_maps)
        self._stale_maps: list = []  # guarded-by: _st_lock
        # the staged or queue plane's slot buffers, kept between encodes
        self._slot_bufs = None  # guarded-by: _st_lock
        self._jax = jax
        self._expand = expand_matrix_bitplanes
        # a TPU, or a process pinned to the CPU — never a silent CPU
        self.on_tpu = device_backend() == "tpu"
        ensure_compile_cache()
        self._devs = parse_device_spec(devices)
        # every device of the slice runs the one-device word kernel on
        # whole dispatches
        self._kernel = device_encode_fn(on_tpu=self.on_tpu)
        # one fixed dispatch width: a multiple of the pallas tile on
        # TPU, of 64 (the u32 word layout) on the CPU; a row group's
        # block must be one too (_row_group)
        q = DEFAULT_TILE_B if self.on_tpu else 64
        self.dispatch_b = max(q, ((dispatch_mb << 20) // q) * q)
        self._row_q = q
        self.depth = depth
        # same matrix family as ReedSolomon so shards are byte-identical
        self.matrix = ReedSolomon(data_shards, parity_shards,
                                  matrix_kind=matrix_kind).matrix
        self._mat_rows = np.ascontiguousarray(self.matrix[data_shards:])
        # LRU: a long-lived volume server cycles geometries and rebuild
        # matrices (every distinct erasure pattern is a distinct key) —
        # unbounded growth would pin HBM-resident plane arrays forever;
        # one copy per device of the slice stays cached
        self._plane_cache: OrderedDict[bytes, object] = OrderedDict()
        self._plane_cache_max = max(8, 2 * len(self._devs))
        # per-call pipeline counters (_reset_stats); where the time of
        # each stage went is the pipeline.* spans' job
        self.stats: dict[str, float] = {}
        # span tracer (observability/tracer.py): None follows the
        # process-global tracer, which is a no-op until enabled — the
        # per-dispatch spans below cost one attribute check when dormant
        self.tracer = tracer

    def _tracer(self):
        return self.tracer if self.tracer is not None else get_tracer()

    # --- kernel dispatch --------------------------------------------------
    def _planes(self, rows: np.ndarray, dev: int = 0):
        """The bit-plane expansion of GF(2^8) rows, cached resident on
        device `dev` of the slice, so no dispatch waits on a plane
        transfer."""
        rows = np.ascontiguousarray(rows)
        key = rows.tobytes() + bytes([rows.shape[0], dev & 0xFF])
        p = self._plane_cache.get(key)
        if p is None:
            p = self._jax.device_put(self._expand(rows), self._devs[dev])
            self._plane_cache[key] = p  # weedlint: disable=W502 producer-only LRU: _planes runs on the critical thread, never on drain threads
            if len(self._plane_cache) > self._plane_cache_max:
                self._plane_cache.popitem(last=False)
        else:
            self._plane_cache.move_to_end(key)
        return p

    def _dispatch(self, planes, buf: np.ndarray, dev: int = 0):
        """Async: the [K, dispatch_b] u8 slot, or a row group's
        [rows, K, block] u8 view of the mapped `.dat`, goes up to device
        `dev` of the slice as u32 words (a free view: C-contiguous, last
        axis a multiple of 4) and comes back an unfetched
        [R, dispatch_b//4] u32 device array in the same word layout
        (ops/gf_matmul.py) either way, with the D2H copy already queued
        behind the kernel, so the fetch streams down while later
        dispatches compute."""
        # rows land on a TPU in its default layout for [rows, K, W],
        # K-major, and the jitted kernel starts with a copy to the
        # row-major layout it reads: that copy is part of the dispatch
        out = self._kernel(planes, self._jax.device_put(
            buf.view(np.uint32), self._devs[dev]))
        out.copy_to_host_async()
        return out

    def _dispatch_survivors(self, planes, views: list):
        """Async: a rebuild's whole chunk as the K chosen survivors' own
        [dispatch_b] u8 spans of their maps, put on the slice's first
        device as K [dispatch_b//4] u32 rows, which the jitted kernel
        stacks on the device into the [K, dispatch_b//4] words a slot
        would give (ops/gf_matmul.py); comes back as _dispatch's
        does."""
        rows = self._jax.device_put([v.view(np.uint32) for v in views],
                                    self._devs[0])
        out = self._kernel(planes, tuple(rows))
        out.copy_to_host_async()
        return out

    def _map_dat(self, dat_path: str, file_size: int, large: int,
                 small: int) -> Optional[_FileMap]:
        """The `.dat` mapped for row-group dispatches, or None where no
        dispatch can be one: a volume shorter than one group, or blocks
        that do not divide the dispatch."""
        b = self.dispatch_b
        if file_size < self.k * b or not any(
                blk and b % blk == 0 and blk % self._row_q == 0
                for blk in (large, small)):
            return None
        return _FileMap(dat_path, file_size)

    def _row_group(self, fills: list, used: int,
                   dat_map: Optional[_FileMap]) -> Optional[np.ndarray]:
        """A packed dispatch as one [rows, K, block] view of the mapped
        `.dat`, where its entries are dispatch_b // block consecutive
        whole rows of one block size, all inside the file; else None,
        and the dispatch is filled into a slot."""
        if dat_map is None or used != self.dispatch_b:
            return None
        _, _, start, block, _ = fills[0]
        step = self.k * block
        if len(fills) * block != used or block % self._row_q or \
                start + len(fills) * step > dat_map.size:
            return None
        for j, (_, n, row_start, blk, off) in enumerate(fills):
            if off or n != block or blk != block or \
                    row_start != start + j * step:
                return None
        return dat_map.rows(start, len(fills), self.k, block)

    def _fill(self, tr, dat, buf: np.ndarray, fills: list, used: int,
              d_idx: int, **attrs) -> None:
        """Read a dispatch's packed entries into its slot, shard-major,
        and zero the columns past them."""
        k = self.k
        with tr.span("pipeline.fill", dispatch=d_idx, bytes=k * used,
                     **attrs):
            for col, n, row_start, block, off in fills:
                if off == 0 and n == block:
                    # whole-block entry: the k per-shard reads are
                    # CONTIGUOUS in the file ([row_start, +k*block)) —
                    # one vectored read straight into the k strided
                    # buffer slices, no intermediate copy
                    preadv_into(dat, [buf[i, col:col + n]
                                      for i in range(k)], row_start)
                else:
                    for i in range(k):
                        buf[i, col:col + n] = pread_padded(
                            dat, n, row_start + i * block + off)
            if used < buf.shape[1]:
                buf[:, used:] = 0

    def _count_dispatch(self, st: dict, mapped: bool) -> None:
        """One dispatch of an encode (either plane) or a rebuild, in the
        call's stats and the process counters /status reports
        (EcPipeline `dispatches`, `mapped_dispatches`)."""
        from ..stats import ec_pipeline_metrics

        st["dispatches"] += 1
        st["mapped_dispatches"] += mapped
        ec_pipeline_metrics().dispatches.inc("map" if mapped else "slot")

    def _write_data(self, outputs: list, sb, src: np.ndarray,
                    used: int) -> float:
        """One lane's data shards of a dispatch, passed through from its
        input: shard i, where outputs[i] is not None, takes a slot's
        row i, or block i of each row of a group in order, and its
        `.eci` crc stream takes each slice right after its write, while
        the slice is still in the CPU's cache.  The other lanes write
        the shards that are None here, at the same time: the write and
        the native crc both drop the GIL.  Returns the crc time."""
        crc_s = 0.0
        for i, f in enumerate(outputs):
            if f is None:
                continue
            for p in (src[:, i] if src.ndim == 3 else (src[i, :used],)):
                f.write(memoryview(p))
                if sb is not None:
                    t0 = time.perf_counter()
                    sb.update(i, p)
                    crc_s += time.perf_counter() - t0
        return crc_s

    def _open_lanes(self, tr, outputs: list, sb, ds: dict,
                    ds_lock) -> DataLanes:
        """An encode attempt's data lanes: lane j of L writes the data
        shards i with i % L == j of every dispatch submitted to it, as
        (d_idx, src, used), under a `pipeline.write kind=data` span
        that says its `lane`, and counts their bytes (/status EcPipeline
        `lane_data_bytes`)."""
        from ..stats import ec_pipeline_metrics

        k = self.k
        n = max(1, min(self._data_lanes, k))
        own = [[f if i % n == j else None for i, f in enumerate(outputs[:k])]
               for j in range(n)]
        counter = ec_pipeline_metrics().lane_data_bytes

        def write(j, meta):  # thread-entry
            d_idx, src, used = meta
            with tr.span("pipeline.write", dispatch=d_idx, kind="data",
                         lane=j):
                crc_s = self._write_data(own[j], sb, src, used)
            counter.inc(amount=used * len(range(j, k, n)))
            with ds_lock:
                ds["sidecar_s"] += crc_s

        return DataLanes(n, write)

    def _wait_data(self, tr, lanes: DataLanes, d: int, **attrs) -> None:
        """Block until the lanes have written the first d dispatches:
        before a slot they read goes back to its pool, before the
        checkpoint passes them.  A wait that blocks is a
        `pipeline.data_wait` span whose `wait` says where: `slot` on a
        fetch thread, `checkpoint` on the parity writer (`final`, the
        producer's wait at the encode's end, is opened by the caller)."""
        if lanes.written(d):
            return
        with tr.span("pipeline.data_wait", **attrs):
            lanes.wait(d, max(4 * self.drain_timeout_s, 120.0))

    def _take_slots(self, nd: int, per_dev: int) -> list:
        """[nd][per_dev] slot buffers: the last clean encode's where the
        geometry matches, else None each, a slot's buffer allocated by
        its first fill (a dispatch of mapped rows takes the slot's token
        alone).  Kept buffers spare the fills the page faults of fresh
        memory, which cost the queue plane 18% of its MB/s on four
        chips; taken under the lock, so concurrent encodes never share
        them."""
        with self._st_lock:
            kept, self._slot_bufs = self._slot_bufs, None
        if kept is not None and len(kept) == nd and all(
                len(s) == per_dev and all(
                    x is None or x.shape == (self.k, self.dispatch_b)
                    for x in s) for s in kept):
            return kept
        return [[None] * per_dev for _ in range(nd)]

    def _keep_slots(self, slots: list) -> None:
        """After a clean encode every dispatch was fetched, so no
        transfer still reads the slots; after a fault one may, and the
        caller drops them."""
        with self._st_lock:
            self._slot_bufs = slots

    def _close_map(self, dat_map: Optional[_FileMap]) -> None:
        """Close an attempt's map, or leave it to _reap_stale_maps
        while a view lives (a transfer not yet released, a fault's
        traceback)."""
        if dat_map is not None and not dat_map.close():
            with self._st_lock:
                self._stale_maps.append(dat_map)

    def _fetch(self, out_dev) -> np.ndarray:  # thread-entry
        """Blocking fetch + host-side unpack back to [R, dispatch-width]
        u8.  Runs on a drainer's fetch thread.  A dispatch that fell back
        to the CPU codec carries its finished parity as the handle."""
        if isinstance(out_dev, np.ndarray):
            return out_dev
        from ..ops.gf_matmul import unpack_u32_host

        words = np.asarray(out_dev)
        return unpack_u32_host(words, words.shape[1] * 4)

    # --- encode -----------------------------------------------------------
    def _reset_stats(self) -> dict:
        # producer-only rebind at encode start, before any drain
        # thread exists; drain threads mutate the DICT via the st
        # alias under _st_lock, never rebind the attribute
        self.stats = {  # weedlint: disable=W502 rebound before the drain threads exist
                      "dispatches": 0, "wall_s": 0.0, "bytes_in": 0,
                      # dispatches sent as views of a mapped file (the
                      # .dat's rows, _row_group; a rebuild's survivor
                      # spans), no fill
                      "mapped_dispatches": 0,
                      "retries": 0, "fallbacks": 0, "worker_restarts": 0,
                      # integrity accounting: sidecar_s = crc build time
                      # on encodes, verify_s = survivor verification on
                      # rebuilds (bench reads these for the verify-
                      # overhead figure)
                      "sidecar_s": 0.0, "verify_s": 0.0,
                      # async-drain accounting: drain_s = CONCURRENT
                      # fetch time on the drainer track (the host
                      # thread's own blocking is the pipeline.drain_wait
                      # spans), parity_bytes_drained = bytes actually
                      # pulled back across the link (parity only — r/k
                      # of bytes_in, the proof the drain never fetches
                      # data shards), drain_pool = fetch threads the
                      # drainer ran with (0 on rebuilds, which drain
                      # inline); worker_restarts stays 0 (/status shape)
                      "drain_s": 0.0, "parity_bytes_drained": 0,
                      "drain_pool": 0}
        return self.stats

    # --- self-healing helpers ---------------------------------------------
    def _cpu_parity(self, data: np.ndarray) -> np.ndarray:
        """Per-dispatch CPU fallback: parity for [k, n] data through the
        host codec — byte-identical to every other engine by the
        differential-test contract."""
        with self._st_lock:
            if self._fb_engine is None:
                from .codec import best_cpu_engine

                self._fb_engine = best_cpu_engine()
            fb = self._fb_engine
        return fb.matmul(self._mat_rows,
                         np.ascontiguousarray(data))

    def _note_fallback(self, st: dict, reason: str) -> None:  # thread-entry
        # called from the pipeline thread AND the drainer's fetch
        # threads: the read-modify-write must not lose counts
        with self._st_lock:
            st["fallbacks"] += 1
        from ..observability import events as _events
        from ..stats import ec_pipeline_metrics

        ec_pipeline_metrics().engine_fallbacks.inc(reason)
        _events.emit("engine_fallback", reason=reason,
                     engine=str(self.engine))

    def _reap_stale_maps(self) -> None:
        with self._st_lock:
            stale, self._stale_maps = self._stale_maps, []
        if not stale:
            return
        # the encode's flush/drain closures form reference cycles that
        # keep views of the map alive past the call's return; collect
        # them now so close() can actually release it (rare path: only
        # runs after a failed attempt or a view outliving its fetch)
        import gc

        gc.collect()
        keep = []
        for m in stale:
            if not m.close():
                # a view the exception being handled still holds: the
                # next reap closes it
                keep.append(m)
        if keep:
            with self._st_lock:
                self._stale_maps.extend(keep)

    def encode_file(self, dat_path: str, out_base: str,
                    large_block_size: int = LARGE_BLOCK_SIZE,
                    small_block_size: int = SMALL_BLOCK_SIZE) -> None:
        """dat_path -> out_base.ec00..ecNN, byte-identical to
        encoder.write_ec_files (WriteEcFiles, ec_encoder.go:57).

        Crash-safe: the staged pipeline checkpoints the last fully
        drained-and-written dispatch, and a mid-encode failure retries
        (up to max_encode_retries) RESUMING from that checkpoint — the
        outputs are truncated back to the checkpoint byte and the entry
        plan fast-forwards past the completed prefix, so a 30GB encode
        that faults at byte 29G does not start over from byte 0.
        Dispatch packing after a resume may differ from a clean run, but
        the GF matmul is column-independent so the shard bytes cannot."""
        retries = 0
        start_entry = start_byte = 0
        # the slice picks the plane: per-device queues on more than one
        # device, which share the staged pipeline's checkpoint-resume
        # contract (self._ckpt) so the retry loop below serves both
        attempt = (self._encode_file_mesh if len(self._devs) > 1
                   else self._encode_file_staged)
        try:
            while True:
                try:
                    return attempt(
                        dat_path, out_base, large_block_size,
                        small_block_size, start_entry, start_byte, retries)
                except (KeyboardInterrupt, SystemExit):
                    raise
                except Exception as e:
                    ck_entry, ck_byte = self._ckpt
                    if retries >= self.max_encode_retries:
                        # same discipline as encoder.write_ec_files: a
                        # truncated .ecNN surviving a failed encode would
                        # satisfy existence checks and mask the missing
                        # bytes on the next mount/rebuild (the stale
                        # sidecar goes with them)
                        for p in [out_base + to_ext(i)
                                  for i in range(self.k + self.r)] + \
                                 [sidecar_path(out_base)]:
                            try:
                                os.remove(p)
                            except OSError:
                                pass
                        raise
                    retries += 1
                    self._reap_stale_maps()  # attempt's views unwound
                    start_entry, start_byte = ck_entry, ck_byte
                    self._tracer().event(
                        "pipeline.retry", scope="encode_file",
                        attempt=retries, resume_entry=ck_entry,
                        resume_byte=ck_byte,
                        error=f"{type(e).__name__}: {e}")
        finally:
            self._reap_stale_maps()

    def _encode_file_staged(self, dat_path: str, out_base: str,
                            large_block_size: int, small_block_size: int,
                            start_entry: int = 0, start_byte: int = 0,
                            retries: int = 0) -> None:
        """One attempt of the staged pipeline (a slice of one device),
        starting at plan entry start_entry / shard byte start_byte.
        Maintains self._ckpt = (entries drained+written, bytes per
        shard) as the contiguous-completion checkpoint (drain order is
        FIFO, so the completed prefix is always contiguous).  A failing
        device dispatch/fetch degrades THIS dispatch (and, for terminal
        faults, the rest of the encode) to the CPU codec — byte-identical
        output either way."""
        k, r, b = self.k, self.r, self.dispatch_b
        st = self._reset_stats()
        st["retries"] = retries
        self._ckpt = (start_entry, start_byte)  # weedlint: disable=W502 producer writes it before the drainer starts; the writer thread advances it and the producer re-reads only after abort() joins
        clock = time.perf_counter
        t_start = clock()
        planes = self._planes(self.matrix[k:])
        file_size = os.path.getsize(dat_path)
        st["devices"] = 1
        tr = self._tracer()
        root = tr.span("pipeline.encode_file", path=dat_path,
                       bytes=file_size, mode="staged", engine=self.engine,
                       devices=st["devices"], resume_entry=start_entry,
                       crc=block_crc_path())
        root.__enter__()
        # setup covers output opens (O_TRUNC over existing shards frees
        # their page cache — real, attributable time), the slots and the
        # map; ends when the first entry is planned
        setup = tr.span("pipeline.setup")
        setup.__enter__()
        outputs: list = []
        sb = SidecarBuilder(k + r, self._sidecar_bs) if self._sidecar \
            else None
        try:
            for i in range(k + r):
                p = out_base + to_ext(i)
                if start_byte and os.path.exists(p):
                    # resume: drop torn bytes past the checkpoint, keep
                    # the completed prefix
                    f = open(p, "r+b")
                    f.truncate(start_byte)
                    f.seek(start_byte)
                    if sb is not None:
                        # crc state can't roll back through a partial
                        # block: re-seed from the surviving prefix
                        sb.seed_from_file(i, f, start_byte)
                else:
                    f = open(p, "wb")
                outputs.append(f)
            slots = self._take_slots(1, self.depth + 1)
            bufs = slots[0]
            dat_map = self._map_dat(dat_path, file_size, large_block_size,
                                    small_block_size)
        except BaseException:
            # the main finally never runs if setup dies: close what
            # opened and unwind the span stack
            for f in outputs:
                f.close()
            exc = sys.exc_info()
            setup.__exit__(*exc)
            root.__exit__(*exc)
            raise

        ok = False
        flags = {"degraded": False}  # terminal fault: rest goes CPU
        # concurrent-side accounting (drainer fetch threads + writer
        # thread own these keys; folded into st once the threads join)
        ds = {"drain_s": 0.0, "sidecar_s": 0.0, "parity_bytes": 0}
        ds_lock = threading.Lock()
        slot_q: queue_mod.Queue = queue_mod.Queue()

        def drain_fetch(meta):
            """Fetch (or fault/fallback-recompute) ONE dispatch's parity
            — the only place kernel output crosses back to the host —
            on the drainer's fetch pool, then recycle its slot."""
            parity_dev, u, bi, d_idx, nfills, rows = meta
            parity = None
            reason = None
            nbytes = 0
            t0 = clock()
            with tr.span("pipeline.drain", dispatch=d_idx, bytes=r * u):
                # injected drain fault: the dispatch recomputes on the
                # CPU.  Hit INSIDE the span so a delay-only fault
                # (slow-drain drills) is attributed to drain, where a
                # real slow fetch would land
                drain_fault = False
                if faultinject._points:
                    try:
                        faultinject.hit("ec.drain")
                    except Exception:
                        drain_fault = True
                if drain_fault:
                    reason = "drain_fault"
                else:
                    try:
                        parity = self._fetch(parity_dev)
                        # parity-only accounting: what actually crossed
                        # the link (r/k of bytes_in — data shards never
                        # transfer back)
                        nbytes = int(parity.nbytes)
                    except (KeyboardInterrupt, SystemExit):
                        raise
                    except Exception:
                        if drainer.aborting:
                            raise  # teardown race, not a pipeline fault
                        reason = "device_fetch"
                        flags["degraded"] = True
            fetch_s = clock() - t0
            if parity is None:
                # the input is still intact: slots recycle only after
                # their dispatch is fetched (or recomputed here), and the
                # map outlives every dispatch, so the CPU codec can
                # recompute losslessly
                with tr.span("pipeline.fallback", dispatch=d_idx,
                             reason=reason):
                    parity = self._cpu_parity(_shard_major(
                        bufs[bi] if rows is None else rows, u))
                self._note_fallback(st, reason)
            with ds_lock:
                ds["drain_s"] += fetch_s
                ds["parity_bytes"] += nbytes
            if rows is None:
                # the data lanes read the slot too
                self._wait_data(tr, lanes, d_idx + 1, wait="slot")
            # the fetched parity is an independent host array and fetch
            # completion proves the kernel consumed the input slot —
            # recycle NOW so the producer refills while this parity
            # queues for writing
            slot_q.put(bi)
            return parity[:, :u]

        def drain_write(meta, parity):
            """Write ONE dispatch's parity + crc stream and advance the
            FIFO checkpoint, on the writer thread."""
            parity_dev, u, bi, d_idx, nfills, _ = meta
            # entries pack side by side, so each parity row's bytes for
            # this dispatch are one contiguous slice
            with tr.span("pipeline.write", dispatch=d_idx, kind="parity"):
                for j in range(r):
                    outputs[k + j].write(memoryview(parity[j, :u]))
                if sb is not None:
                    # drain order is FIFO == write order (the writer
                    # consumes in submission order), so each parity
                    # row's crc stream stays sequential; the crc time
                    # counts as write stage and is broken out in
                    # sidecar_s
                    t1 = clock()
                    for j in range(r):
                        sb.update(k + j, parity[j, :u])
                    with ds_lock:
                        ds["sidecar_s"] += clock() - t1
            self._wait_data(tr, lanes, d_idx + 1, wait="checkpoint")
            # dispatch d_idx is fully drained AND written on every shard:
            # advance the resume checkpoint past its entries/bytes
            ck_e, ck_b = self._ckpt
            self._ckpt = (ck_e + nfills, ck_b + u)  # weedlint: disable=W502 writer thread owns the checkpoint while draining; the producer reads it only after the drainer is joined (happens-before)

        def acquire_slot() -> int:
            if drainer.error is not None:
                raise drainer.error
            try:
                return slot_q.get_nowait()
            except queue_mod.Empty:
                pass
            # every slot is in flight: THIS is the pipeline's residual
            # drain stall — the one the async drain exists to shrink
            with tr.span("pipeline.drain_wait"):
                deadline = time.monotonic() + max(
                    4 * self.drain_timeout_s, 120.0)
                while True:
                    try:
                        return slot_q.get(timeout=0.2)
                    except queue_mod.Empty:
                        if drainer.error is not None:
                            raise drainer.error
                        if time.monotonic() >= deadline:
                            raise RuntimeError(
                                "async drain stalled: no free "
                                "dispatch slot")

        # multi-buffered async drain: several D2H copies may be in
        # flight on the fetch pool, the writer keeps FIFO order
        drainer = AsyncDrainer(drain_fetch, drain_write,
                               pool_size=self._drain_pool,
                               queue_depth=len(bufs) + 2)
        for i in range(len(bufs)):
            slot_q.put(i)
        st["drain_pool"] = drainer.pool_size
        lanes = self._open_lanes(tr, outputs, sb, ds, ds_lock)
        st["data_lanes"] = lanes.n

        try:
            with open(dat_path, "rb") as dat:
                fills: list[tuple[int, int, int, int, int]] = []
                used = 0
                bi = acquire_slot()

                def flush():
                    nonlocal bi, used, fills
                    if not used:
                        return
                    d_idx = st["dispatches"]
                    # a whole group of rows goes as a view of the mapped
                    # .dat; anything else is filled into the slot
                    rows = self._row_group(fills, used, dat_map)
                    if rows is None:
                        if bufs[bi] is None:
                            bufs[bi] = np.empty((k, b), dtype=np.uint8)
                        src = bufs[bi]
                        self._fill(tr, dat, src, fills, used, d_idx)
                    else:
                        src = rows
                    # injected dispatch fault: THIS dispatch goes CPU,
                    # the pipeline stays on its engine
                    dispatch_fault = False
                    if faultinject._points:
                        try:
                            faultinject.hit("ec.dispatch")
                        except Exception:
                            dispatch_fault = True
                    with tr.span("pipeline.dispatch", dispatch=d_idx,
                                 bytes=k * used,
                                 source="slot" if rows is None else "map"):
                        if flags["degraded"] or dispatch_fault:
                            reason = ("degraded" if flags["degraded"]
                                      else "dispatch_fault")
                            parity_dev = self._cpu_parity(
                                _shard_major(src, used))
                            self._note_fallback(st, reason)
                            # on the trace too: a fallback decision that
                            # leaves no span would let a degraded run
                            # read as clean in the analyzer
                            tr.event("pipeline.fallback", dispatch=d_idx,
                                     reason=reason)
                        else:
                            try:
                                parity_dev = self._dispatch(planes, src)
                            except (KeyboardInterrupt, SystemExit):
                                raise
                            except Exception as e:
                                # device dispatch failed: degrade the
                                # rest of the encode to the CPU codec
                                flags["degraded"] = True
                                self._note_fallback(st, "device_dispatch")
                                tr.event("pipeline.fallback",
                                         dispatch=d_idx,
                                         reason="device_dispatch",
                                         error=f"{type(e).__name__}: {e}")
                                parity_dev = self._cpu_parity(
                                    _shard_major(src, used))
                    self._count_dispatch(st, rows is not None)
                    st["bytes_in"] += k * used
                    # data shards pass through from the host input on the
                    # lanes while the device computes parity
                    lanes.submit((d_idx, src, used))
                    # hand the dispatch to the drainer and move straight
                    # on to filling the next slot — the data writes, the
                    # fetch and the parity write overlap everything
                    # below.  Backpressure is normally the slot pool, but
                    # the bounded writer queue can also push back (fast
                    # fetch over a slow shard disk recycles slots before
                    # the write)
                    drainer.submit((parity_dev, used, bi, d_idx, len(fills),
                                    rows))
                    fills, used = [], 0
                    bi = acquire_slot()

                setup.__exit__(None, None, None)
                setup = None
                entries = _plan_entries(file_size, k, large_block_size,
                                        small_block_size, b)
                for _ in range(start_entry):  # resume: skip completed
                    next(entries, None)
                for n, row_start, block, off in entries:
                    if used + n > b:
                        flush()
                    fills.append((used, n, row_start, block, off))
                    used += n
                flush()
                # tail stall: the lanes write their last data, then the
                # last in-flight dispatches finish fetching + writing
                with tr.span("pipeline.data_wait", wait="final"):
                    lanes.finish()
                with tr.span("pipeline.drain_wait", final=True):
                    drainer.finish()
            if sb is not None:
                t0 = clock()
                sb.finalize().save(out_base)
                st["sidecar_s"] += clock() - t0
            else:
                try:  # stale sidecar would mass-demote the fresh shards
                    os.remove(sidecar_path(out_base))
                except OSError:
                    pass
            ok = True
        finally:
            exc = sys.exc_info() if not ok else (None, None, None)
            if setup is not None:  # failed before the loop started
                setup.__exit__(*exc)
            if not ok:
                lanes.abort()
                drainer.abort()
            # fold the concurrent lane/drain/writer accounting into the
            # call stats now that the threads have joined
            st["drain_s"] += ds["drain_s"]
            st["sidecar_s"] += ds["sidecar_s"]
            st["parity_bytes_drained"] += ds["parity_bytes"]
            self._close_map(dat_map)
            if ok:
                self._keep_slots(slots)
            with tr.span("pipeline.close"):
                for f in outputs:
                    f.close()
            st["wall_s"] = clock() - t_start
            root.__exit__(*exc)

    def _encode_file_mesh(self, dat_path: str, out_base: str,
                          large_block_size: int, small_block_size: int,
                          start_entry: int = 0, start_byte: int = 0,
                          retries: int = 0) -> None:
        """One attempt of the per-device dispatch-queue plane (a slice
        of more than one device): whole dispatches round-robin across
        the slice, so N dispatches compute and transfer concurrently
        instead of serializing on device 0, and no byte crosses between
        devices.

        Per device: a slot pool of host staging buffers (one committed
        device_put batches the whole [k, b] H2D), a dispatch queue, and
        its own drain lane (overlap.DrainerGroup) — a slow
        device back-pressures only its own queue.  Up to `coalesce`
        dispatches ride one drain call per device, so several D2H
        transfers amortize one wire turnaround when the link is the
        ceiling.

        Output discipline: data shards append on the data lanes in
        dispatch order (exactly the staged pipeline); parity rows are
        PWRITTEN at their known shard offsets by whichever lane finishes
        first (order-free), while the `.eci` crc stream and the resume
        checkpoint advance through an ordered completion tracker keyed
        by dispatch index — shard bytes and sidecar stay byte-identical
        to the CPU codec, and self._ckpt keeps the staged pipeline's
        retry-from-checkpoint contract.  Per-dispatch faults degrade to
        the CPU codec exactly like the staged path (the CPU parity rides
        the same lane as a plain ndarray handle)."""
        k, r, b = self.k, self.r, self.dispatch_b
        nd = len(self._devs)
        # several dispatches per drain call when a thin link dominates;
        # on CPU/GPU backends the "transfer" is a memcpy — keep latency
        coalesce = 2 if self.on_tpu else 1
        slots_per_dev = coalesce + 1
        st = self._reset_stats()
        st["retries"] = retries
        st["devices"] = nd
        self._ckpt = (start_entry, start_byte)  # weedlint: disable=W502 producer writes it before the drain lanes start; the writer lanes advance it under comp_lock and the producer re-reads only after the group is joined
        clock = time.perf_counter
        t_start = clock()
        planes_dev = [self._planes(self.matrix[k:], i) for i in range(nd)]
        file_size = os.path.getsize(dat_path)
        tr = self._tracer()
        root = tr.span("pipeline.encode_file", path=dat_path,
                       bytes=file_size, mode="mesh", engine=self.engine,
                       devices=nd, resume_entry=start_entry,
                       crc=block_crc_path())
        root.__enter__()
        setup = tr.span("pipeline.setup")
        setup.__enter__()
        outputs: list = []
        sb = SidecarBuilder(k + r, self._sidecar_bs) if self._sidecar \
            else None
        try:
            for i in range(k + r):
                p = out_base + to_ext(i)
                if start_byte and os.path.exists(p):
                    f = open(p, "r+b")
                    f.truncate(start_byte)
                    f.seek(start_byte)
                    if sb is not None:
                        sb.seed_from_file(i, f, start_byte)
                else:
                    f = open(p, "wb")
                outputs.append(f)
            out_fds = [f.fileno() for f in outputs]
            dev_bufs = self._take_slots(nd, slots_per_dev)
        except BaseException:
            for f in outputs:
                f.close()
            exc = sys.exc_info()
            setup.__exit__(*exc)
            root.__exit__(*exc)
            raise
        ok = False
        flags = {"degraded": False}  # terminal fault: rest goes CPU
        ds = {"drain_s": 0.0, "sidecar_s": 0.0, "parity_bytes": 0}
        ds_lock = threading.Lock()
        dev_drain_s = [0.0] * nd   # guarded-by: ds_lock
        dev_dispatches = [0] * nd  # producer-only
        slot_qs = [queue_mod.Queue() for _ in range(nd)]
        for q in slot_qs:
            for s in range(slots_per_dev):
                q.put(s)
        # ordered completion tracker: parity pwrites land out of order
        # across lanes, but the crc sidecar and the resume checkpoint
        # must advance in dispatch order — buffer completions and retire
        # the contiguous prefix
        comp_lock = threading.Lock()
        comp: dict[int, tuple] = {}
        nxt = [0]

        def _retire_locked():
            # holds comp_lock; sb parity crc streams stay sequential
            # because only the contiguous prefix ever retires
            sc = 0.0
            while nxt[0] in comp:
                parity, u, nfills = comp.pop(nxt[0])
                if sb is not None:
                    t1 = clock()
                    for j in range(r):
                        sb.update(k + j, parity[j, :u])
                    sc += clock() - t1
                ck_e, ck_b = self._ckpt
                self._ckpt = (ck_e + nfills, ck_b + u)  # weedlint: disable=W502 writer lanes advance it under comp_lock while draining; the producer reads it only after the group is joined (happens-before)
                nxt[0] += 1
            if sc:
                with ds_lock:
                    ds["sidecar_s"] += sc

        def drain_fetch_dev(meta):
            """Fetch ONE device's batched D2H transfers (this lane's
            thread) — failures recompute on the CPU codec from the
            still-held slot buffers, then every slot recycles."""
            dev_i, jobs = meta
            parities: list = [None] * len(jobs)
            reasons: list = [None] * len(jobs)
            nbytes = 0
            t0 = clock()
            with tr.span("pipeline.drain", device=dev_i,
                         dispatch=jobs[0][3], n=len(jobs),
                         bytes=sum(r * j[1] for j in jobs)):
                drain_fault = False
                if faultinject._points:
                    try:
                        faultinject.hit("ec.drain")
                    except Exception:
                        drain_fault = True
                for ji, (handle, u, slot, d_idx, nfills, off) \
                        in enumerate(jobs):
                    if drain_fault:
                        reasons[ji] = "drain_fault"
                        continue
                    try:
                        parities[ji] = self._fetch(handle)
                        nbytes += int(parities[ji].nbytes)
                    except (KeyboardInterrupt, SystemExit):
                        raise
                    except Exception:
                        if drainers is not None and drainers.aborting:
                            raise  # teardown race, not a pipeline fault
                        reasons[ji] = "device_fetch"
                        flags["degraded"] = True
            fetch_s = clock() - t0
            for ji, (handle, u, slot, d_idx, nfills, off) \
                    in enumerate(jobs):
                if parities[ji] is None:
                    # slot buffer still intact (slots recycle below,
                    # after fetch-or-recompute): lossless CPU recompute
                    with tr.span("pipeline.fallback", dispatch=d_idx,
                                 device=dev_i, reason=reasons[ji]):
                        parities[ji] = self._cpu_parity(
                            dev_bufs[dev_i][slot][:, :u])
                    self._note_fallback(st, reasons[ji])
                    tr.event("pipeline.fallback", dispatch=d_idx,
                             device=dev_i, reason=reasons[ji])
                parities[ji] = parities[ji][:, :u]
                # the data lanes read the slot too
                self._wait_data(tr, lanes, d_idx + 1, wait="slot",
                                device=dev_i)
                slot_qs[dev_i].put(slot)
            with ds_lock:
                ds["drain_s"] += fetch_s
                ds["parity_bytes"] += nbytes
                dev_drain_s[dev_i] += fetch_s
            return parities

        def drain_write_dev(meta, parities):
            """This lane's writer thread: parity rows pwrite at their
            known shard offsets (cross-lane order-free), then the
            ordered tracker retires crc + checkpoint."""
            # the fetch released the jobs' slots only once the data
            # lanes had written them, so the checkpoint below never
            # passes unwritten data
            dev_i, jobs = meta
            with tr.span("pipeline.write", device=dev_i,
                         dispatch=jobs[0][3], kind="parity"):
                for (handle, u, slot, d_idx, nfills, off), parity \
                        in zip(jobs, parities):
                    for j in range(r):
                        os.pwrite(out_fds[k + j],
                                  memoryview(parity[j, :u]), off)
                    with comp_lock:
                        comp[d_idx] = (parity, u, nfills)
                        _retire_locked()

        drainers = DrainerGroup(nd, drain_fetch_dev, drain_write_dev,
                                queue_depth=slots_per_dev + 2)
        st["drain_pool"] = nd
        lanes = self._open_lanes(tr, outputs, sb, ds, ds_lock)
        st["data_lanes"] = lanes.n
        batches: list[list] = [[] for _ in range(nd)]

        def submit_batch(dev_i: int) -> None:
            jobs, batches[dev_i] = batches[dev_i], []
            if not jobs:
                return
            drainers.submit(dev_i, (dev_i, jobs))

        def acquire_slot(dev_i: int) -> int:
            err = drainers.error
            if err is not None:
                raise err
            try:
                return slot_qs[dev_i].get_nowait()
            except queue_mod.Empty:
                pass
            # every slot of THIS device is in flight: the residual
            # drain stall, attributed to the lane that back-pressured
            with tr.span("pipeline.drain_wait", device=dev_i):
                deadline = time.monotonic() + max(
                    4 * self.drain_timeout_s, 120.0)
                while True:
                    try:
                        return slot_qs[dev_i].get(timeout=0.2)
                    except queue_mod.Empty:
                        err = drainers.error
                        if err is not None:
                            raise err
                        if time.monotonic() >= deadline:
                            raise RuntimeError(
                                "mesh drain stalled: no free slot "
                                f"on device {dev_i}")

        try:
            with open(dat_path, "rb") as dat:
                fills: list[tuple[int, int, int, int, int]] = []
                used = 0
                out_off = start_byte

                def flush():
                    nonlocal used, fills, out_off
                    if not used:
                        return
                    d_idx = st["dispatches"]
                    dev_i = d_idx % nd  # round-robin across the slice
                    slot = acquire_slot(dev_i)
                    if dev_bufs[dev_i][slot] is None:
                        dev_bufs[dev_i][slot] = np.empty((k, b),
                                                         dtype=np.uint8)
                    buf = dev_bufs[dev_i][slot]
                    self._fill(tr, dat, buf, fills, used, d_idx,
                               device=dev_i)
                    dispatch_fault = False
                    if faultinject._points:
                        try:
                            faultinject.hit("ec.dispatch")
                        except Exception:
                            dispatch_fault = True
                    with tr.span("pipeline.dispatch", dispatch=d_idx,
                                 device=dev_i, bytes=k * used,
                                 source="slot"):
                        if flags["degraded"] or dispatch_fault:
                            # the CPU parity rides the same lane as a
                            # plain ndarray handle: ordering, slot
                            # recycling and accounting stay uniform
                            reason = ("degraded" if flags["degraded"]
                                      else "dispatch_fault")
                            handle = self._cpu_parity(buf[:, :used])
                            self._note_fallback(st, reason)
                            tr.event("pipeline.fallback", dispatch=d_idx,
                                     device=dev_i, reason=reason)
                        else:
                            try:
                                handle = self._dispatch(planes_dev[dev_i],
                                                        buf, dev_i)
                            except (KeyboardInterrupt, SystemExit):
                                raise
                            except Exception as e:
                                flags["degraded"] = True
                                self._note_fallback(st, "device_dispatch")
                                tr.event("pipeline.fallback",
                                         dispatch=d_idx, device=dev_i,
                                         reason="device_dispatch",
                                         error=f"{type(e).__name__}: {e}")
                                handle = self._cpu_parity(buf[:, :used])
                    self._count_dispatch(st, False)
                    st["bytes_in"] += k * used
                    dev_dispatches[dev_i] += 1
                    lanes.submit((d_idx, buf, used))
                    batches[dev_i].append((handle, used, slot, d_idx,
                                           len(fills), out_off))
                    out_off += used
                    fills, used = [], 0
                    if len(batches[dev_i]) >= coalesce:
                        submit_batch(dev_i)

                setup.__exit__(None, None, None)
                setup = None
                entries = _plan_entries(file_size, k, large_block_size,
                                        small_block_size, b)
                for _ in range(start_entry):  # resume: skip completed
                    next(entries, None)
                for n, row_start, block, off in entries:
                    if used + n > b:
                        flush()
                    fills.append((used, n, row_start, block, off))
                    used += n
                flush()
                for dev_i in range(nd):
                    submit_batch(dev_i)
                # tail stall: the data lanes, then every drain lane's
                # in-flight dispatches finish fetching + writing
                with tr.span("pipeline.data_wait", wait="final"):
                    lanes.finish()
                with tr.span("pipeline.drain_wait", final=True):
                    drainers.finish()
                if nxt[0] != st["dispatches"]:
                    raise RuntimeError(
                        f"mesh completion tracker retired {nxt[0]} of "
                        f"{st['dispatches']} dispatches")
            if sb is not None:
                t0 = clock()
                sb.finalize().save(out_base)
                st["sidecar_s"] += clock() - t0
            else:
                try:  # stale sidecar would mass-demote the fresh shards
                    os.remove(sidecar_path(out_base))
                except OSError:
                    pass
            ok = True
        finally:
            exc = sys.exc_info() if not ok else (None, None, None)
            if setup is not None:  # failed before the loop started
                setup.__exit__(*exc)
            if ok:
                self._keep_slots(dev_bufs)
            else:
                lanes.abort()
                drainers.abort()
            st["drain_s"] += ds["drain_s"]
            st["sidecar_s"] += ds["sidecar_s"]
            st["parity_bytes_drained"] += ds["parity_bytes"]
            st["per_device"] = {
                str(i): {"dispatches": dev_dispatches[i],
                         "drain_s": round(dev_drain_s[i], 4)}
                for i in range(nd)}
            with tr.span("pipeline.close"):
                for f in outputs:
                    f.close()
            st["wall_s"] = clock() - t_start
            root.__exit__(*exc)

    # --- rebuild ----------------------------------------------------------
    def rebuild_files(self, base_file_name: str) -> list[int]:
        """Streaming RebuildEcFiles (ec_encoder.go:61,:233-287): regenerate
        every missing .ecNN from >= data_shards survivors with ONE composed
        [missing, k] reconstruction matmul per chunk (decode submatrix
        inversion folded with parity re-encode rows).

        Survivors are verified against the `.eci` sidecar before their
        bytes feed the matmul (inline per dispatch when the dispatch
        width is block-aligned, else one upfront scan); a crc-
        mismatching survivor is DEMOTED to an erasure and the rebuild
        retries with an alternate survivor set — which also regenerates
        the demoted shard.  ShardCorruptError when demotions leave
        fewer than data_shards clean shards."""
        sidecar = EciSidecar.load(base_file_name)
        demoted: set[int] = set()
        try:
            while True:
                try:
                    return self._rebuild_files_once(base_file_name, sidecar,
                                                    demoted)
                except CorruptSurvivor as e:
                    demoted.add(e.shard_id)
                    note_corruption("rebuild", e.shard_id, base_file_name,
                                    block=e.block, tracer=self._tracer())
                self._reap_stale_maps()  # the attempt's views unwound
        finally:
            tb = sys.exc_info()[2]
            if tb is not None:
                # the traceback pins the failed attempt's frames, and the
                # views of its maps they hold (on the CPU backend a
                # dispatch's inputs alias them): drop their locals, so
                # the maps close before the call unwinds
                traceback.clear_frames(tb)
            self._reap_stale_maps()

    def _rebuild_files_once(self, base_file_name: str,
                            sidecar: Optional[EciSidecar],
                            demoted: set[int]) -> list[int]:
        """One rebuild attempt against a fixed clean-survivor set."""
        k, r, b = self.k, self.r, self.dispatch_b
        total = k + r
        has = [os.path.exists(base_file_name + to_ext(i))
               and i not in demoted for i in range(total)]
        if sum(has) < k:
            if demoted:
                raise ShardCorruptError(
                    f"unrepairable: only {sum(has)} clean shards after "
                    f"demoting corrupt {sorted(demoted)}",
                    tuple(sorted(demoted)))
            raise ValueError(
                f"unrepairable: only {sum(has)} of {total} shards present")
        missing = [i for i in range(total) if not has[i]]
        if not missing:
            return []
        survivors = [i for i in range(total) if has[i]][:k]
        if sidecar is not None and sidecar.shard_size != \
                os.path.getsize(base_file_name + to_ext(survivors[0])):
            sidecar = None  # stale sidecar: unverifiable, not rot
        if sidecar is not None:
            # present-but-unchosen shards never feed the matmul, so the
            # inline verify can't see them — scan them here (the CPU
            # rebuild reads ALL present shards and gets this for free):
            # a rotted spare is regenerated NOW instead of surfacing at
            # the next degraded read
            for s in range(total):
                if has[s] and s not in survivors:
                    bad = verify_shard_file(
                        sidecar, base_file_name + to_ext(s), s)
                    if bad:
                        raise CorruptSurvivor(s, bad[0])
        if sidecar is not None and b % sidecar.block_size:
            # dispatch chunks don't land on crc-block boundaries, so the
            # per-dispatch inline verify can't check them — fall back to
            # one upfront scan of each chosen survivor (still before any
            # byte is trusted), then rebuild without inline checks
            for s in survivors:
                bad = verify_shard_file(sidecar, base_file_name + to_ext(s),
                                        s)
                if bad:
                    raise CorruptSurvivor(s, bad[0])
            sidecar = None

        # decode[k,k]: chosen survivors -> original data shards
        sub = [[int(v) for v in self.matrix[i]] for i in survivors]
        decode = mat_invert(sub)
        rows = []
        for m in missing:
            if m < k:
                rows.append(decode[m])
            else:  # parity row composed through the decode matrix
                rows.append(mat_mul([[int(v) for v in self.matrix[m]]],
                                    decode)[0])
        rec = np.array(rows, dtype=np.uint8)
        planes = self._planes(rec)

        inputs = {i: open(base_file_name + to_ext(i), "rb")
                  for i in survivors}
        # validate survivors BEFORE creating any output file: an empty
        # .ecNN left behind by a failed rebuild would count as "present"
        # on the next call and mask the still-missing shard
        try:
            shard_size = os.fstat(inputs[survivors[0]].fileno()).st_size
            for f in inputs.values():
                if os.fstat(f.fileno()).st_size != shard_size:
                    raise ValueError("ec shard size mismatch")
        except BaseException:
            for f in inputs.values():
                f.close()
            raise
        if sidecar is not None and sidecar.shard_size != shard_size:
            sidecar = None  # stale sidecar: unverifiable, not rot
        outputs = {m: open(base_file_name + to_ext(m), "wb")
                   for m in missing}
        # a whole chunk goes up as each survivor's own span of its map,
        # no fill; the tail, shorter than a dispatch, is filled into one
        # zero-padded slot, the kernel's one 2-D shape
        maps: dict[int, _FileMap] = {}
        pending: deque[tuple[object, int, int]] = deque()

        st = self._reset_stats()
        clock = time.perf_counter
        t_start = clock()
        tr = self._tracer()
        root = tr.span("pipeline.rebuild_files", path=base_file_name,
                       mode="staged", missing=len(missing),
                       engine=self.engine)
        root.__enter__()

        def drain_one():
            out_dev, n, d_idx = pending.popleft()
            with tr.span("pipeline.drain", dispatch=d_idx):
                out = self._fetch(out_dev)
            with tr.span("pipeline.write", dispatch=d_idx, kind="rebuilt"):
                for row_i, m in enumerate(missing):
                    outputs[m].write(out[row_i, :n])

        ok = False
        try:
            if shard_size >= b:
                for s in survivors:
                    maps[s] = _FileMap(base_file_name + to_ext(s),
                                       shard_size)
            for offset in range(0, shard_size, b):
                n = min(b, shard_size - offset)
                mapped = n == b
                d_idx = st["dispatches"]
                if mapped:
                    views = [maps[s].span(offset, b) for s in survivors]
                else:
                    slot = np.zeros((k, b), dtype=np.uint8)
                    with tr.span("pipeline.fill", dispatch=d_idx,
                                 bytes=k * n):
                        for row_i, s in enumerate(survivors):
                            preadv_into(inputs[s], [slot[row_i, :n]], offset)
                    views = list(slot)
                if sidecar is not None:
                    # verify before dispatch, on the very bytes that go
                    # up: corrupt bytes must never reach the
                    # reconstruction matmul (the raised CorruptSurvivor
                    # aborts the attempt; the caller demotes and retries
                    # with an alternate survivor)
                    t0 = clock()
                    for s, raw in zip(survivors, views):
                        raw = raw[:n]
                        if faultinject._points:
                            raw = faultinject.corrupt_block(
                                "ec.shard.corrupt", s, raw, offset)
                        bad = sidecar.verify_range(s, offset, raw)
                        if bad is not None:
                            raise CorruptSurvivor(s, bad)
                    st["verify_s"] += clock() - t0
                with tr.span("pipeline.dispatch", dispatch=d_idx,
                             bytes=k * n, source="map" if mapped else "slot"):
                    pending.append((
                        self._dispatch_survivors(planes, views) if mapped
                        else self._dispatch(planes, slot), n, d_idx))
                self._count_dispatch(st, mapped)
                st["bytes_in"] += k * n
                if len(pending) > self.depth:
                    drain_one()
            while pending:
                drain_one()
            ok = True
        finally:
            # drop every view this frame holds, so a map whose dispatches
            # were all fetched closes now (_close_map keeps the others)
            pending.clear()
            views = raw = slot = None
            for m in maps.values():
                self._close_map(m)
            for f in inputs.values():
                f.close()
            for f in outputs.values():
                f.close()
            if not ok:
                # partial outputs must not survive: the next rebuild would
                # see them as present shards
                for m in missing:
                    try:
                        os.remove(base_file_name + to_ext(m))
                    except OSError:
                        pass
            st["wall_s"] = clock() - t_start
            root.__exit__(*(sys.exc_info() if not ok
                            else (None, None, None)))
        return missing
