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
  - every dispatch has the SAME width, so XLA compiles one kernel per
    input layout (tail dispatches are zero-padded, and parity-of-zeros
    is zeros, which is simply not written out);
  - dispatch d+1's host fill and the data-shard writes (data bytes are
    a host-side pass-through) overlap the device compute of dispatch d:
    `device_put` + the jitted kernel return immediately, and the parity
    fetch lags `depth` dispatches behind;
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
    also makes the zero-copy CPU-backend aliasing safe); shm-backed
    worker slots recycle after the parity WRITE (the fetched view
    aliases the slot).

Striping semantics are identical to encoder.write_ec_files (strict-`>`
large rows, zero-padded tails, ec_encoder.go:194-231) — differential
tests enforce byte-identical shards against the CPU path.
"""

from __future__ import annotations

import os
import queue as queue_mod
import sys
import threading
import time
from collections import OrderedDict, deque
from typing import Iterator, Optional

import numpy as np

from ..observability import get_tracer
from ..utils import faultinject
from ..utils.ioutil import pread_padded, preadv_into
from .gf256 import mat_invert, mat_mul
from .integrity import (
    CorruptSurvivor,
    EciSidecar,
    ShardCorruptError,
    SidecarBuilder,
    backfill_sidecar,
    note_corruption,
    sidecar_path,
    verify_shard_file,
)
from .overlap import (AsyncDrainer, DrainerGroup, WorkerGaveUp,
                      WorkerJobError)
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
    without oversubscribing the host; seq-numbered worker protocols
    always drain on one thread regardless (FIFO acks)."""
    n = cores if cores is not None else (os.cpu_count() or 1)
    return max(1, min(4, n - 1))


def _restart_total() -> int:
    """Process-wide parity-worker restart count (all worker kinds);
    encode calls snapshot it so stats["worker_restarts"] is a per-call
    delta.  Best-effort under concurrency: parallel encodes in one
    process can leak restarts into each other's deltas (a false
    "degraded" flag at worst, never a false "clean")."""
    from ..stats import ec_pipeline_metrics

    return ec_pipeline_metrics().totals()["worker_restarts"]


def _fallocate(fd: int, size: int) -> None:
    """Reserve blocks for an output that will be written through an
    mmap'd store: a sparse file's blocks are otherwise allocated at
    fault time, where ENOSPC arrives as an uncatchable SIGBUS instead
    of an OSError.  tmpfs/ext4/xfs all support it; where unsupported
    (EOPNOTSUPP) fall back to truncate and accept the pwrite-era risk."""
    try:
        os.posix_fallocate(fd, 0, size)
    except OSError as e:
        import errno

        if e.errno in (errno.EOPNOTSUPP, errno.EINVAL):
            os.ftruncate(fd, size)
        else:
            raise


def _shard_size(file_size: int, k: int, large: int, small: int) -> int:
    """Bytes per shard for a file striped per encodeDatFile's row rules
    (ec_encoder.go:194-231): whole large rows while more than k*large
    remains, then zero-padded small rows."""
    sz = 0
    remaining = file_size
    while remaining > large * k:
        sz += large
        remaining -= large * k
    while remaining > 0:
        sz += small
        remaining -= small * k
    return sz


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


class _DatMap:
    """The `.dat` mapped read-only for one device encode attempt, so a
    dispatch of whole small-block rows goes to the device as a view of
    the file's own bytes, [rows, K, block] in file order, with no fill.

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
                 engine: str = "auto", devices: Optional[str] = None,
                 zero_copy: bool = True, overlap: str = "auto",
                 tracer=None, drain_timeout_s: float = 30.0,
                 max_worker_restarts: int = 3,
                 max_encode_retries: int = 2,
                 sidecar: bool = True,
                 sidecar_block_size: Optional[int] = None,
                 async_drain: Optional[bool] = None,
                 drain_pool: Optional[int] = None):
        """engine: 'auto' uses the jax device path on a real accelerator
        and the host SIMD codec otherwise (jax-on-CPU is a correctness
        surface, ~200x slower than the AVX2 codec); 'device' forces the
        jax path (tests exercise the XLA kernels with it); 'host' forces
        the SIMD codec; 'mesh' is 'device' under the name of
        `-ec.engine=mesh`.

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

        Self-healing knobs: drain_timeout_s bounds every wait on a
        parity worker ack (a stalled worker surfaces as a fault, never a
        hang); max_worker_restarts is the supervisor's respawn budget
        per worker before the encode degrades to the CPU codec;
        max_encode_retries bounds whole-call retries of the staged
        encode, each resuming from the last fully-drained-and-written
        dispatch checkpoint instead of byte 0.

        sidecar: encodes also write the `.eci` block-crc sidecar
        (ec/integrity.py) and rebuilds verify survivors against it,
        demoting crc-mismatching shards to erasures; sidecar_block_size
        overrides the crc block granularity (default 256KB).

        async_drain: None (auto) engages the multi-buffered async drain
        (overlap.AsyncDrainer) whenever the pipeline has a REAL
        asynchronous producer — device kernel D2H, host worker pool, or
        parity worker process — keeping up to depth+1 dispatches in
        flight while a drainer thread pulls parity back and a writer
        thread appends it in FIFO order; True/False force it on/off.
        The pure-serial host path keeps the inline drain (nothing
        asynchronous to overlap, and its stage spans must sum to the
        wall).  drain_pool overrides the drainer fetch-thread count
        (default: default_drain_pool(), sized from os.cpu_count(),
        bounded [1, 4]; worker-backed encodes always use 1 — the seq
        ack protocol is FIFO)."""
        from .codec import ReedSolomon, best_cpu_engine

        self.k = data_shards
        self.r = parity_shards
        if engine == "auto":
            import jax

            engine = "device" if jax.default_backend() == "tpu" else "host"
        if engine not in ("host", "device", "mesh"):
            # catch the -ec.engine vocabulary ("cpu"/"tpu") early rather
            # than silently taking the jax path
            raise ValueError(
                f"engine must be auto/host/device/mesh, got {engine!r}")
        self.engine = engine
        # host mode prefers the mmap row-pointer path (no staging copies);
        # False forces the staged pipeline (differential tests cover both)
        # an EXPLICIT overlap worker request means the staged pipeline —
        # zero-copy's synchronous mmap path would silently ignore it
        self.zero_copy = zero_copy and overlap not in ("process", "thread")
        self._host_engine = None
        self._host_pool = None
        self._proc_worker = None
        self._file_worker = None  # mmap-path parity process (lazy)
        self._overlap = overlap
        self.drain_timeout_s = drain_timeout_s
        self.max_worker_restarts = max_worker_restarts
        self.max_encode_retries = max_encode_retries
        self._async_drain = async_drain
        self._drain_pool = (max(1, int(drain_pool)) if drain_pool
                            else default_drain_pool())
        # stats counters are bumped from the drainer/writer threads
        # too — and _st_lock also serializes worker-handle claims
        # (_drop_file_worker/_abandon_proc_worker run on the drainer
        # thread AND the producer) plus the stale-worker list and the
        # lazy fallback-engine init
        self._st_lock = threading.Lock()
        self._sidecar = sidecar
        self._sidecar_bs = sidecar_block_size
        # lazy CPU codec for per-dispatch fallback
        self._fb_engine = None  # guarded-by: _st_lock
        # abandoned (killed, shm kept) workers whose buffers may still
        # back live views; fully closed once the encode call unwinds
        self._stale_workers: list = []  # guarded-by: _st_lock
        # the device engines' slice and word kernel (None in host mode)
        self._devs = None
        self._kernel = None
        # the staged or queue plane's slot buffers, kept between encodes
        self._slot_bufs = None  # guarded-by: _st_lock
        b = dispatch_mb << 20
        if engine == "host":
            self.on_tpu = False
            self._host_engine = best_cpu_engine()
            # one worker gives the host codec the same overlap the device
            # path gets for free: the SIMD matmul computes dispatch d
            # while the main thread fills and writes dispatch d+1.  ONE
            # worker: dispatch order must match drain order, and the
            # codec is already memory-bound so more workers would just
            # thrash cache.  overlap kinds:
            #   "thread"  in-process worker (ctypes call releases the
            #             GIL) — needs a second core or it GIL-convoys
            #             (measured ~7x WORSE than serial on 1 core)
            #   "process" separate process over shared memory
            #             (ec/overlap.py) — the mechanism bench.py
            #             measures on/off for the README overlap claim
            #   "auto"    thread when >1 core, else none; on the mmap
            #             path, a FileParityWorker process when >1 core
            #   "mmap-process"  force the mmap-path parity process
            #   "none"    synchronous
            # (no pool when the zero-copy mmap path will serve encodes —
            # it is synchronous and the idle thread would just leak)
            if overlap == "thread" or (
                    overlap == "auto" and (os.cpu_count() or 1) > 1
                    and self._native_ptrs() is None):
                import concurrent.futures
                import weakref

                self._host_pool = concurrent.futures.ThreadPoolExecutor(
                    max_workers=1, thread_name_prefix="ec-host")
                # encoders are sometimes created per-operation: the
                # worker must not outlive its encoder
                weakref.finalize(self, self._host_pool.shutdown,
                                 wait=False)
        else:
            import jax

            from ..ops.device import device_backend, ensure_compile_cache
            from ..ops.gf_matmul import DEFAULT_TILE_B, expand_matrix_bitplanes
            from ..parallel.mesh import device_encode_fn, parse_device_spec

            self._jax = jax
            self._expand = expand_matrix_bitplanes
            # a TPU, or a process pinned to the CPU — never a silent CPU
            self.on_tpu = device_backend() == "tpu"
            ensure_compile_cache()
            self._devs = parse_device_spec(devices)
            # every device of the slice runs the one-device word kernel
            # on whole dispatches
            self._kernel = device_encode_fn(on_tpu=self.on_tpu)
            # one fixed dispatch width: a multiple of the pallas tile on
            # TPU, of 64 (the u32 word layout) on the CPU; a row group's
            # block must be one too (_row_group)
            q = DEFAULT_TILE_B if self.on_tpu else 64
            b = max(q, (b // q) * q)
            self._row_q = q
        self.dispatch_b = b
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
        self._plane_cache_max = max(8, 2 * len(self._devs or ()))
        # per-call pipeline counters (_reset_stats); where the time of
        # each stage went is the pipeline.* spans' job
        self.stats: dict[str, float] = {}
        # span tracer (observability/tracer.py): None follows the
        # process-global tracer, which is a no-op until enabled — the
        # per-dispatch spans below cost one attribute check when dormant
        self.tracer = tracer

    def _tracer(self):
        return self.tracer if self.tracer is not None else get_tracer()

    def _merge_worker_span(self, tr, worker, root_id, dispatch: int) -> None:
        """Fold the worker process's compute window (shipped back in its
        ack — a serializable span log) into the parent timeline, parented
        under the pipeline's root span."""
        job = getattr(worker, "last_job_span", None)
        if job is not None:
            tr.add_span("worker.compute", job[0], job[1], parent_id=root_id,
                        thread=f"ec-worker-{worker.worker_pid}",
                        tid=worker.worker_pid, dispatch=dispatch,
                        worker_pid=worker.worker_pid)

    # --- kernel dispatch --------------------------------------------------
    def _planes(self, rows: np.ndarray, dev: int = 0):
        """Device mode: the bit-plane expansion, cached resident on
        device `dev` of the slice, so no dispatch waits on a plane
        transfer.  Host mode: the raw GF(2^8) rows, consumed by the SIMD
        codec."""
        rows = np.ascontiguousarray(rows)
        if self.engine == "host":
            return rows
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
        """Device mode, async: the [K, dispatch_b] u8 slot, or a row
        group's [rows, K, block] u8 view of the mapped `.dat`, goes up
        to device `dev` of the slice as u32 words (a free view:
        C-contiguous, last axis a multiple of 4) and comes back an
        unfetched [R, dispatch_b//4] u32 device array in the same word
        layout (ops/gf_matmul.py) either way, with the D2H copy already
        queued behind the kernel, so the fetch streams down while later
        dispatches compute.  Host mode: the SIMD codec runs
        synchronously and the parity comes back finished."""
        if self.engine == "host":
            if self._host_pool is None:
                return self._host_engine.matmul(planes, buf)
            return self._host_pool.submit(self._host_engine.matmul,
                                          planes, buf)
        # rows land on a TPU in its default layout for [rows, K, W],
        # K-major, and the jitted kernel starts with a copy to the
        # row-major layout it reads: that copy is part of the dispatch
        out = self._kernel(planes, self._jax.device_put(
            buf.view(np.uint32), self._devs[dev]))
        out.copy_to_host_async()
        return out

    def _map_dat(self, dat_path: str, file_size: int, large: int,
                 small: int) -> Optional[_DatMap]:
        """The `.dat` mapped for row-group dispatches, or None where no
        dispatch can be one: the host engine, or a volume shorter than
        one group, or blocks that do not divide the dispatch."""
        b = self.dispatch_b
        if self._devs is None or file_size < self.k * b or not any(
                blk and b % blk == 0 and blk % self._row_q == 0
                for blk in (large, small)):
            return None
        return _DatMap(dat_path, file_size)

    def _row_group(self, fills: list, used: int,
                   dat_map: Optional[_DatMap]) -> Optional[np.ndarray]:
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
        """One dispatch of the staged or queue plane, in the call's
        stats and the process counters /status reports (EcPipeline
        `dispatches`, `mapped_dispatches`)."""
        from ..stats import ec_pipeline_metrics

        st["dispatches"] += 1
        st["mapped_dispatches"] += mapped
        ec_pipeline_metrics().dispatches.inc("map" if mapped else "slot")

    def _write_data(self, outputs: list, sb, src: np.ndarray,
                    used: int) -> float:
        """Data shards pass through from the dispatch's input: shard i
        takes a slot's row i, or block i of each row of a group in
        order; the `.eci` crc stream takes each slice right after its
        write, while the slice is still in the CPU's cache (one vectored
        write a shard, crc after, ran slower on a v5e host).  Returns
        the crc time."""
        crc_s = 0.0
        for i in range(self.k):
            for p in (src[:, i] if src.ndim == 3 else (src[i, :used],)):
                outputs[i].write(memoryview(p))
                if sb is not None:
                    t0 = time.perf_counter()
                    sb.update(i, p)
                    crc_s += time.perf_counter() - t0
        return crc_s

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

    def _close_map(self, dat_map: Optional[_DatMap]) -> None:
        """Close an attempt's map, or leave it to _reap_stale_workers
        while a view lives (a transfer not yet released, a fault's
        traceback)."""
        if dat_map is not None and not dat_map.close():
            with self._st_lock:
                self._stale_workers.append(dat_map)

    def _fetch(self, out_dev) -> np.ndarray:  # thread-entry
        """Blocking fetch + host-side unpack back to [R, dispatch-width]
        u8.  Runs on the async drainer's fetch thread."""
        import concurrent.futures

        if isinstance(out_dev, tuple) and out_dev[0] == "proc":
            if self._proc_worker is None:
                # worker already abandoned mid-encode: the still-pending
                # handles behind it surface uniformly as gave-up so the
                # fallback accounting stays truthful
                raise WorkerGaveUp("parity worker already abandoned")
            return self._proc_worker.fetch(out_dev[1])
        if isinstance(out_dev, concurrent.futures.Future):  # host worker
            return out_dev.result()
        if isinstance(out_dev, np.ndarray):  # host mode: already finished
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
                      # dispatches sent as views of the mapped .dat
                      # (_row_group), no fill
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
                      # drainer ran with (0 = inline serial drain)
                      "drain_s": 0.0, "parity_bytes_drained": 0,
                      "drain_pool": 0}
        self._restart_base = _restart_total()  # weedlint: disable=W502 rebound before the drain threads exist
        return self.stats

    # --- self-healing helpers ---------------------------------------------
    def _cpu_parity(self, data: np.ndarray) -> np.ndarray:
        """Per-dispatch CPU fallback: parity for [k, n] data through the
        host codec — byte-identical to every other engine by the
        differential-test contract."""
        with self._st_lock:
            if self._fb_engine is None:
                from .codec import best_cpu_engine

                self._fb_engine = (self._host_engine
                                   if self._host_engine is not None
                                   else best_cpu_engine())
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

    def _drain_async_enabled(self) -> bool:
        """Async drain engages whenever the pipeline has a REAL
        asynchronous producer whose results arrive later (device kernel
        D2H, host worker-pool future, parity-worker ack).  The pure-
        serial host path keeps the inline drain: there is nothing to
        overlap, and its per-dispatch stage spans must still sum to the
        wall (the tracing contract)."""
        if self._async_drain is not None:
            return self._async_drain
        return (self.engine != "host" or self._host_pool is not None
                or self._proc_worker is not None)

    def _abandon_proc_worker(self) -> None:
        """Kill the staged worker but keep its shared memory alive: the
        encode keeps using the input slots as plain staging buffers for
        CPU-fallback compute; the worker is fully closed once the call's
        views unwind (_reap_stale_workers)."""
        # atomic claim: the producer's submit-failure path and the
        # drainer's fetch-failure path can race here — exactly one
        # caller may own the abandon+stash, or the worker is torn down
        # twice
        with self._st_lock:
            w = self._proc_worker
            self._proc_worker = None
        if w is not None:
            try:
                w.abandon()
            except Exception:  # pragma: no cover - already-dead races
                pass
            with self._st_lock:
                self._stale_workers.append(w)

    def _finish_sidecar_backfill(self, out_base: str, st: dict,
                                 clock) -> None:
        """Write the `.eci` sidecar after a completed encode whose
        parity never passed through host buffers (mmap path: the
        kernel's stores went straight into the output mappings) — one
        read-back pass over the page-cache-hot shard files.  With
        sidecars disabled, drop any stale one instead: its table
        describes the previous encode's bytes and would mass-demote the
        fresh shards."""
        t0 = clock()
        if self._sidecar:
            with self._tracer().span("ec.sidecar.backfill", path=out_base):
                backfill_sidecar(out_base, self.k + self.r,
                                 self._sidecar_bs)
        else:
            try:
                os.remove(sidecar_path(out_base))
            except OSError:
                pass
        st["sidecar_s"] += clock() - t0

    def _reap_stale_workers(self) -> None:
        with self._st_lock:
            stale, self._stale_workers = self._stale_workers, []
        if not stale:
            return
        # the encode's flush/drain closures form reference cycles that
        # keep shm-backed buffer views alive past the call's return;
        # collect them now so close() can actually release the mappings
        # (rare path: only runs after a mid-encode worker abandonment
        # or a failed device attempt)
        import gc

        gc.collect()
        keep = []
        for w in stale:
            try:
                if w.close() is False:
                    # a mapped .dat whose view the exception being
                    # handled still holds: the next reap closes it
                    keep.append(w)
            except Exception:  # pragma: no cover - best-effort teardown
                pass
        if keep:
            with self._st_lock:
                self._stale_workers.extend(keep)

    # --- zero-copy host path ----------------------------------------------
    def _native_ptrs(self):
        """The row-pointer native matmul, or None (no toolchain / forced
        off / non-host engine)."""
        if self.engine != "host" or not self.zero_copy:
            return None
        from .. import native

        if native.load() is None:
            return None
        return native.gf_matmul_ptrs

    def _file_parity_worker(self, mat: np.ndarray, dat_path: str):
        """Lazily-spawned FileParityWorker for the mmap encode, or None
        (overlap off / spawn failed).  Cached across encodes — the
        ~200ms spawn amortizes over a volume's many dispatches and over
        repeated encodes; each file is re-opened in the worker."""
        # MEASURED on a 1-core tmpfs host: no win (pwrite is kernel-mode
        # memcpy, the core is busy during writes — 1118 serial vs 1038
        # worker MB/s), so auto engages only with a second core, where
        # compute genuinely runs beside the write syscalls.
        # "mmap-process" forces it (differential tests).
        if self._overlap == "mmap-process":
            pass
        elif self._overlap != "auto" or (os.cpu_count() or 1) <= 1:
            return None
        with self._st_lock:
            w = self._file_worker
        if w is not None and w and w.b != self.dispatch_b:
            # slot geometry is baked into the worker's shm ring: a stale
            # b would silently truncate parity columns
            self._drop_file_worker()
            w = None
        if w is None:
            try:
                import weakref

                from .overlap import FileParityWorker

                w = FileParityWorker(
                    self.k, self.r, self.dispatch_b, mat,
                    ack_timeout=self.drain_timeout_s,
                    max_restarts=self.max_worker_restarts)
                weakref.finalize(self, FileParityWorker.close, w)
            except Exception:
                w = False  # don't retry every encode
            with self._st_lock:
                self._file_worker = w
        if not w:
            return None
        try:
            w.open(dat_path)
        except Exception:
            # dead or desynced worker: drop it so the next encode
            # respawns (~200ms) instead of stalling on a corpse
            self._drop_file_worker()
            return None
        return w

    def _drop_file_worker(self) -> None:  # thread-entry
        """Runs on the drainer's fetch thread (gave-up fallback) AND
        the producer (submit failure): the claim must be atomic or a
        race tears the same worker down twice."""
        with self._st_lock:
            w = self._file_worker
            self._file_worker = None
        if w:
            try:
                w.close()
            except Exception:  # pragma: no cover - best-effort teardown
                pass

    def _encode_file_mmap(self, dat_path: str, out_base: str,
                          large: int, small: int, matmul_ptrs) -> None:
        """Zero-copy encode: the input volume is mmap'd and the SIMD
        matmul reads it in place — no fill phase.  Parity outputs are
        mmap'd too (bulk pre-faulted via MADV_POPULATE_WRITE where the
        kernel supports it) so the matmul's stores land straight in the
        page cache: parity is written ONCE by the kernel instead of
        staged + pwritten — r/k of the volume saved a full pass.  Data
        shards are pwritten straight from the input mapping (the one
        unavoidable kernel-side copy)."""
        import mmap as mmap_mod

        k, r = self.k, self.r
        st = self._reset_stats()
        clock = time.perf_counter
        t_start = clock()
        file_size = os.path.getsize(dat_path)
        tr = self._tracer()
        root = tr.span("pipeline.encode_file", path=dat_path,
                       bytes=file_size, mode="mmap", engine=self.engine)
        root.__enter__()
        shard_size = _shard_size(file_size, k, large, small)
        mat = np.ascontiguousarray(self.matrix[k:])
        # "r+b" when the shard file already exists: every byte of every
        # output is written below (_plan_entries coverage is total, tail
        # rows ride zero-padded buffers), so re-encode over old shards
        # need not truncate-to-zero first — that frees every page cache
        # page only for the pwrites/stores to re-allocate (and re-zero)
        # them all
        outs = []
        try:
            for i in range(k + r):
                p = out_base + to_ext(i)
                outs.append(open(p, "r+b" if os.path.exists(p) else "w+b"))
            out_fds = [f.fileno() for f in outs]
            in_f = open(dat_path, "rb")
        except BaseException:
            # the finally below never runs if we die before its try:
            # close what opened and unwind the span stack (tagging the
            # root span with the real exception)
            for f in outs:
                f.close()
            root.__exit__(*sys.exc_info())
            raise
        in_map = None
        in_mv = None
        tail_buf: Optional[np.ndarray] = None
        parity_maps: list = []
        parity_addrs: list[int] = []
        ok = False
        try:
            for f in outs:
                # full-size upfront: pwrite fills real bytes; anything a
                # tail entry skips past EOF stays a correct zero
                f.truncate(shard_size)
            if shard_size == 0:
                ok = True
                return
            # parity outputs are mmap'd so the SIMD kernel stores parity
            # STRAIGHT into the page cache — one pass instead of the old
            # stage-buffer store + pwrite copy (a full extra traversal of
            # r/k of the volume).  Data shards keep pwrite: their copy
            # from the input mapping is unavoidable either way.  Created
            # LAZILY: with the overlap worker active parity arrives via
            # pwrite-from-shm, and populating r*shard_size of pages
            # upfront would be a wasted serial pass.
            map_lock = threading.Lock()

            def parity_mappings() -> list[int]:
                # called from the main thread (inline compute) AND the
                # drainer's fetch thread (fallback recompute): the lazy
                # init must not run twice
                with map_lock:
                    return _parity_mappings_locked()

            def _parity_mappings_locked() -> list[int]:
                if parity_addrs:
                    return parity_addrs
                for j in range(r):
                    # reserve blocks NOW so disk-full is a catchable
                    # OSError here, not a SIGBUS under the kernel's
                    # stores into a sparse mapping
                    _fallocate(out_fds[k + j], shard_size)
                    pm = mmap_mod.mmap(out_fds[k + j], shard_size,
                                       access=mmap_mod.ACCESS_WRITE)
                    try:
                        # bulk pre-fault (MADV_POPULATE_WRITE, Linux
                        # 5.14+): one syscall instead of a per-page trap
                        # under the kernel's stores; harmless to skip
                        pm.madvise(getattr(mmap_mod,
                                           "MADV_POPULATE_WRITE", 23))
                    except (OSError, ValueError):
                        pass
                    parity_maps.append(pm)
                    parity_addrs.append(
                        np.frombuffer(pm, dtype=np.uint8).ctypes.data)
                return parity_addrs

            in_map = mmap_mod.mmap(in_f.fileno(), 0,
                                   access=mmap_mod.ACCESS_READ)
            if hasattr(in_map, "madvise"):
                in_map.madvise(mmap_mod.MADV_SEQUENTIAL)
            in_arr = np.frombuffer(in_map, dtype=np.uint8)
            in_mv = memoryview(in_map)
            in_addr = in_arr.ctypes.data
            # parity worker: a separate process mmaps the SAME file and
            # computes dispatch d+1's parity while this process sits in
            # pwrite for dispatch d — kernel-mode write time and SIMD
            # compute overlap even on one core (bench.py measures the
            # mechanism at ~1.5-1.8x there)
            worker = self._file_parity_worker(mat, dat_path)
            # async multi-buffered drain: the ONLY drain this path has
            # is the parity worker's ack stream, so the drainer engages
            # exactly when the worker does.  One fetch thread pulls acks
            # FIFO (seq protocol), the writer thread pwrites parity from
            # the shm slots, and the MAIN thread keeps submitting spans
            # and pwriting data shards — compute, parity writeback and
            # data writes all overlap.  wstate lets the fetch thread
            # retire a gave-up worker so the main loop switches to
            # inline compute without a lock.
            wstate: dict = {"worker": worker}
            slot_q: queue_mod.Queue = queue_mod.Queue()
            ds = {"drain_s": 0.0, "parity_bytes": 0}
            ds_lock = threading.Lock()
            drainer: Optional[AsyncDrainer] = None

            def drain_fetch(meta):
                """Fetch ONE dispatch's parity from the worker (drainer
                fetch thread) — fault/fallback recompute lands straight
                in the parity mappings, exactly like the serial path."""
                slot, n, off, base, block, d_idx = meta
                w = wstate["worker"]
                parity = None
                t0 = clock()
                with tr.span("pipeline.drain", dispatch=d_idx,
                             bytes=r * n):
                    # injected drain fault: per-dispatch semantics —
                    # THIS dispatch recomputes serially, the worker
                    # (which did the work) gets its FIFO realigned and
                    # keeps the rest of the encode.  Hit inside the span
                    # so delay-only faults attribute to drain
                    drain_fault = False
                    if faultinject._points:
                        try:
                            faultinject.hit("ec.drain")
                        except Exception:
                            drain_fault = True
                    if drain_fault:
                        if w is not None:
                            w.skip_next()
                        self._note_fallback(st, "drain_fault")
                        tr.event("pipeline.fallback", dispatch=d_idx,
                                 reason="drain_fault")
                    else:
                        try:
                            if w is None:  # lost on an earlier dispatch
                                raise WorkerGaveUp("parity worker lost")
                            parity = w.fetch(slot)[:, :n]
                        except WorkerJobError:
                            # the job failed INSIDE a live worker
                            # (input file vanished under it): recompute
                            # this one dispatch, keep the worker
                            self._note_fallback(st, "worker_job")
                            tr.event("pipeline.fallback",
                                     dispatch=d_idx, reason="worker_job")
                        except (KeyboardInterrupt, SystemExit):
                            raise
                        except Exception as e:
                            if drainer is not None and drainer.aborting:
                                raise  # teardown race, not a fault
                            # supervision exhausted its respawn budget
                            # (WorkerGaveUp) or desynced: recompute the
                            # lost dispatches serially, finish without it
                            self._drop_file_worker()
                            wstate["worker"] = None
                            reason = ("worker_gave_up"
                                      if isinstance(e, WorkerGaveUp)
                                      else "worker_error")
                            self._note_fallback(st, reason)
                            tr.event("pipeline.fallback",
                                     dispatch=d_idx, reason=reason)
                fetch_s = clock() - t0
                if parity is not None:
                    self._merge_worker_span(tr, w, root.span_id, d_idx)
                    with ds_lock:
                        ds["drain_s"] += fetch_s
                        ds["parity_bytes"] += int(parity.nbytes)
                    return parity  # slot recycles after the pwrite
                with ds_lock:
                    ds["drain_s"] += fetch_s
                with tr.span("pipeline.compute", dispatch=d_idx,
                             bytes=k * n):
                    matmul_ptrs(
                        mat,
                        [in_addr + base + i * block for i in range(k)],
                        [a + off for a in parity_mappings()], n)
                slot_q.put(slot)
                return None

            def drain_write(meta, parity):
                if parity is None:  # fallback already stored via mmap
                    return
                slot, n, off, base, block, d_idx = meta
                with tr.span("pipeline.write", dispatch=d_idx,
                             kind="parity"):
                    for j in range(r):
                        os.pwrite(out_fds[k + j],
                                  memoryview(parity[j, :n]), off)
                # parity was pwritten straight from the shm out slot:
                # only now may the worker compute into it again
                slot_q.put(slot)

            if worker is not None:
                drainer = AsyncDrainer(drain_fetch, drain_write,
                                       pool_size=1,
                                       queue_depth=worker.nbufs + 2,
                                       name="ec-mmap-drain")
                for i in range(worker.nbufs):
                    slot_q.put(i)
                st["drain_pool"] = drainer.pool_size

            def acquire_slot() -> int:
                if drainer.error is not None:
                    raise drainer.error
                try:
                    return slot_q.get_nowait()
                except queue_mod.Empty:
                    pass
                # every shm slot is in flight: the residual drain stall
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
                                    "parity slot")

            try:
                out_off = 0
                for n, row_start, block, off in _plan_entries(
                        file_size, k, large, small, self.dispatch_b):
                    base = row_start + off
                    if base + (k - 1) * block + n <= file_size:
                        w = wstate["worker"]
                        # injected dispatch fault: per-dispatch
                        # semantics — THIS dispatch computes inline,
                        # the worker keeps the rest of the encode
                        dispatch_fault = False
                        if faultinject._points:
                            try:
                                faultinject.hit("ec.dispatch")
                            except Exception:
                                dispatch_fault = True
                        if w is not None and dispatch_fault:
                            self._note_fallback(st, "dispatch_fault")
                            tr.event("pipeline.fallback",
                                     dispatch=st["dispatches"],
                                     reason="dispatch_fault")
                        elif w is not None:
                            slot = acquire_slot()  # may block: backpressure
                            submitted = False
                            with tr.span("pipeline.dispatch",
                                         dispatch=st["dispatches"],
                                         bytes=k * n):
                                try:
                                    w.submit(slot, base, block, n)
                                    submitted = True
                                except (KeyboardInterrupt, SystemExit):
                                    raise
                                except Exception as e:
                                    # submit path gave up: the drainer
                                    # recomputes what's in flight,
                                    # finish without the worker
                                    self._drop_file_worker()
                                    wstate["worker"] = None
                                    reason = ("worker_gave_up"
                                              if isinstance(e, WorkerGaveUp)
                                              else "worker_error")
                                    self._note_fallback(st, reason)
                                    tr.event("pipeline.fallback",
                                             dispatch=st["dispatches"],
                                             reason=reason)
                            if submitted:
                                d_idx = st["dispatches"]
                                # data shards pwrite NOW, from the input
                                # mapping, while the worker computes the
                                # parity this dispatch just submitted
                                with tr.span("pipeline.write",
                                             dispatch=d_idx, kind="data"):
                                    for i in range(k):
                                        s = base + i * block
                                        os.pwrite(out_fds[i],
                                                  in_mv[s:s + n], out_off)
                                drainer.submit((slot, n, out_off, base,
                                                block, d_idx))
                                st["dispatches"] += 1
                                st["bytes_in"] += k * n
                                out_off += n
                                continue
                            slot_q.put(slot)  # submit failed: slot unused
                        # all k source rows fully inside the file: matmul
                        # in place from the mapping, parity stored
                        # straight into the output mappings
                        with tr.span("pipeline.compute",
                                     dispatch=st["dispatches"], bytes=k * n):
                            matmul_ptrs(
                                mat,
                                [in_addr + base + i * block
                                 for i in range(k)],
                                [a + out_off for a in parity_mappings()], n)
                        with tr.span("pipeline.write",
                                     dispatch=st["dispatches"], kind="data"):
                            for i in range(k):
                                s = base + i * block
                                os.pwrite(out_fds[i], in_mv[s:s + n],
                                          out_off)
                    else:
                        # tail entry: some rows cross EOF — stage through
                        # a zero-padded buffer (ec_encoder.go:172-176)
                        with tr.span("pipeline.fill",
                                     dispatch=st["dispatches"], tail=True):
                            if tail_buf is None or tail_buf.shape[1] < n:
                                tail_buf = np.zeros((k, n), dtype=np.uint8)
                            else:
                                tail_buf[:, :n] = 0
                            for i in range(k):
                                s = base + i * block
                                e = min(file_size, s + n)
                                if e > s:
                                    tail_buf[i, :e - s] = in_arr[s:e]
                        buf = tail_buf[:, :n]
                        row = buf.strides[0]
                        with tr.span("pipeline.compute",
                                     dispatch=st["dispatches"], bytes=k * n):
                            matmul_ptrs(
                                mat,
                                [buf.ctypes.data + i * row
                                 for i in range(k)],
                                [a + out_off for a in parity_mappings()], n)
                        with tr.span("pipeline.write",
                                     dispatch=st["dispatches"], kind="data"):
                            for i in range(k):
                                os.pwrite(out_fds[i], memoryview(buf[i]),
                                          out_off)
                    st["dispatches"] += 1
                    st["bytes_in"] += k * n
                    out_off += n
                if drainer is not None:
                    # tail stall: the last in-flight parity finishes
                    # fetching + writing
                    with tr.span("pipeline.drain_wait", final=True):
                        drainer.finish()
            finally:
                if drainer is not None:
                    if drainer.inflight:
                        # abnormal exit with submitted-but-undrained
                        # jobs: their acks would desync the next
                        # encode's protocol.  Flag the abort FIRST so
                        # the fetch thread skips recovery/fallback, then
                        # abandon+drop the worker so a blocked fetch
                        # unwinds fast; a later encode respawns fresh
                        drainer.aborting = True
                        w = wstate["worker"]
                        if w is not None:
                            try:
                                w.abandon()
                            except Exception:  # pragma: no cover
                                pass
                        self._drop_file_worker()
                        wstate["worker"] = None
                    # join the drain threads BEFORE the input views are
                    # released below (the fetch fallback reads in_addr)
                    drainer.abort()
                    st["drain_s"] += ds["drain_s"]
                    st["parity_bytes_drained"] += ds["parity_bytes"]
                # the view and exported memoryview must drop before the
                # mmap closes or close() raises BufferError
                if in_mv is not None:
                    in_mv.release()
                del in_arr
            self._finish_sidecar_backfill(out_base, st, clock)
            ok = True
        finally:
            for pm in parity_maps:
                try:
                    pm.close()
                except BufferError:
                    pass
            if in_map is not None:
                in_map.close()
            in_f.close()
            for f in outs:
                f.close()
            st["wall_s"] = clock() - t_start
            st["worker_restarts"] = int(_restart_total() -
                                        self._restart_base)
            # a failed encode tags the root span with the in-flight
            # exception (ok gates against a stale caller-level exc_info)
            root.__exit__(*(sys.exc_info() if not ok
                            else (None, None, None)))

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
        matmul_ptrs = self._native_ptrs()
        if matmul_ptrs is not None:
            return self._encode_file_mmap(
                dat_path, out_base, large_block_size, small_block_size,
                matmul_ptrs)
        retries = 0
        start_entry = start_byte = 0
        # the slice picks the plane: per-device queues on more than one
        # device, which share the staged pipeline's checkpoint-resume
        # contract (self._ckpt) so the retry loop below serves both
        attempt = (self._encode_file_mesh if len(self._devs or ()) > 1
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
                    self._reap_stale_workers()  # attempt's views unwound
                    start_entry, start_byte = ck_entry, ck_byte
                    self._tracer().event(
                        "pipeline.retry", scope="encode_file",
                        attempt=retries, resume_entry=ck_entry,
                        resume_byte=ck_byte,
                        error=f"{type(e).__name__}: {e}")
        finally:
            self._reap_stale_workers()

    def _encode_file_staged(self, dat_path: str, out_base: str,
                            large_block_size: int, small_block_size: int,
                            start_entry: int = 0, start_byte: int = 0,
                            retries: int = 0) -> None:
        """One attempt of the staged (non-mmap) pipeline, starting at
        plan entry start_entry / shard byte start_byte.  Maintains
        self._ckpt = (entries drained+written, bytes per shard) as the
        contiguous-completion checkpoint (drain order is FIFO, so the
        completed prefix is always contiguous).  Per-dispatch engine
        decisions: a worker fault heals via supervision inside fetch();
        a worker that gave up, or a failing device dispatch/fetch,
        degrades THIS dispatch (and, for terminal faults, the rest of
        the encode) to the CPU codec — byte-identical output either
        way."""
        k, r, b = self.k, self.r, self.dispatch_b
        st = self._reset_stats()
        st["retries"] = retries
        self._ckpt = (start_entry, start_byte)  # weedlint: disable=W502 producer writes it before the drainer starts; the writer thread advances it and the producer re-reads only after abort() joins
        clock = time.perf_counter
        t_start = clock()
        planes = self._planes(self.matrix[k:])
        file_size = os.path.getsize(dat_path)
        st["devices"] = 1 if self._devs else 0
        tr = self._tracer()
        root = tr.span("pipeline.encode_file", path=dat_path,
                       bytes=file_size, mode="staged", engine=self.engine,
                       devices=st["devices"], resume_entry=start_entry)
        root.__enter__()
        # setup covers output opens (O_TRUNC over existing shards frees
        # their page cache — real, attributable time), buffer allocation
        # and worker spawn; ends when the first entry is planned
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
            if self.engine == "host" and self._overlap == "process":
                if self._proc_worker is not None \
                        and self._proc_worker.b != b:
                    self._proc_worker.close()  # dispatch width changed
                    self._proc_worker = None  # weedlint: disable=W502 encode setup: the previous encode's drain threads were joined in its finally
                if self._proc_worker is None:
                    from .overlap import ProcessOverlapWorker

                    try:
                        self._proc_worker = ProcessOverlapWorker(  # weedlint: disable=W502 encode setup: no drain thread exists yet
                            k, r, b, self.matrix[k:], self.depth + 1,
                            ack_timeout=self.drain_timeout_s,
                            max_restarts=self.max_worker_restarts)
                    except Exception as e:
                        # no worker is a degraded mode, not a failure:
                        # the encode runs synchronously on the CPU codec
                        self._note_fallback(st, "worker_spawn")
                        tr.event("pipeline.fallback", reason="worker_spawn",
                                 error=f"{type(e).__name__}: {e}")
            # process overlap: dispatch buffers ARE the shared-memory pool
            slots = None if self._proc_worker is not None \
                else self._take_slots(1, self.depth + 1)
            bufs = self._proc_worker.bufs if slots is None else slots[0]
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
        free: deque[int] = deque(range(len(bufs)))
        # (parity handle, packed width, buffer index, dispatch index,
        #  entries packed into the dispatch, mapped rows or None)
        pending: deque[tuple] = deque()

        ok = False
        flags = {"degraded": False}  # terminal fault: rest goes CPU
        # concurrent-side accounting (drainer fetch threads + writer
        # thread own these keys; folded into st once the threads join)
        ds = {"drain_s": 0.0, "sidecar_s": 0.0, "parity_bytes": 0}
        ds_lock = threading.Lock()
        slot_q: queue_mod.Queue = queue_mod.Queue()
        drainer: Optional[AsyncDrainer] = None
        def drain_fetch_core(meta):
            """Fetch (or fault/fallback-recompute) ONE dispatch's parity
            — the only place kernel output crosses back to the host.
            Runs on the drainer's fetch pool in async mode, inline on
            the pipeline thread in serial mode.  Returns
            (parity[:, :u], fetch_s, fetched_bytes)."""
            parity_dev, u, bi, d_idx, nfills, rows = meta
            is_proc = isinstance(parity_dev, tuple) and \
                parity_dev[0] == "proc"
            parity = None
            reason = None
            nbytes = 0
            t0 = clock()
            with tr.span("pipeline.drain", dispatch=d_idx, bytes=r * u):
                # injected drain fault: the dispatch recomputes on the
                # CPU, the worker (which did the work) gets its FIFO
                # realigned.  Hit INSIDE the span so a delay-only fault
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
                    if is_proc and self._proc_worker is not None:
                        self._proc_worker.skip_next()
                else:
                    try:
                        parity = self._fetch(parity_dev)
                        # parity-only accounting: what actually crossed
                        # the link (r/k of bytes_in — data shards never
                        # transfer back)
                        nbytes = int(parity.nbytes)
                    except WorkerJobError:
                        # failed inside a live worker: recompute this one
                        # dispatch, keep the worker (seq already consumed)
                        reason = "worker_job"
                    except (KeyboardInterrupt, SystemExit):
                        raise
                    except Exception as e:
                        if drainer is not None and drainer.aborting:
                            raise  # teardown race, not a pipeline fault
                        if isinstance(e, WorkerGaveUp):
                            reason = "worker_gave_up"
                        elif is_proc:
                            reason = "worker_error"  # protocol desync
                        else:
                            reason = "device_fetch"
                        if is_proc:
                            self._abandon_proc_worker()
                        flags["degraded"] = True
            fetch_s = clock() - t0
            if parity is not None and is_proc and \
                    self._proc_worker is not None:
                self._merge_worker_span(tr, self._proc_worker,
                                        root.span_id, d_idx)
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
            return parity[:, :u], fetch_s, nbytes

        def drain_write_core(meta, parity):
            """Write ONE dispatch's parity + crc stream and advance the
            FIFO checkpoint; runs on the writer thread in async mode.
            Returns the crc time (sidecar_s)."""
            parity_dev, u, bi, d_idx, nfills, _ = meta
            sc = 0.0
            # entries pack side by side, so each parity row's bytes for
            # this dispatch are one contiguous slice
            with tr.span("pipeline.write", dispatch=d_idx, kind="parity"):
                for j in range(r):
                    outputs[k + j].write(memoryview(parity[j, :u]))
                if sb is not None:
                    # drain order is FIFO == write order (the async
                    # writer consumes in submission order), so each
                    # parity row's crc stream stays sequential; the crc
                    # time counts as write stage and is broken out in
                    # sidecar_s for the bench overhead figure
                    t1 = clock()
                    for j in range(r):
                        sb.update(k + j, parity[j, :u])
                    sc = clock() - t1
            # dispatch d_idx is fully drained AND written on every shard:
            # advance the resume checkpoint past its entries/bytes
            ck_e, ck_b = self._ckpt
            self._ckpt = (ck_e + nfills, ck_b + u)  # weedlint: disable=W502 writer thread owns the checkpoint while draining; the producer reads it only after the drainer is joined (happens-before)
            return sc

        def drain_one():
            """Serial drain: fetch + write inline on the pipeline thread
            (fetch time IS host-blocked time here)."""
            meta = pending.popleft()
            parity, _, nbytes = drain_fetch_core(meta)
            st["parity_bytes_drained"] += nbytes
            sc = drain_write_core(meta, parity)
            st["sidecar_s"] += sc
            free.append(meta[2])

        def drain_fetch_async(meta):
            parity, fetch_s, nbytes = drain_fetch_core(meta)
            with ds_lock:
                ds["drain_s"] += fetch_s
                ds["parity_bytes"] += nbytes
            if not (isinstance(meta[0], tuple) and meta[0][0] == "proc"):
                # device/host handles: the fetched parity is an
                # independent host array and fetch completion proves the
                # kernel consumed the input slot — recycle NOW so the
                # producer refills while this parity queues for writing
                slot_q.put(meta[2])
            return parity

        def drain_write_async(meta, parity):
            sc = drain_write_core(meta, parity)
            with ds_lock:
                ds["sidecar_s"] += sc
            if isinstance(meta[0], tuple) and meta[0][0] == "proc":
                # proc parity is a VIEW into the shm out slot (same
                # index as the input slot): recycle only once written
                slot_q.put(meta[2])

        def acquire_slot() -> int:
            if drainer is None:
                return free.popleft()
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

        if self._drain_async_enabled():
            # multi-buffered async drain: worker-backed encodes fetch on
            # ONE thread (FIFO ack protocol); device encodes may keep
            # several D2H copies in flight
            pool = 1 if (self.engine == "host"
                         or self._proc_worker is not None) \
                else self._drain_pool
            drainer = AsyncDrainer(drain_fetch_async, drain_write_async,
                                   pool_size=pool,
                                   queue_depth=len(bufs) + 2)
            for i in range(len(bufs)):
                slot_q.put(i)
            st["drain_pool"] = drainer.pool_size

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
                        elif self._proc_worker is not None:
                            try:
                                parity_dev = (
                                    "proc",
                                    self._proc_worker.submit(bi, used))
                            except (KeyboardInterrupt, SystemExit):
                                raise
                            except Exception as e:
                                # submit gave up: this and all later
                                # dispatches degrade to the CPU codec
                                self._abandon_proc_worker()
                                flags["degraded"] = True
                                reason = ("worker_gave_up"
                                          if isinstance(e, WorkerGaveUp)
                                          else "worker_error")
                                self._note_fallback(st, reason)
                                tr.event("pipeline.fallback",
                                         dispatch=d_idx, reason=reason)
                                parity_dev = self._cpu_parity(
                                    _shard_major(src, used))
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
                    # data shards pass through from the host input while
                    # the device computes parity; crc time rides the
                    # write stage (see the parity-side note), sidecar_s
                    # sub-counts it
                    with tr.span("pipeline.write", dispatch=d_idx,
                                 kind="data"):
                        st["sidecar_s"] += self._write_data(
                            outputs, sb, src, used)
                    meta = (parity_dev, used, bi, d_idx, len(fills), rows)
                    fills, used = [], 0
                    if drainer is not None:
                        # async: hand the dispatch to the drainer and
                        # move straight on to filling the next slot —
                        # the fetch + parity write overlap everything
                        # below.  Backpressure is normally the slot
                        # pool, but the bounded writer queue can also
                        # push back (fast fetch over a slow shard
                        # disk recycles device slots before the write)
                        drainer.submit(meta)
                    else:
                        pending.append(meta)
                        if len(pending) > self.depth:
                            drain_one()
                        if not free:
                            drain_one()
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
                if drainer is not None:
                    # tail stall: the last in-flight dispatches finish
                    # fetching + writing
                    with tr.span("pipeline.drain_wait", final=True):
                        drainer.finish()
                else:
                    while pending:
                        drain_one()
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
            if drainer is not None:
                if not ok:
                    if drainer.inflight and self._proc_worker is not None:
                        # flag the abort FIRST (the fetch thread skips
                        # recovery/fallback), then abandon so a fetch
                        # blocked on the worker fails fast (WorkerGaveUp)
                        # instead of the teardown waiting out a respawn
                        drainer.aborting = True
                        self._abandon_proc_worker()
                    drainer.abort()
                # fold the concurrent drain/writer accounting into the
                # call stats now that the threads have joined
                st["drain_s"] += ds["drain_s"]
                st["sidecar_s"] += ds["sidecar_s"]
                st["parity_bytes_drained"] += ds["parity_bytes"]
            if pending and self._proc_worker is not None:
                # abnormal exit with submitted-but-undrained jobs: their
                # acks would desync the retry attempt's (or a later
                # encode's) seq stream — abandon the worker; the retry
                # respawns fresh (mmap path does the same)
                self._abandon_proc_worker()
            pending.clear()
            self._close_map(dat_map)
            if ok and slots is not None:
                self._keep_slots(slots)
            with tr.span("pipeline.close"):
                for f in outputs:
                    f.close()
            st["wall_s"] = clock() - t_start
            st["worker_restarts"] = int(_restart_total() -
                                        self._restart_base)
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

        Output discipline: data shards append on the producer thread in
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
                       devices=nd, resume_entry=start_entry)
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
                    with tr.span("pipeline.write", dispatch=d_idx,
                                 kind="data"):
                        st["sidecar_s"] += self._write_data(
                            outputs, sb, buf, used)
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
                # tail stall: every lane's in-flight dispatches finish
                # fetching + writing
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
            st["worker_restarts"] = int(_restart_total() -
                                        self._restart_base)
            root.__exit__(*exc)

    def _rebuild_files_mmap(self, base: str, missing: list[int],
                            survivors: list[int], rec: np.ndarray,
                            matmul_ptrs,
                            sidecar: Optional[EciSidecar] = None) -> None:
        """Zero-copy rebuild: survivors are mmap'd whole files read in
        place by the matmul, and the rebuilt shards are mmap'd OUTPUTS —
        the kernel's stores are the write (fallocate'd first so ENOSPC
        is a catchable error, bulk pre-faulted where the kernel can)."""
        import mmap as mmap_mod

        k, b = self.k, self.dispatch_b
        st = self._reset_stats()
        clock = time.perf_counter
        t_start = clock()
        tr = self._tracer()
        root = tr.span("pipeline.rebuild_files", path=base, mode="mmap",
                       missing=len(missing), engine=self.engine)
        root.__enter__()
        rec = np.ascontiguousarray(rec)
        nm = len(missing)
        in_fs = []
        try:
            for i in survivors:
                in_fs.append(open(base + to_ext(i), "rb"))
        except BaseException:
            for f in in_fs:
                f.close()
            root.__exit__(*sys.exc_info())
            raise
        in_maps: list = []
        out_fs: list = []
        out_maps: list = []
        ok = False
        try:
            shard_size = os.fstat(in_fs[0].fileno()).st_size
            for f in in_fs:
                if os.fstat(f.fileno()).st_size != shard_size:
                    raise ValueError("ec shard size mismatch")
            if sidecar is not None and sidecar.shard_size != shard_size:
                sidecar = None  # stale sidecar: unverifiable, not rot
            out_fs = [open(base + to_ext(m), "w+b") for m in missing]
            if shard_size == 0:
                ok = True
                return
            # rebuilt shards are mmap'd outputs: the kernel's stores ARE
            # the write — same single-pass discipline as the encode path
            out_addrs: list[int] = []
            for f in out_fs:
                _fallocate(f.fileno(), shard_size)
                om = mmap_mod.mmap(f.fileno(), shard_size,
                                   access=mmap_mod.ACCESS_WRITE)
                try:
                    om.madvise(getattr(mmap_mod, "MADV_POPULATE_WRITE", 23))
                except (OSError, ValueError):
                    pass
                out_maps.append(om)
                out_addrs.append(
                    np.frombuffer(om, dtype=np.uint8).ctypes.data)
            in_maps = [mmap_mod.mmap(f.fileno(), 0,
                                     access=mmap_mod.ACCESS_READ)
                       for f in in_fs]
            for m in in_maps:
                if hasattr(m, "madvise"):
                    m.madvise(mmap_mod.MADV_SEQUENTIAL)
            in_arrs = [np.frombuffer(m, dtype=np.uint8) for m in in_maps]
            in_addr = [a.ctypes.data for a in in_arrs]
            try:
                for offset in range(0, shard_size, b):
                    n = min(b, shard_size - offset)
                    if sidecar is not None:
                        # verify every survivor block BEFORE its bytes
                        # feed the reconstruction matmul: a mismatch
                        # aborts this attempt and the caller retries
                        # with the corrupt shard demoted to an erasure.
                        # `raw` views the input mapping — it must be
                        # dropped before raising, or the exception
                        # frame pins the buffer and in_map.close()
                        # dies with BufferError in the cleanup path
                        t0 = clock()
                        corrupt = None
                        for row_i, s in enumerate(survivors):
                            raw = in_arrs[row_i][offset:offset + n]
                            if faultinject._points:
                                raw = faultinject.corrupt_block(
                                    "ec.shard.corrupt", s, raw, offset)
                            bad = sidecar.verify_range(s, offset, raw)
                            del raw
                            if bad is not None:
                                corrupt = (s, bad)
                                break
                        st["verify_s"] += clock() - t0
                        if corrupt is not None:
                            raise CorruptSurvivor(*corrupt)
                    with tr.span("pipeline.compute",
                                 dispatch=st["dispatches"],
                                 bytes=len(survivors) * n):
                        matmul_ptrs(rec,
                                    [a + offset for a in in_addr],
                                    [a + offset for a in out_addrs], n)
                    st["dispatches"] += 1
                    st["bytes_in"] += len(survivors) * n
            finally:
                del in_arrs
            ok = True
        finally:
            for m in out_maps:
                try:
                    m.close()
                except BufferError:
                    pass
            for m in in_maps:
                m.close()
            for f in in_fs + out_fs:
                f.close()
            if not ok:
                for m in missing:
                    try:
                        os.remove(base + to_ext(m))
                    except OSError:
                        pass
            st["wall_s"] = clock() - t_start
            root.__exit__(*(sys.exc_info() if not ok
                            else (None, None, None)))

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
        while True:
            try:
                return self._rebuild_files_once(base_file_name, sidecar,
                                                demoted)
            except CorruptSurvivor as e:
                demoted.add(e.shard_id)
                note_corruption("rebuild", e.shard_id, base_file_name,
                                block=e.block, tracer=self._tracer())

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
        matmul_ptrs = self._native_ptrs()
        if matmul_ptrs is not None:
            self._rebuild_files_mmap(base_file_name, missing, survivors,
                                     rec, matmul_ptrs, sidecar)
            return missing
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
        bufs = [np.zeros((k, b), dtype=np.uint8)
                for _ in range(self.depth + 1)]
        free: deque[int] = deque(range(len(bufs)))
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
            out_dev, n, bi, d_idx = pending.popleft()
            with tr.span("pipeline.drain", dispatch=d_idx):
                out = self._fetch(out_dev)
            with tr.span("pipeline.write", dispatch=d_idx, kind="rebuilt"):
                for row_i, m in enumerate(missing):
                    outputs[m].write(out[row_i, :n])
            free.append(bi)

        ok = False
        try:
            for offset in range(0, shard_size, b):
                n = min(b, shard_size - offset)
                if not free:
                    drain_one()
                bi = free.popleft()
                buf = bufs[bi]
                d_idx = st["dispatches"]
                with tr.span("pipeline.fill", dispatch=d_idx,
                             bytes=len(survivors) * n):
                    for row_i, s in enumerate(survivors):
                        preadv_into(inputs[s], [buf[row_i, :n]], offset)
                    if n < b:
                        buf[:, n:] = 0
                if sidecar is not None:
                    # verify before dispatch: corrupt bytes must never
                    # reach the reconstruction matmul (the raised
                    # CorruptSurvivor aborts the attempt; the caller
                    # demotes and retries with an alternate survivor)
                    t0 = clock()
                    for row_i, s in enumerate(survivors):
                        raw = buf[row_i, :n]
                        if faultinject._points:
                            raw = faultinject.corrupt_block(
                                "ec.shard.corrupt", s, raw, offset)
                        bad = sidecar.verify_range(s, offset, raw)
                        if bad is not None:
                            raise CorruptSurvivor(s, bad)
                    st["verify_s"] += clock() - t0
                with tr.span("pipeline.dispatch", dispatch=d_idx,
                             bytes=len(survivors) * n):
                    pending.append((self._dispatch(planes, buf), n, bi,
                                    d_idx))
                st["dispatches"] += 1
                st["bytes_in"] += len(survivors) * n
                if len(pending) > self.depth:
                    drain_one()
            while pending:
                drain_one()
            ok = True
        finally:
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
