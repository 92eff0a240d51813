"""Volume-server store: disk locations, volumes, EC volumes, heartbeats.

Equivalent of weed/storage/store.go + disk_location.go + store_ec.go.  One
Store owns N data directories, loads existing volumes/EC shards on startup,
routes needle operations by volume id, and builds master heartbeats.
Serialization: one RLock per volume for the write path (the reference's
dataFileAccessLock); reads are lock-free preads.
"""

from __future__ import annotations

import glob
import os
import re
import threading
from typing import Callable, Optional

import numpy as np

from ..ec.codec import ReedSolomon, best_cpu_engine
from ..ec.ec_volume import EcVolume, NeedleNotFoundError
from ..ec.integrity import ShardCorruptError
from ..ec.layout import to_ext
from ..ec import encoder as ec_encoder
from ..observability import get_tracer
from ..observability.tracer import NOOP_SPAN
from ..storage.needle import Needle
from ..storage.super_block import ReplicaPlacement
from ..storage.ttl import TTL
from ..storage.types import Version
from ..storage.volume import (
    CookieMismatchError,
    DeletedError,
    NotFoundError,
    Volume,
    volume_file_prefix,
)
from ..utils import ioutil  # noqa: F401  (re-exported for tooling)


def parse_volume_file_name(name: str) -> tuple[str, int]:
    """'collection_vid' or 'vid' -> (collection, vid)."""
    base = name
    if "_" in base:
        collection, vid_str = base.rsplit("_", 1)
    else:
        collection, vid_str = "", base
    return collection, int(vid_str)


class DiskLocation:
    """One data directory (disk_location.go)."""

    def __init__(self, directory: str):
        self.directory = directory
        os.makedirs(directory, exist_ok=True)

    def discover_volumes(self) -> list[tuple[str, int]]:
        found = []
        # a tiered volume has no local .dat — only .vif + .idx — so both
        # extensions mark a volume (disk_location.go loads .vif'd volumes)
        for ext in ("*.dat", "*.vif"):
            for path in glob.glob(os.path.join(self.directory, ext)):
                name = os.path.basename(path)[:-4]
                if re.fullmatch(r"(?:[\w.-]+_)?\d+", name):
                    parsed = parse_volume_file_name(name)
                    if parsed not in found:
                        found.append(parsed)
        return found

    def discover_ec_volumes(self) -> list[tuple[str, int]]:
        found = set()
        for path in glob.glob(os.path.join(self.directory, "*.ecx")):
            name = os.path.basename(path)[:-4]
            if re.fullmatch(r"(?:[\w.-]+_)?\d+", name):
                found.add(parse_volume_file_name(name))
        return sorted(found)


class _SwapLock:
    """Many concurrent needle ops (shared) OR one plane swap (exclusive).
    Quiesce-window fsync writes and miss-path reads take the shared side,
    so they serialize against detach/reattach swaps WITHOUT serializing
    against each other (group-commit batching survives) or against a
    compaction that holds the per-volume lock for seconds."""

    def __init__(self):
        self._cond = threading.Condition()
        self._shared = 0
        self._exclusive = False
        self._writers_waiting = 0

    def acquire_shared(self):
        with self._cond:
            # writer preference: new readers also wait while a swap is
            # QUEUED, or sustained write traffic would starve
            # reattach/detach forever (which hold volume_locks -> every
            # maintenance path would hang behind them)
            while self._exclusive or self._writers_waiting:
                self._cond.wait()
            self._shared += 1

    def release_shared(self):
        with self._cond:
            self._shared -= 1
            if self._shared == 0:
                self._cond.notify_all()

    def acquire_exclusive(self):
        with self._cond:
            self._writers_waiting += 1
            try:
                while self._exclusive or self._shared:
                    self._cond.wait()
                self._exclusive = True
            finally:
                self._writers_waiting -= 1

    def release_exclusive(self):
        with self._cond:
            self._exclusive = False
            self._cond.notify_all()

    def shared(self):
        import contextlib

        @contextlib.contextmanager
        def _ctx():
            self.acquire_shared()
            try:
                yield
            finally:
                self.release_shared()

        return _ctx()

    def exclusive(self):
        import contextlib

        @contextlib.contextmanager
        def _ctx():
            self.acquire_exclusive()
            try:
                yield
            finally:
                self.release_exclusive()

        return _ctx()


class Store:
    def __init__(self, directories: list[str], ip: str = "127.0.0.1",
                 port: int = 8080, public_url: str = "",
                 max_volume_count: int = 8,
                 ec_engine: str = "cpu", ec_mesh_devices: str = "",
                 use_mmap: bool = False,
                 needle_cache_mb: int = 64):
        from .needle_cache import NeedleCache

        self.ip, self.port = ip, port
        # popularity-aware needle read cache (needle_cache.py): hot
        # Zipf-head reads skip the pread+CRC pass; write/delete/vacuum
        # invalidate below.  0 disables (-dataplane.cacheMB)
        self.needle_cache = NeedleCache(
            max_bytes=max(0, int(needle_cache_mb)) << 20)
        self.public_url = public_url or f"{ip}:{port}"
        self.locations = [DiskLocation(d) for d in directories]
        self.max_volume_count = max_volume_count
        self.volumes: dict[int, Volume] = {}
        self.volume_locks: dict[int, threading.RLock] = {}
        self.ec_volumes: dict[int, EcVolume] = {}
        self.ec_collections: dict[int, str] = {}
        self.volume_size_limit = 30 * 1000 * 1000 * 1000
        self.ec_engine_name = ec_engine
        # -ec.mesh.devices: the device slice of -ec.engine=mesh
        # (parallel.mesh.parse_device_spec vocabulary); "" = all devices.
        self.ec_mesh_devices = ec_mesh_devices
        # stats of the last streaming encode/rebuild (admin responses)
        self.last_ec_stats: dict = {}
        if ec_engine in ("tpu", "mesh"):
            # the device engines fail at server start, not at first
            # encode: no TPU (and no explicit CPU pin) is NoTpuError, a
            # bad -ec.mesh.devices spec is ValueError
            from ..ops.device import device_backend

            device_backend()
            if ec_engine == "mesh":
                from ..parallel.mesh import parse_device_spec

                parse_device_spec(ec_mesh_devices)
        # mmap-backed .dat files (-memoryMapSizeMB analog, backend/memory_map)
        self.use_mmap = use_mmap
        # native C++ data plane (native/dataplane.cpp): when attached, it
        # is the single writer/reader for registered volumes' needles.
        # _native_holds counts outstanding native_detach()s per volume so
        # overlapping maintenance (vacuum + readonly flip + tier) cannot
        # re-register the plane while any of them still owns the files
        self.native_plane = None
        # False when the server has an IP whitelist configured: the plane
        # has no whitelist slot, so its TCP port must not accept W/D at
        # all (HTTP writes, which the whitelist does guard, still funnel
        # through the plane via the local C API)
        self.native_tcp_writes_ok = True
        self._native_holds: dict[int, int] = {}
        self._native_hold_lock = threading.Lock()
        self._swap_locks: dict[int, _SwapLock] = {}
        self._rs_cache: dict[str, ReedSolomon] = {}
        # delta-heartbeat bookkeeping (volume_grpc_client_to_master.go:48
        # streams incremental new/deleted volume + EC-shard lists between
        # periodic full syncs)
        self._delta_lock = threading.Lock()
        self._new_vids: set[int] = set()
        self._gone_vids: set[int] = set()
        self._new_ec_vids: set[int] = set()
        self._gone_ec_vids: set[int] = set()
        self.load_existing()

    # --- engine selection (-ec.engine={cpu,tpu,mesh}) ---------------------
    def rs(self, engine: Optional[str] = None) -> ReedSolomon:
        name = engine or self.ec_engine_name
        rs = self._rs_cache.get(name)
        if rs is None:
            if name == "tpu":
                from ..ops.gf_matmul import TpuEngine

                rs = ReedSolomon(10, 4, engine=TpuEngine())
            elif name == "mesh":
                from ..ec.codec import MeshEngine

                rs = ReedSolomon(
                    10, 4, engine=MeshEngine(devices=self.ec_mesh_devices))
            else:
                rs = ReedSolomon(10, 4, engine=best_cpu_engine())
            self._rs_cache[name] = rs
        return rs

    # --- loading ----------------------------------------------------------
    def load_existing(self) -> None:
        for loc in self.locations:
            for collection, vid in loc.discover_volumes():
                if vid not in self.volumes:
                    self._open_volume(loc.directory, collection, vid)
            for collection, vid in loc.discover_ec_volumes():
                if vid not in self.ec_volumes:
                    self._open_ec_volume(loc.directory, collection, vid)

    def _open_volume(self, directory: str, collection: str, vid: int) -> Volume:
        v = Volume(directory, collection, vid,
                   volume_size_limit=self.volume_size_limit,
                   use_mmap=self.use_mmap)
        self.volumes[vid] = v
        self.volume_locks[vid] = threading.RLock()
        self.note_volume_change(vid)
        return v

    def _open_ec_volume(self, directory: str, collection: str, vid: int) -> EcVolume:
        base = volume_file_prefix(directory, collection, vid)
        ev = EcVolume(base, vid)
        if self.ec_engine_name in ("tpu", "mesh"):
            # before any GET can reach the volume: a degraded read then
            # runs a program compiled here, never one compiled on a
            # user's request.  reconstruct_interval reads data_shards
            # survivors and ReedSolomon.reconstruct makes every other
            # shard, so its matrix has total - data rows
            compile_classes = getattr(self.rs().engine, "compile_classes",
                                      None)
            if compile_classes is not None:
                compile_classes(ev.total_shards - ev.data_shards,
                                ev.data_shards)
        self.ec_volumes[vid] = ev
        self.ec_collections[vid] = collection
        self.note_ec_change(vid)
        return ev

    # --- delta heartbeat ---------------------------------------------------
    def note_volume_change(self, vid: int, gone: bool = False) -> None:
        with self._delta_lock:
            if gone:
                self._new_vids.discard(vid)
                self._gone_vids.add(vid)
            else:
                self._gone_vids.discard(vid)
                self._new_vids.add(vid)

    def note_ec_change(self, vid: int, gone: bool = False) -> None:
        with self._delta_lock:
            if gone:
                self._new_ec_vids.discard(vid)
                self._gone_ec_vids.add(vid)
            else:
                self._gone_ec_vids.discard(vid)
                self._new_ec_vids.add(vid)

    def pop_heartbeat_delta(self) -> Optional[dict]:
        """Pending changes since the last pop, as an incremental heartbeat
        body; None when nothing changed.  On send failure the caller must
        requeue_heartbeat_delta so no change is ever lost."""
        from ..master.topology import ShardBits

        with self._delta_lock:
            if not (self._new_vids or self._gone_vids
                    or self._new_ec_vids or self._gone_ec_vids):
                return None
            new_vids, self._new_vids = self._new_vids, set()
            gone_vids, self._gone_vids = self._gone_vids, set()
            new_ec, self._new_ec_vids = self._new_ec_vids, set()
            gone_ec, self._gone_ec_vids = self._gone_ec_vids, set()
        new_volumes = []
        for vid in sorted(new_vids):
            v = self.volumes.get(vid)
            if v is None:  # raced with a delete after the note
                gone_vids.add(vid)
            else:
                try:
                    new_volumes.append(self._volume_info(v))
                except Exception:
                    # mid-compaction-commit swap window (closed .dat):
                    # re-queue for the next pulse instead of crashing
                    # the heartbeat thread
                    self.note_volume_change(vid)
        new_ec_shards = []
        for vid in sorted(new_ec):
            ev = self.ec_volumes.get(vid)
            if ev is None:
                gone_ec.add(vid)
            else:
                bits = ShardBits()
                for sid in ev.shards:
                    bits = bits.add(sid)
                new_ec_shards.append({
                    "volume_id": vid,
                    "collection": self.ec_collections.get(vid, ""),
                    "ec_index_bits": bits.bits})
        return {"new_volumes": new_volumes,
                "deleted_volumes": sorted(gone_vids),
                "new_ec_shards": new_ec_shards,
                "deleted_ec_shards": sorted(gone_ec)}

    def requeue_heartbeat_delta(self, delta: dict) -> None:
        with self._delta_lock:
            for v in delta.get("new_volumes", []):
                self._new_vids.add(int(v["id"]))
            self._gone_vids.update(delta.get("deleted_volumes", []))
            for e in delta.get("new_ec_shards", []):
                self._new_ec_vids.add(int(e["volume_id"]))
            self._gone_ec_vids.update(delta.get("deleted_ec_shards", []))

    # --- volume admin -----------------------------------------------------
    def add_volume(self, vid: int, collection: str = "",
                   replication: str = "000", ttl: str = "",
                   offset_5: bool = False) -> Volume:
        if vid in self.volumes:
            return self.volumes[vid]
        loc = min(self.locations,
                  key=lambda l: sum(1 for v in self.volumes.values()
                                    if v.directory == l.directory))
        v = Volume(loc.directory, collection, vid,
                   replica_placement=ReplicaPlacement.parse(replication),
                   ttl=TTL.parse(ttl),
                   volume_size_limit=self.volume_size_limit,
                   use_mmap=self.use_mmap, offset_5=offset_5)
        self.volumes[vid] = v
        self.volume_locks[vid] = threading.RLock()
        self._native_add(vid, v)
        return v

    def delete_volume(self, vid: int) -> None:
        if self.native_plane is not None:
            self.native_plane.remove_volume(vid)
            with self._native_hold_lock:
                self._native_holds.pop(vid, None)
        v = self.volumes.pop(vid, None)
        self.volume_locks.pop(vid, None)
        self.needle_cache.invalidate_volume(vid, "unmount")
        if v is not None:
            v.destroy()
            self.note_volume_change(vid, gone=True)

    def unmount_volume(self, vid: int) -> None:
        if self.native_plane is not None:
            self.native_plane.remove_volume(vid)
            with self._native_hold_lock:
                self._native_holds.pop(vid, None)
        v = self.volumes.pop(vid, None)
        self.volume_locks.pop(vid, None)
        self.needle_cache.invalidate_volume(vid, "unmount")
        if v is not None:
            v.close()
            self.note_volume_change(vid, gone=True)

    def mount_volume(self, vid: int) -> None:
        for loc in self.locations:
            for collection, found_vid in loc.discover_volumes():
                if found_vid == vid:
                    self._open_volume(loc.directory, collection, vid)
                    self.native_register(vid)
                    return
        raise KeyError(f"volume {vid} not found on disk")

    def get_volume(self, vid: int) -> Volume:
        v = self.volumes.get(vid)
        if v is None:
            raise KeyError(f"volume {vid} not found")
        return v

    def _swap_lock(self, vid: int) -> _SwapLock:
        with self._native_hold_lock:
            sl = self._swap_locks.get(vid)
            if sl is None:
                sl = self._swap_locks[vid] = _SwapLock()
            return sl

    # --- native data plane (native/dataplane.cpp) -------------------------
    def attach_native_plane(self, plane) -> None:
        """Register every eligible volume; from here every needle op on
        those volumes funnels through the C++ engine (single writer)."""
        self.native_plane = plane
        for vid, v in self.volumes.items():
            self._native_add(vid, v)

    def _native_add(self, vid: int, v: Volume) -> None:
        # the C++ plane speaks 16-byte (4-byte-offset) idx entries only:
        # 5-byte-offset volumes stay on the Python engine
        if self.native_plane is None or v.tiered \
                or v.version != Version.V3 \
                or getattr(v, "offset_size", 4) != 4:
            return
        # direct TCP writes bypass the HTTP layer's replication fan-out,
        # so only replication-000 volumes take them (the reference's
        # -useTcp experiment is likewise local-only,
        # ref: weed/server/volume_server_tcp_handlers_write.go)
        tcp_ok = (self.native_tcp_writes_ok
                  and v.super_block.replica_placement.to_byte() == 0)
        self.native_plane.add_volume(vid, v.dat_path, v.idx_path,
                                     read_only=v.read_only,
                                     tcp_writable=tcp_ok)

    def native_detach(self, vid: int) -> None:
        """Quiesce: unregister from the plane and REOPEN the Python volume
        so its needle map replays everything the plane appended.  Needle
        ops fall back to the Python engine until native_reattach.  Holds
        nest: each detach must be paired with a reattach, and the plane
        only re-registers when the LAST hold releases.

        Both the plane removal and the volume swap happen under the
        volume lock so they can never interleave with a reattach's
        re-registration or a Python-engine fallback write."""
        plane = self.native_plane
        if plane is None:
            return
        with self._native_hold_lock:
            self._native_holds[vid] = self._native_holds.get(vid, 0) + 1
        lock = self.volume_locks.get(vid)
        if lock is None:
            # volume raced a delete/unmount: the hold must not leak, or a
            # reused vid could never register on the plane again
            with self._native_hold_lock:
                n = self._native_holds.get(vid, 0)
                if n <= 1:
                    self._native_holds.pop(vid, None)
                else:
                    self._native_holds[vid] = n - 1
            return
        with lock, self._swap_lock(vid).exclusive():
            if not plane.has(vid):
                return
            plane.remove_volume(vid)
            v = self.volumes.get(vid)
            if v is None:
                return
            directory, collection, ro = v.directory, v.collection, v.read_only
            v.close()
            v2 = Volume(directory, collection, vid,
                        volume_size_limit=self.volume_size_limit,
                        use_mmap=self.use_mmap)
            v2.read_only = ro
            self.volumes[vid] = v2

    def native_reattach(self, vid: int) -> None:
        """Release one hold; the LAST release re-registers the plane.
        Strictly paired with native_detach: an unpaired call (no hold
        outstanding) is a no-op, so it can never steal a concurrent
        maintenance op's hold and re-register over files it still owns."""
        plane = self.native_plane
        if plane is None:
            return
        lock = self.volume_locks.get(vid)
        if lock is None:
            with self._native_hold_lock:
                if self._native_holds.get(vid, 0):
                    n = self._native_holds[vid]
                    if n <= 1:
                        self._native_holds.pop(vid, None)
                    else:
                        self._native_holds[vid] = n - 1
            return
        with lock, self._swap_lock(vid).exclusive():
            with self._native_hold_lock:
                n = self._native_holds.get(vid, 0)
                if n == 0:
                    return  # unpaired: someone else's hold logic governs
                if n > 1:
                    self._native_holds[vid] = n - 1
                    return  # another maintenance op still owns the files
                # n == 1: last hold — keep it visible until re-added so
                # readers missing on a stale pre-swap object still settle
            v = self.volumes.get(vid)
            if v is not None and not plane.has(vid):
                self._native_add(vid, v)
            with self._native_hold_lock:
                n = self._native_holds.get(vid, 0)
                if n <= 1:
                    self._native_holds.pop(vid, None)
                else:  # a new detach arrived while re-registering
                    self._native_holds[vid] = n - 1

    def native_register(self, vid: int) -> None:
        """Register a volume that newly became plane-eligible (tier
        download, ec.decode restore, mount) — a no-op while any
        maintenance hold is outstanding or the plane already has it."""
        plane = self.native_plane
        if plane is None or plane.has(vid):
            return
        lock = self.volume_locks.get(vid)
        if lock is None:
            return
        with lock, self._swap_lock(vid).exclusive():
            with self._native_hold_lock:
                if self._native_holds.get(vid, 0):
                    return
            v = self.volumes.get(vid)
            if v is not None and not plane.has(vid):
                self._native_add(vid, v)

    def native_refresh(self, vid: int) -> None:
        """Re-register with current flags (read_only) — a no-op while any
        maintenance hold is outstanding; that hold's reattach will pick
        the flags up."""
        plane = self.native_plane
        if plane is None or not plane.has(vid):
            return
        with self._native_hold_lock:
            if self._native_holds.get(vid, 0):
                return
        self.native_detach(vid)
        self.native_reattach(vid)

    def native_quiesced(self, vid: int):
        """Context manager around maintenance that touches volume files."""
        import contextlib

        @contextlib.contextmanager
        def _ctx():
            self.native_detach(vid)
            try:
                yield
            finally:
                self.native_reattach(vid)

        return _ctx()

    # --- needle ops (store.go:338,362) ------------------------------------
    @staticmethod
    def _plane_gone(exc) -> bool:
        # True when the plane dropped the volume between has() and the
        # call (quiesce race): fall back to the Python engine
        from .dataplane import DP_NO_VOLUME, DataPlaneError

        return isinstance(exc, DataPlaneError) and exc.code == DP_NO_VOLUME

    def _native_append(self, plane, vid: int, n: Needle,
                       fsync: bool) -> tuple[int, bool]:
        """Single-writer funnel: Python serializes (rich needles keep
        name/mime/flags/cipher), C++ appends under its volume lock.
        Divergence from the Python path: no unchanged-write dedupe."""
        import time as _time

        v = self.get_volume(vid)
        if not n.append_at_ns:
            n.append_at_ns = _time.time_ns()
        blob = n.to_bytes(v.version)
        plane.append(vid, n.id, n.cookie, blob, n.size)
        if fsync:
            plane.sync(vid)
        self.note_volume_change(vid)
        return n.size, False

    def _plane_eligible(self, vid: int) -> bool:
        v = self.volumes.get(vid)
        return (v is not None and not v.tiered
                and v.version == Version.V3
                and getattr(v, "offset_size", 4) == 4)

    def write_needle(self, vid: int, n: Needle, fsync: bool = False) -> tuple[int, bool]:
        plane = self.native_plane
        if plane is not None and not plane.has(vid) \
                and not self._native_holds.get(vid) \
                and not self._plane_eligible(vid):
            # never on the plane (tiered / non-v3): plain engine semantics
            plane = None
        if plane is not None:
            if plane.has(vid):
                try:
                    return self._native_append(plane, vid, n, fsync)
                except OSError as e:
                    if not self._plane_gone(e):
                        raise
            # quiesce window: the SHARED side of the swap lock serializes
            # this fallback against detach/reattach swaps (dp_add must
            # never snapshot around an in-flight Python append) without
            # serializing fallback writers against EACH OTHER — fsync
            # writers keep group-commit batching — or against a
            # compaction holding the per-volume lock.  The has() RE-CHECK
            # routes back to the plane if re-registration won the race.
            with self._swap_lock(vid).shared():
                if plane.has(vid):
                    try:
                        return self._native_append(plane, vid, n, fsync)
                    except OSError as e:
                        if not self._plane_gone(e):
                            raise
                v = self.get_volume(vid)
                if fsync:
                    _, size, unchanged = v.write_needle2(n, fsync=True)
                else:
                    _, size, unchanged = v.write_needle(n)
            self.needle_cache.invalidate(vid, n.id, "write")
            self.note_volume_change(vid)
            return size, unchanged
        if fsync:
            # group-commit worker (volume_write.py): the store lock is NOT
            # held while waiting, so concurrent fsync writers batch into one
            # fsync (writeNeedle2, volume_write.go:110-128)
            _, size, unchanged = self.get_volume(vid).write_needle2(
                n, fsync=True)
        else:
            with self.volume_locks[vid]:
                # refetch under the lock: compaction commit swaps the
                # volume object under this same lock
                _, size, unchanged = self.get_volume(vid).write_needle(n)
        # overwrites must not serve yesterday's bytes from the read
        # cache (AFTER the disk write: the epoch also fences racing
        # read-side repopulation)
        self.needle_cache.invalidate(vid, n.id, "write")
        # stats changed: the next delta pulse refreshes this volume's
        # counters on the master (idle volumes cost nothing)
        self.note_volume_change(vid)
        return size, unchanged

    def delete_needle(self, vid: int, n: Needle, fsync: bool = False) -> int:
        plane = self.native_plane
        if plane is not None and not plane.has(vid) \
                and not self._native_holds.get(vid) \
                and not self._plane_eligible(vid):
            plane = None  # never on the plane: plain engine semantics
        if plane is not None:
            if plane.has(vid):
                try:
                    size = plane.delete(vid, n.id, n.cookie)
                    if fsync:
                        plane.sync(vid)
                    self.note_volume_change(vid)
                    return size
                except OSError as e:
                    if not self._plane_gone(e):
                        raise
            # same shared-lock + re-check discipline as write_needle
            with self._swap_lock(vid).shared():
                if plane.has(vid):
                    try:
                        size = plane.delete(vid, n.id, n.cookie)
                        if fsync:
                            plane.sync(vid)
                        self.note_volume_change(vid)
                        return size
                    except OSError as e:
                        if not self._plane_gone(e):
                            raise
                v = self.get_volume(vid)
                size = v.delete_needle2(n, fsync=True) if fsync \
                    else v.delete_needle(n)
            self.needle_cache.invalidate(vid, n.id, "delete")
            self.note_volume_change(vid)
            return size
        if fsync:
            size = self.get_volume(vid).delete_needle2(n, fsync=True)
        else:
            with self.volume_locks[vid]:
                size = self.get_volume(vid).delete_needle(n)
        self.needle_cache.invalidate(vid, n.id, "delete")
        self.note_volume_change(vid)
        return size

    def _cache_check(self, vid: int, key: int,
                     cookie: Optional[int]) -> Optional[Needle]:
        """Popularity-cache hit, with the same handler-level cookie
        check the disk path applies — a cached hit must be
        indistinguishable from a pread."""
        n = self.needle_cache.get(vid, key)
        if n is None:
            return None
        if cookie is not None and n.cookie != cookie:
            raise CookieMismatchError(f"cookie mismatch for {key}")
        return n

    def read_needle(self, vid: int, key: int, cookie: Optional[int] = None) -> Needle:
        plane = self.native_plane
        if plane is None:
            cache = self.needle_cache
            if cache.enabled:
                n = self._cache_check(vid, key, cookie)
                if n is not None:
                    return n
                ep = cache.epoch(vid)
                n = self.get_volume(vid).read_needle(key, cookie)
                cache.offer(vid, key, n, epoch=ep)
                return n
            return self.get_volume(vid).read_needle(key, cookie)
        # two rounds: a plane_gone in round 1 may mean "mid-reattach";
        # round 2 re-checks has() so a just-re-registered plane serves the
        # read (its map is fresher than the quiesce-era Python volume's)
        for _ in range(2):
            if not plane.has(vid):
                break
            try:
                v = self.get_volume(vid)
                blob, size = plane.read_record(vid, key, cookie)
                return Needle.from_bytes(blob, size, v.version)
            except OSError as e:
                if not self._plane_gone(e):
                    raise
        try:
            return self.get_volume(vid).read_needle(key, cookie)
        except (NotFoundError, DeletedError, CookieMismatchError,
                ValueError, OSError):
            # possibly a stale volume object mid-quiesce-swap (its map is
            # frozen at the last attach, and its closed .dat handle never
            # comes back): settle on the SHARED swap lock — serializing
            # with detach/reattach swaps but not with compaction or other
            # readers — and ask both engines again.  A miss with no hold
            # outstanding and no registration is a PLAIN miss (ineligible
            # or permanently detached volume): skip the settle entirely
            if not self._native_holds.get(vid) and not plane.has(vid):
                raise
            with self._swap_lock(vid).shared():
                if plane.has(vid):
                    try:
                        v = self.get_volume(vid)
                        blob, size = plane.read_record(vid, key, cookie)
                        return Needle.from_bytes(blob, size, v.version)
                    except OSError as e:
                        if not self._plane_gone(e):
                            raise
                return self.get_volume(vid).read_needle(key, cookie)

    # --- EC (store_ec.go + volume_grpc_erasure_coding.go backends) --------
    def ec_generate(self, vid: int, collection: str = "",
                    engine: Optional[str] = None) -> None:
        """VolumeEcShardsGenerate: .dat -> .ec00..13 + .ecx + mark readonly."""
        with get_tracer().span("store.ec_generate", vid=vid):
            # quiesce the native plane for the encode: writes fall back
            # to the (reopened, idx-replayed) Python engine; reads keep
            # working.  The finally-reattach re-registers read_only, so
            # the plane keeps serving reads of the frozen volume while
            # shards spread
            self.native_detach(vid)
            try:
                self._ec_generate_locked(vid, engine)
            finally:
                self.native_reattach(vid)

    def _ec_generate_locked(self, vid: int,
                            engine: Optional[str] = None) -> None:
        v = self.get_volume(vid)
        if getattr(v, "offset_size", 4) != 4:
            # the EC surface (.ecx entries, shard serving) is 16-byte /
            # 4-byte-offset only — parsing a 17-byte idx as 16-byte
            # would write a corrupt .ecx.  The reference has the same
            # global-width coupling (5BytesOffset is a whole-binary
            # build tag); a >32GB volume must be split before encoding.
            raise ValueError(
                f"volume {vid} uses 5-byte offsets; EC encoding "
                "supports 4-byte-offset volumes only")
        base = v.file_prefix
        with self.volume_locks[vid]:
            v.read_only = True
            name = engine or self.ec_engine_name
            if name in ("tpu", "mesh"):
                # overlapped device pipeline (ec/streaming.py), not the
                # serial read->matmul->write loop; on several devices
                # both engines spread whole dispatches across
                # per-device queues
                enc = self._streaming_encoder(name)
                enc.encode_file(base + ".dat", base)
                self.last_ec_stats = enc.stats
            else:
                ec_encoder.write_ec_files(base, self.rs(engine))
            with get_tracer().span("store.ecx_sort") as sp:
                sp.set(needles=ec_encoder.write_sorted_file_from_idx(base))

    def ec_rebuild(self, vid: int, collection: str = "",
                   engine: Optional[str] = None) -> list[int]:
        """VolumeEcShardsRebuild: regenerate missing local shards."""
        with get_tracer().span("store.ec_rebuild", vid=vid):
            base = self._ec_base(vid, collection)
            name = engine or self.ec_engine_name
            if name in ("tpu", "mesh"):
                enc = self._streaming_encoder(name)
                rebuilt = enc.rebuild_files(base)
                self.last_ec_stats = enc.stats
                return rebuilt
            return ec_encoder.rebuild_ec_files(base, self.rs(engine))

    def _streaming_encoder(self, engine: str = "tpu"):
        # explicit device engines only: never auto-detected.  tpu and
        # mesh share one encode plane, chosen by the slice's size
        # (ec/streaming.py); mesh adds only the -ec.mesh.devices slice
        stream = "mesh" if engine == "mesh" else "device"
        cache = getattr(self, "_stream_encs", None)
        if cache is None:
            cache = self._stream_encs = {}
        enc = cache.get(stream)
        if enc is None:
            from ..ec.streaming import StreamingEncoder

            enc = cache[stream] = StreamingEncoder(
                engine=stream,
                devices=self.ec_mesh_devices if stream == "mesh" else None)
            if stream == "device":
                # long-standing probe point (tests, driver smoke runs)
                self._stream_enc = enc
        return enc

    def _ec_base(self, vid: int, collection: str = "") -> str:
        ev = self.ec_volumes.get(vid)
        if ev is not None:
            return ev.base_file_name
        for loc in self.locations:
            base = volume_file_prefix(loc.directory, collection, vid)
            if (glob.glob(base + ".ec[0-9][0-9]") or os.path.exists(base + ".ecx")
                    or os.path.exists(base + ".dat")):
                return base
        return volume_file_prefix(self.locations[0].directory, collection, vid)

    def ec_mount(self, vid: int, collection: str = "") -> None:
        if vid in self.ec_volumes:
            self.ec_volumes[vid].close()
            del self.ec_volumes[vid]
        base = self._ec_base(vid, collection)
        directory = os.path.dirname(base)
        self._open_ec_volume(directory, collection, vid)

    def ec_unmount(self, vid: int) -> None:
        ev = self.ec_volumes.pop(vid, None)
        self.ec_collections.pop(vid, None)
        if ev is not None:
            ev.close()
            self.note_ec_change(vid, gone=True)

    def ec_delete_shards(self, vid: int, shard_ids: list[int],
                         collection: str = "") -> None:
        with get_tracer().span("store.ec_delete", vid=vid) as sp:
            base = self._ec_base(vid, collection)
            was_mounted = vid in self.ec_volumes
            if was_mounted:
                self.ec_unmount(vid)
            removed = unlinked = 0
            for sid in shard_ids:
                p = base + to_ext(sid)
                if os.path.exists(p):
                    unlinked += os.path.getsize(p)
                    os.remove(p)
                    removed += 1
            if not glob.glob(base + ".ec[0-9][0-9]"):
                # last shard gone: drop the index, journal, crc sidecar,
                # and any quarantined .bad evidence files with it
                for path in [base + ext for ext in (".ecx", ".ecj", ".eci")] \
                        + glob.glob(base + ".ec[0-9][0-9].bad"):
                    if os.path.exists(path):
                        unlinked += os.path.getsize(path)
                        os.remove(path)
            elif was_mounted:
                self.ec_mount(vid, collection)
            sp.set(shards=removed, bytes=unlinked)

    def ec_shard_read(self, vid: int, shard_id: int, offset: int,
                      length: int) -> bytes:
        ev = self.ec_volumes.get(vid)
        if ev is None or shard_id not in ev.shards:
            raise NeedleNotFoundError(f"shard {vid}.{shard_id} not here")
        return ev.shards[shard_id].read_at(length, offset)

    def read_ec_needle(self, vid: int, key: int,
                       fetch_remote: Optional[Callable[[int, int, int, int], bytes]] = None,
                       ) -> tuple[bytes, int]:
        """ReadEcShardNeedle (store_ec.go:125-163): local shards first, then
        remote shard reads, then on-the-fly reconstruction via fetch_remote
        (vid, shard_id, offset, length) -> bytes.  Returns (record, size).
        Its span counts the needle's intervals, those read from a local
        shard, and those recovered otherwise (remote or reconstructed)."""
        tr = get_tracer()
        with (tr.span("store.read_ec_needle", vid=vid)
              if tr.enabled else NOOP_SPAN) as sp:
            ev = self.ec_volumes.get(vid)
            if ev is None:
                raise KeyError(f"ec volume {vid} not found")
            offset, size, intervals = ev.locate_ec_shard_needle(key)
            from ..storage.types import size_is_deleted

            if size_is_deleted(size):
                raise NeedleNotFoundError(f"needle {key} deleted")
            out = []
            local = 0
            for iv in intervals:
                shard_id, shard_offset = iv.to_shard_id_and_offset(
                    ev.large_block_size, ev.small_block_size, ev.data_shards)
                piece = None
                if shard_id in ev.shards \
                        and shard_id not in ev.corrupt_shards:
                    try:
                        # sidecar-verified read (ec/integrity.py): a crc
                        # mismatch demotes the shard for the whole mount
                        # and self-heals below via remote fetch /
                        # reconstruction instead of serving rotted bytes
                        piece = ev._verified_read(shard_id, shard_offset,
                                                  iv.size).tobytes()
                        local += 1
                    except ShardCorruptError:
                        ev._note_corrupt(shard_id)
                        piece = None
                    except OSError:
                        # bad sector/dying disk: treat the shard as
                        # absent and self-heal through the degraded-read
                        # paths below
                        piece = None
                if piece is None and fetch_remote is not None:
                    try:
                        piece = fetch_remote(vid, shard_id, shard_offset,
                                             iv.size)
                    except Exception:
                        piece = None
                if piece is None:
                    piece = ev.reconstruct_interval(shard_id, shard_offset,
                                                    iv.size, self.rs())
                out.append(piece)
            sp.set(intervals=len(intervals), local_reads=local,
                   reconstructed=len(intervals) - local)
            return b"".join(out), size

    def ec_delete_needle(self, vid: int, key: int) -> None:
        ev = self.ec_volumes.get(vid)
        if ev is None:
            raise KeyError(f"ec volume {vid} not found")
        ev.delete_needle(key)

    def ec_to_volume(self, vid: int, collection: str = "") -> None:
        """VolumeEcShardsToVolume: decode .ec00-09 + .ecx/.ecj back into a
        normal volume (volume_grpc_erasure_coding.go:382-413)."""
        base = self._ec_base(vid, collection)
        dat_size = ec_encoder.find_dat_file_size(base, base)
        ec_encoder.write_dat_file(base, dat_size)
        ec_encoder.write_idx_file_from_ec_index(base)
        self.ec_unmount(vid)
        directory = os.path.dirname(base)
        self._open_volume(directory, collection, vid)
        self.native_register(vid)

    # --- heartbeat (store.go:216 CollectHeartbeat) ------------------------
    def _volume_info(self, v: Volume) -> dict:
        """to_volume_information with native-plane stats overlaid: while
        the plane owns the volume, the Python map is stale — size,
        file_count, and max_file_key (the master reseeds its sequencer
        from it) must come from the plane."""
        info = v.to_volume_information()
        plane = self.native_plane
        if plane is not None and plane.has(v.id):
            st = plane.stat(v.id)
            if st is not None:
                dat_size, file_count, max_key, deleted_bytes = st
                info["size"] = dat_size
                info["file_count"] = max(info["file_count"], file_count)
                info["max_file_key"] = max(info["max_file_key"], max_key)
                info["deleted_byte_count"] = max(
                    info["deleted_byte_count"], deleted_bytes)
        return info

    def collect_heartbeat(self) -> dict:
        from ..master.topology import ShardBits

        volumes = []
        for v in list(self.volumes.values()):
            try:
                volumes.append(self._volume_info(v))
            except Exception:
                pass  # mid-swap (compaction/tier commit): next pulse
        ec_shards = []
        for vid, ev in self.ec_volumes.items():
            bits = ShardBits()
            for sid in ev.shards:
                bits = bits.add(sid)
            ec_shards.append({"volume_id": vid,
                              "collection": self.ec_collections.get(vid, ""),
                              "ec_index_bits": bits.bits})
        return {
            "ip": self.ip, "port": self.port, "public_url": self.public_url,
            "max_volume_count": self.max_volume_count,
            "volumes": volumes, "ec_shards": ec_shards,
        }

    def close(self) -> None:
        for v in self.volumes.values():
            v.close()
        for ev in self.ec_volumes.values():
            ev.close()
