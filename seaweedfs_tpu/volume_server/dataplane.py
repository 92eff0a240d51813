"""ctypes wrapper for the native C++ data plane (native/dataplane.cpp).

The volume server's framed-TCP needle IO served GIL-free by C++ threads,
with the Python Store routing its own needle ops through the same engine
so there is exactly ONE writer per volume.  Quiesce protocol: maintenance
(vacuum, EC encode, copy, tier) calls Store.native_quiesced(vid), which
detaches the volume from the plane, reopens the Python Volume (full idx
replay, so its needle map sees everything the plane appended), runs the
operation, and re-attaches.
"""

from __future__ import annotations

import ctypes
import threading
from typing import Optional

from ..native import load_dataplane_lib
from ..observability import get_tracer
from ..observability.tracer import NOOP_SPAN


# error codes (dataplane.cpp enum)
DP_OK = 0
DP_NOT_FOUND = -2
DP_COOKIE = -3
DP_DELETED = -4
DP_READONLY = -5
DP_NO_VOLUME = -6
DP_IO = -7
DP_CRC = -8
DP_TCP_FORBIDDEN = -11

_lib = None
_lib_lock = threading.Lock()


def load_dataplane():
    """Build (if stale) + load the library; None when unavailable."""
    global _lib
    lib = load_dataplane_lib()  # builds under its own lock
    if lib is None:
        return None
    with _lib_lock:
        if _lib is not None:
            return _lib
        u8p = ctypes.POINTER(ctypes.c_ubyte)
        lib.dp_start.restype = ctypes.c_void_p
        lib.dp_start.argtypes = [ctypes.c_char_p, ctypes.c_int]
        lib.dp_port.argtypes = [ctypes.c_void_p]
        lib.dp_add_volume.argtypes = [
            ctypes.c_void_p, ctypes.c_uint, ctypes.c_char_p,
            ctypes.c_char_p, ctypes.c_int, ctypes.c_int]
        lib.dp_remove_volume.argtypes = [ctypes.c_void_p, ctypes.c_uint]
        lib.dp_write.argtypes = [
            ctypes.c_void_p, ctypes.c_uint, ctypes.c_ulonglong,
            ctypes.c_uint, u8p, ctypes.c_uint,
            ctypes.POINTER(ctypes.c_uint)]
        lib.dp_append.argtypes = [
            ctypes.c_void_p, ctypes.c_uint, ctypes.c_ulonglong,
            ctypes.c_uint, u8p, ctypes.c_ulonglong, ctypes.c_int]
        lib.dp_delete.argtypes = [
            ctypes.c_void_p, ctypes.c_uint, ctypes.c_ulonglong,
            ctypes.c_uint, ctypes.POINTER(ctypes.c_uint)]
        lib.dp_read.argtypes = [
            ctypes.c_void_p, ctypes.c_uint, ctypes.c_ulonglong,
            ctypes.c_uint, ctypes.POINTER(u8p),
            ctypes.POINTER(ctypes.c_uint)]
        lib.dp_read_record.argtypes = [
            ctypes.c_void_p, ctypes.c_uint, ctypes.c_ulonglong,
            ctypes.c_uint, ctypes.c_int, ctypes.POINTER(u8p),
            ctypes.POINTER(ctypes.c_ulonglong),
            ctypes.POINTER(ctypes.c_int)]
        lib.dp_free.argtypes = [ctypes.c_void_p]
        lib.dp_stat.argtypes = [
            ctypes.c_void_p, ctypes.c_uint,
            ctypes.POINTER(ctypes.c_ulonglong),
            ctypes.POINTER(ctypes.c_ulonglong),
            ctypes.POINTER(ctypes.c_ulonglong),
            ctypes.POINTER(ctypes.c_ulonglong),
            ctypes.POINTER(ctypes.c_ulonglong)]
        lib.dp_sync.argtypes = [ctypes.c_void_p, ctypes.c_uint]
        lib.dp_stop.argtypes = [ctypes.c_void_p]
        _lib = lib
        return lib


class DataPlaneError(OSError):
    def __init__(self, code: int, message: str):
        super().__init__(message)
        self.code = code


def _raise(code: int, context: str = ""):
    from ..storage.volume import (CookieMismatchError, DeletedError,
                                  NotFoundError)

    if code == DP_NOT_FOUND:
        raise NotFoundError(context)
    if code == DP_COOKIE:
        raise CookieMismatchError(context)
    if code == DP_DELETED:
        raise DeletedError(context)
    if code == DP_READONLY:
        raise PermissionError(f"volume is read only {context}")
    raise DataPlaneError(code, f"data plane error {code} {context}")


class NativeDataPlane:
    """One running C++ server + its registered volumes."""

    def __init__(self, host: str = "127.0.0.1", port: int = 0):
        """port=-1 starts the engine with NO TCP listener (engine-only:
        whitelist-guarded servers keep native needle IO through the HTTP
        funnel while exposing no unguarded port; self.port reads 0)."""
        lib = load_dataplane()
        if lib is None:
            raise RuntimeError("native data plane unavailable (no toolchain)")
        self._lib = lib
        self._h = lib.dp_start(host.encode(), port)
        if not self._h:
            # OSError (not RuntimeError) so callers can retry transient
            # bind races without also retrying "no toolchain" above
            raise OSError(f"data plane could not bind {host}:{port}")
        self.port = lib.dp_port(self._h)
        self.vids: set[int] = set()
        self._lock = threading.Lock()

    def add_volume(self, vid: int, dat_path: str, idx_path: str,
                   read_only: bool = False,
                   tcp_writable: bool = True) -> None:
        """tcp_writable=False rejects W/D frames arriving over the plane's
        TCP port (reads still serve): set for replicated volumes — direct
        TCP writes would bypass fan-out — and whitelist-guarded servers,
        since the plane has no whitelist slot.  Local funnel calls
        (append/write/delete below) are unaffected."""
        rc = self._lib.dp_add_volume(
            self._h, vid, dat_path.encode(), idx_path.encode(),
            1 if read_only else 0, 1 if tcp_writable else 0)
        if rc != DP_OK:
            _raise(rc, f"add_volume {vid}")
        with self._lock:
            self.vids.add(vid)

    def remove_volume(self, vid: int) -> None:
        with self._lock:
            self.vids.discard(vid)
        self._lib.dp_remove_volume(self._h, vid)

    def has(self, vid: int) -> bool:
        return vid in self.vids

    def _handle(self):
        h = self._h
        if h is None:  # stopped: report "not mine" so callers fall back
            raise DataPlaneError(DP_NO_VOLUME, "data plane stopped")
        return h

    def append(self, vid: int, key: int, cookie: int, record: bytes,
               size: int) -> None:
        # per-needle hot path: attrs dicts only when the tracer is live
        tr = get_tracer()
        with (tr.span("dataplane.append", vid=vid, key=key,
                      bytes=len(record)) if tr.enabled else NOOP_SPAN):
            buf = (ctypes.c_ubyte * len(record)).from_buffer_copy(record)
            rc = self._lib.dp_append(self._handle(), vid, key, cookie, buf,
                                     len(record), size)
        if rc != DP_OK:
            _raise(rc, f"append {vid},{key:x}")

    def write(self, vid: int, key: int, cookie: int, data: bytes) -> int:
        out = ctypes.c_uint()
        tr = get_tracer()
        with (tr.span("dataplane.write", vid=vid, key=key,
                      bytes=len(data)) if tr.enabled else NOOP_SPAN):
            buf = (ctypes.c_ubyte * len(data)).from_buffer_copy(data)
            rc = self._lib.dp_write(self._handle(), vid, key, cookie, buf,
                                    len(data), ctypes.byref(out))
        if rc != DP_OK:
            _raise(rc, f"write {vid},{key:x}")
        return out.value

    def delete(self, vid: int, key: int, cookie: int) -> int:
        out = ctypes.c_uint()
        tr = get_tracer()
        with (tr.span("dataplane.delete", vid=vid, key=key)
              if tr.enabled else NOOP_SPAN):
            rc = self._lib.dp_delete(self._handle(), vid, key, cookie,
                                     ctypes.byref(out))
        if rc != DP_OK:
            _raise(rc, f"delete {vid},{key:x}")
        return out.value

    def read_record(self, vid: int, key: int,
                    cookie: Optional[int]) -> tuple[bytes, int]:
        """(raw record bytes, stored size) — parse with Needle.from_bytes.
        cookie=None skips the cookie check (read_needle semantics)."""
        u8p = ctypes.POINTER(ctypes.c_ubyte)
        out = u8p()
        out_len = ctypes.c_ulonglong()
        out_size = ctypes.c_int()
        tr = get_tracer()
        with (tr.span("dataplane.read", vid=vid, key=key)
              if tr.enabled else NOOP_SPAN):
            rc = self._lib.dp_read_record(self._handle(), vid, key,
                                          cookie or 0,
                                          0 if cookie is None else 1,
                                          ctypes.byref(out),
                                          ctypes.byref(out_len),
                                          ctypes.byref(out_size))
            if rc != DP_OK:
                _raise(rc, f"read {vid},{key:x}")
            try:
                blob = ctypes.string_at(out, out_len.value)
            finally:
                self._lib.dp_free(out)
        return blob, out_size.value

    def stat(self, vid: int) -> Optional[tuple[int, int, int, int]]:
        """(dat_size, live file_count, max_file_key, deleted_bytes), or
        None if the volume is not registered."""
        full = self.stat_full(vid)
        return None if full is None else full[:4]

    def stat_full(self, vid: int) -> Optional[tuple[int, int, int, int, int]]:
        """stat() plus the group-commit fsync pass count."""
        h = self._h  # read once: stop() nulls it concurrently, and a
        if h is None:  # NULL handle through ctypes would segfault
            return None
        ds = ctypes.c_ulonglong()
        fc = ctypes.c_ulonglong()
        mk = ctypes.c_ulonglong()
        db = ctypes.c_ulonglong()
        sp = ctypes.c_ulonglong()
        rc = self._lib.dp_stat(h, vid, ctypes.byref(ds),
                               ctypes.byref(fc), ctypes.byref(mk),
                               ctypes.byref(db), ctypes.byref(sp))
        if rc != DP_OK:
            return None
        return ds.value, fc.value, mk.value, db.value, sp.value

    def sync(self, vid: int) -> None:
        rc = self._lib.dp_sync(self._handle(), vid)
        if rc != DP_OK:
            _raise(rc, f"sync {vid}")

    def stats_all(self) -> dict[int, tuple[int, int, int, int, int]]:
        """Snapshot of per-volume (size, live_files, max_key,
        deleted_bytes, fsync_passes) for every registered volume — owns
        the registry lock so callers never touch plane internals."""
        with self._lock:
            vids = sorted(self.vids)
        out = {}
        for vid in vids:
            st = self.stat_full(vid)
            if st is not None:
                out[vid] = st
        return out

    def stop(self) -> None:
        if self._h:
            self._lib.dp_stop(self._h)
            self._h = None
