"""Volume server: object HTTP IO + admin/EC endpoints + heartbeat loop.

Equivalent of weed/server/volume_server*.go.  Object IO mirrors the
reference's public HTTP surface (GET/POST/DELETE /<vid>,<fid>), replication
mirrors topology/store_replicate.go (synchronous fan-out with ?type=replicate
loop-guard).  Admin "RPCs" are HTTP POST endpoints carrying the reference
gRPC names (volume_server.proto) — the full EC set is implemented:
Generate/Rebuild/Copy/Delete/Mount/Unmount/ShardRead/BlobDelete/ToVolume.

Uploads are raw-body POSTs with metadata in query/headers (divergence from
the reference's multipart forms, which the S3/filer layer will paper over).
"""

from __future__ import annotations

import os
import threading
import time
from typing import Optional

from ..ec.ec_volume import NeedleNotFoundError
from ..ec.layout import TOTAL_SHARDS_COUNT, to_ext
from ..storage.file_id import FileId
from ..storage.needle import (
    FLAG_HAS_LAST_MODIFIED,
    FLAG_HAS_MIME,
    FLAG_HAS_NAME,
    Needle,
)
from ..storage.ttl import TTL
from ..storage.volume import (
    CookieMismatchError,
    DeletedError,
    NotFoundError,
    volume_file_prefix,
)
from ..utils.httpd import (
    HttpError,
    Request,
    Response,
    Router,
    extract_upload,
    qint,
    http_bytes,
    http_json,
    serve,
)
from .store import Store

FID_PATTERN = r"/(\d+),([0-9a-f]+)"
# the loop fast path only takes bare object paths: any query string
# (resize, readDeleted, ...) or trailing segment stays on the pool
import re as _re

_FAST_FID_RE = _re.compile(r"^/(\d+),([0-9a-f]+)$", _re.IGNORECASE)


def _bind_with_retry(factory, timeout: float = 3.0, pause: float = 0.15,
                     role: str = "volume", server: str = ""):
    """The TCP data plane binds the DERIVED port tcp_port_for(http_port),
    so a prior server instance draining its listener (restart, test
    teardown, TIME_WAIT without reuse) races the bind — retry briefly
    before giving up.  Only bind failures retry: OSError, or a degraded
    FramedServer (its start() swallows the bind error and comes back
    with alive=False).  Anything else — e.g. the native plane's
    RuntimeError when there is no C++ toolchain — fails fast.

    Coming up degraded is an OBSERVABLE event, not a silent one: it
    lands on the tracer as a server.degraded_bind span and on /metrics
    as SeaweedFS_server_degraded_binds_total{role=...}."""
    deadline = time.monotonic() + timeout
    while True:
        exc, srv = None, None
        try:
            srv = factory()
            if getattr(srv, "alive", True):
                return srv
        except OSError as e:
            exc = e
        if time.monotonic() >= deadline:
            if exc is not None:
                raise exc
            from ..observability import events as _events
            from ..observability import get_tracer
            from ..stats import ec_pipeline_metrics

            ec_pipeline_metrics().degraded_binds.inc(role)
            get_tracer().event("server.degraded_bind", role=role,
                               detail="tcp plane bind failed; "
                                      "HTTP plane still serves")
            _events.emit("degraded_bind", role=role,
                         server=server or None,
                         detail="tcp plane bind failed; "
                                "HTTP plane still serves")
            return srv  # degraded server: the HTTP plane still serves
        time.sleep(pause)


class VolumeServer:
    def __init__(self, directories: list[str], master_url: str,
                 host: str = "127.0.0.1", port: int = 8080,
                 public_url: str = "", data_center: str = "",
                 rack: str = "", max_volume_count: int = 8,
                 pulse_seconds: float = 5.0, ec_engine: str = "cpu",
                 ec_mesh_devices: str = "",
                 guard: Optional["Guard"] = None,
                 backends: Optional[dict] = None,
                 full_sync_every: int = 12,
                 tls_context=None,
                 tcp: bool = True, use_mmap: bool = False,
                 dataplane: str = "python", max_inflight: int = 0,
                 needle_cache_mb: int = 64, heat: bool = True,
                 heat_halflife_s: float = 30.0, heat_topk: int = 512,
                 ledger: bool = True, ledger_halflife_s: float = 60.0):
        from ..security import Guard

        if backends:
            from ..storage.backend import configure_backends

            configure_backends(backends)
        # comma-separated master list; heartbeats follow the raft leader
        self.masters = [m.strip() for m in master_url.split(",") if m.strip()]
        self.master_url = self.masters[0]
        self.data_center = data_center
        self.rack = rack
        self.pulse_seconds = pulse_seconds
        # delta heartbeats between full syncs; every Nth pulse resends the
        # whole state so stat drift (sizes, counters) converges
        self.full_sync_every = max(1, full_sync_every)
        self.guard = guard or Guard()
        self.store = Store(directories, host, port, public_url,
                           max_volume_count, ec_engine=ec_engine,
                           ec_mesh_devices=ec_mesh_devices,
                           use_mmap=use_mmap,
                           needle_cache_mb=needle_cache_mb)
        from ..stats import ec_pipeline_metrics, volume_server_metrics

        self.metrics = volume_server_metrics()
        # register the self-healing counter families up front so a
        # scraper sees the series (at 0) before the first restart or
        # fallback ever happens
        ec_pipeline_metrics()
        from ..stats import (dataplane_metrics, ec_integrity_metrics,
                             needle_cache_metrics)

        ec_integrity_metrics()
        # same up-front registration for the serving-dataplane and
        # needle-cache families: a scraper must see the zero-valued
        # series before first traffic, not a gap
        dataplane_metrics()
        needle_cache_metrics()
        # EC bit-rot scrubber (scrubber.py): idle until /ec/scrub/start
        # (or weed shell ec.scrub); pauses itself while request traffic
        # is high
        from .scrubber import EcScrubber

        self._req_sample = (0.0, time.monotonic())
        self._req_busy = False
        self.scrubber = EcScrubber(self.store, busy_fn=self._scrub_busy)
        # sampled-trace span shipping to the master's collector (follows
        # the heartbeat's current leader); chained attach, so several
        # servers sharing one process each ship and the collector dedups
        from ..observability import get_tracer
        from ..observability.collector import TraceShipper

        self._trace_shipper = TraceShipper(
            get_tracer(), server=self.url,
            master_url_fn=lambda: self.master_url)
        # structured-event shipping to the master's cluster journal
        # (same follow-the-leader transport as the trace shipper), and
        # the flight-recorder spool on this server's first data dir so
        # captured bundles survive restarts with the data they explain
        from ..observability.events import EventShipper, get_journal
        from ..observability.flightrecorder import get_flightrecorder

        self._event_shipper = EventShipper(
            get_journal(), server=self.url,
            master_url_fn=lambda: self.master_url)
        # workload access-record shipping to the master's /cluster/
        # workload journal (observability/reqlog.py, same transport):
        # the recorder itself is process-global and off by default —
        # the shipper just stands ready for `workload.record`
        from ..observability.reqlog import ReqlogShipper, get_recorder

        self._reqlog_shipper = ReqlogShipper(
            get_recorder(), server=self.url,
            master_url_fn=lambda: self.master_url)
        # heat telemetry (observability/heat.py): per-SERVER accumulator
        # (never process-global — co-located fixtures must not pool
        # heat and the master attributes per peer) + snapshot shipper.
        # heat=False leaves router.heat/tcp.heat None: accounting off
        # is one attribute check per request at each chokepoint.
        from ..observability.heat import HeatAccumulator, HeatShipper
        from ..stats import heat_metrics

        heat_metrics()  # register the drop-counter family up front
        self.heat = HeatAccumulator(server=self.url,
                                    half_life=heat_halflife_s,
                                    top_k=heat_topk, enabled=heat)
        self._heat_shipper = HeatShipper(
            self.heat, server=self.url,
            master_url_fn=lambda: self.master_url) if heat else None
        if heat:
            cache = self.store.needle_cache
            cache.on_hit = self.heat.note_cache_hit
            cache.on_admit = self.heat.note_cache_admit
        # resource ledger (observability/ledger.py): per-SERVER request
        # cost tables + continuous profiler, shipped like heat.
        # ledger=False leaves router.ledger/tcp.ledger None — the
        # accounting-off cost is one attribute check per request.
        from ..observability.ledger import LedgerShipper, RequestLedger
        from ..observability.profiler import WindowedProfiler
        from ..stats import ledger_metrics

        ledger_metrics()  # register the families up front
        self.ledger = RequestLedger(
            server=self.url, half_life=ledger_halflife_s) \
            if ledger else None
        self._ledger_shipper = LedgerShipper(
            self.ledger, server=self.url,
            master_url_fn=lambda: self.master_url) if ledger else None
        self._profiler = WindowedProfiler() if ledger else None
        if self.ledger is not None:
            self.ledger.profile_fn = self._profiler.summary
            cache = self.store.needle_cache
            # compose with the heat hook: one callable slot, both
            # accumulators fed (heat wants per-volume attribution, the
            # ledger wants the per-request hit/miss stamp)
            prev_hit = cache.on_hit
            if prev_hit is None:
                cache.on_hit = RequestLedger.note_cache_hit
            else:
                def _on_hit(vid, key, nbytes, _heat_hook=prev_hit):
                    _heat_hook(vid, key, nbytes)
                    RequestLedger.note_cache_hit(vid, key, nbytes)
                cache.on_hit = _on_hit
            cache.on_miss = RequestLedger.note_cache_miss
        if directories:
            get_flightrecorder().configure(
                spool_dir=os.path.join(directories[0], "flightrecorder"))
        self.metrics.max_volume_counter.set(max_volume_count)
        self.router = Router("volume", metrics=self.metrics)
        self.router.server_url = self.url
        # admission control (utils/admission.py): -maxInflight > 0
        # sheds excess object-route load early with a fast 503 instead
        # of letting every caller time out late
        from ..utils.admission import maybe_controller

        self.router.admission = maybe_controller(max_inflight, "volume")
        # HTTP-plane heat feed: object-route responses note into the
        # per-server accumulator (None when -heat.off)
        self.router.heat = self.heat if heat else None
        # HTTP-plane ledger feed (None when -ledger.off)
        self.router.ledger = self.ledger
        # event-loop fast path (utils/eventloop.py): GET/HEAD object
        # reads whose needle the popularity cache holds dispatch inline
        # on the reactor loop — zero thread handoffs for the Zipf head
        self.router.loop_fast_probe = self._loop_fast_probe
        self._register_routes()
        self._server = None
        self._tls_context = tls_context
        self._stop = threading.Event()
        # vid -> (replica urls, expiry); see _lookup_replicas.  Request
        # threads fill it concurrently and the TTL prune rebinds the
        # whole dict — iteration during an unlocked insert would raise
        self._vid_lock = threading.Lock()
        self._vid_cache: dict[int, tuple[list, float]] = {}  # guarded-by: _vid_lock
        self.vid_cache_ttl = 10.0
        self._tcp_enabled = tcp
        self._tcp_server = None
        # "native": the C++ data plane owns the framed-TCP port and every
        # registered volume's needle IO (native/dataplane.cpp)
        self.dataplane = dataplane
        self._native_plane = None

    @property
    def url(self) -> str:
        return f"{self.store.ip}:{self.store.port}"

    def _loop_fast_probe(self, method: str, path: str) -> bool:
        """Loop-safe membership probe for the reactor's inline fast
        path: True only for plain object GET/HEADs (no query — resize
        and friends stay on the pool) whose needle the popularity
        cache is currently holding.  A True answer means the dispatch
        will complete without touching disk (a raced invalidation
        degrades to one bounded pread).  Must never block: one regex,
        one fid parse, one dict lookup."""
        m = _FAST_FID_RE.match(path)
        if m is None:
            return False
        cache = self.store.needle_cache
        if not cache.enabled or self.store.native_plane is not None:
            return False
        try:
            fid = FileId.parse(f"{m.group(1)},{m.group(2)}")
        except ValueError:
            return False
        return cache.contains(fid.volume_id, fid.key)

    def _scrub_busy(self) -> bool:
        """Scrubber load gate: True while this server is taking real
        request traffic (> ~50 req/s since the last sample), so scan IO
        never competes with the serving path."""
        prev_total, prev_t = self._req_sample
        now = time.monotonic()
        dt = now - prev_t
        if dt < 0.5:
            # the scrubber polls per 256KB block (every few ms at the
            # default rate); a rate computed over a ms-scale window turns
            # one stray request into ">250 req/s" — hold the last verdict
            # until a meaningful sample window has elapsed
            return self._req_busy
        total = sum(self.metrics.request_counter.snapshot().values())
        self._req_sample = (total, now)
        self._req_busy = (total - prev_total) / dt > 50.0
        return self._req_busy

    # --- lifecycle --------------------------------------------------------
    def start(self) -> "VolumeServer":
        self._server = serve(self.router, self.store.ip, self.store.port,  # weedlint: disable=W502 lifecycle handoff: written on the start() thread before the heartbeat thread exists
                             tls_context=self._tls_context)
        # BEFORE the TCP plane binds: a degraded_bind event emitted by
        # _bind_with_retry must find the shipper hooked (attach has no
        # backfill — an event emitted before it never ships)
        self._event_shipper.attach()
        # the framed-TCP path has no JWT or TLS slot, so it must never
        # open an unauthenticated side door: it stays closed when write
        # OR read JWTs are configured, and when cluster mTLS is on
        # (IP whitelists still apply when it does run)
        if self._tcp_enabled and not self.guard.signing_key \
                and not self.guard.read_signing_key \
                and self._tls_context is None:
            if self.dataplane == "native":
                # the C++ plane binds the TCP port itself and the store
                # funnels needle ops through it.  The plane has no
                # IP-whitelist slot and no replication fan-out, so:
                # with a whitelist configured it runs engine-only (no
                # listener at all — the Python TCP plane likewise drops
                # non-whitelisted connections, reads included), and W/D
                # frames are only accepted for replication-000 volumes
                # (store._native_add gates per volume).  Everything else
                # still gets native needle IO through the HTTP plane's
                # local funnel.
                from ..utils.framing import tcp_port_for
                from .dataplane import NativeDataPlane

                self.store.native_tcp_writes_ok = not self.guard.white_list
                tcp_port = (-1 if self.guard.white_list
                            else tcp_port_for(self.store.port))
                self._native_plane = _bind_with_retry(  # weedlint: disable=W502 lifecycle handoff: written on the start() thread before the heartbeat thread exists
                    lambda: NativeDataPlane(self.store.ip, tcp_port),
                    role="volume-native", server=self.url)
                self.store.attach_native_plane(self._native_plane)
            else:
                from .tcp import TcpVolumeServer

                self._tcp_server = _bind_with_retry(  # weedlint: disable=W502 lifecycle handoff: written on the start() thread before the heartbeat thread exists
                    lambda: TcpVolumeServer(
                        self.store, self.store.ip,
                        whitelist_ok=(self.guard.check_white_list
                                      if self.guard.is_write_active else None),
                        replicate_write=self._tcp_replicate_write,
                        replicate_delete=self._tcp_replicate_delete,
                        heat=self.heat if self.heat.enabled
                        else None).start(),
                    role="volume-tcp", server=self.url)
                if self._tcp_server is not None:
                    # framed-plane ledger feed: serve_frame reads it
                    # off the FramedServer (threaded) or listener
                    # owner (reactor)
                    self._tcp_server.ledger = self.ledger
        if self.ledger is not None:
            # loop saturation stats ride every ledger snapshot, and
            # the reactor watchdog records stalls THROUGH the ledger
            # (route + exemplar attribution lives there)
            from ..utils import eventloop

            if eventloop.reactor_enabled():
                reactor = eventloop.get_reactor()
                self.ledger.loop_stats_fn = reactor.loop_lag_stats
                reactor.stall_hook = self.ledger.note_stall
            self._profiler.start()
        self._trace_shipper.attach()
        self._reqlog_shipper.attach()
        if self._heat_shipper is not None:
            self._heat_shipper.attach()
        if self._ledger_shipper is not None:
            self._ledger_shipper.attach()
        threading.Thread(target=self._heartbeat_loop, daemon=True,
                         name=f"heartbeat:{self.url}").start()
        return self

    def stop(self) -> None:
        self._stop.set()
        self._trace_shipper.detach()
        self._event_shipper.detach()
        self._reqlog_shipper.detach()
        if self._heat_shipper is not None:
            self._heat_shipper.detach()
        if self._ledger_shipper is not None:
            self._ledger_shipper.detach()
        if self._profiler is not None:
            self._profiler.stop()
        if self.ledger is not None:
            # unhook the shared reactor so a stopped server's ledger no
            # longer receives stall callbacks (the process-wide reactor
            # outlives any one server in embedded/test topologies)
            from ..utils import eventloop

            if eventloop.reactor_enabled():
                reactor = eventloop.get_reactor()
                if reactor.stall_hook == self.ledger.note_stall:
                    reactor.stall_hook = None
                if self.ledger.loop_stats_fn == reactor.loop_lag_stats:
                    self.ledger.loop_stats_fn = None
        self.scrubber.stop(join_timeout=0.5)
        if self._tcp_server is not None:
            self._tcp_server.stop()
        if self._server:
            from ..utils.httpd import stop_server

            stop_server(self._server)
        if self._native_plane is not None:
            # unroute BEFORE tearing the plane down: an in-flight handler
            # that already passed has(vid) hits a drained (never freed)
            # C++ server and falls back to the Python engine
            self.store.native_plane = None
            self._native_plane.stop()
            self._native_plane = None  # weedlint: disable=W502 lifecycle teardown: runs after _stop is set and the servers are down
        self.store.close()

    def _heartbeat_loop(self) -> None:
        """Full state on the first pulse (and every full_sync_every after,
        or when the master asks to resync); tiny DELTA heartbeats in
        between (volume_grpc_client_to_master.go:48-120 streams incremental
        new/deleted lists instead of O(volumes) payloads every pulse)."""
        pulse = 0
        while not self._stop.is_set():
            full = pulse % self.full_sync_every == 0
            delta = None
            try:
                # payload building races volume swaps (compaction/tier
                # commits close+reopen .dat); a crash here would kill the
                # heartbeat thread and unregister the whole node
                if full:
                    payload = self.heartbeat_payload()
                else:
                    delta = self.store.pop_heartbeat_delta()
                    payload = {"ip": self.store.ip, "port": self.store.port,
                               "public_url": self.store.public_url,
                               "delta": True, **(delta or {})}
            except Exception:
                self._stop.wait(self.pulse_seconds)
                continue
            try:
                resp = http_json("POST", f"http://{self.master_url}/heartbeat",
                                 payload,
                                 timeout=max(3.0, self.pulse_seconds * 2))
                if resp.get("not_leader"):
                    if delta:
                        self.store.requeue_heartbeat_delta(delta)
                    leader = resp.get("leader")
                    if leader and leader != self.master_url:
                        # follower redirect: re-target without waiting, and
                        # open with a full sync (the new leader may be fresh)
                        self.master_url = leader  # weedlint: disable=W502 atomic str rebind: heartbeat loop and heartbeat_now converge on the same leader, readers tolerate one stale retry
                        pulse = 0
                        continue
                    # leaderless cluster: rotate and wait out the pulse
                    if len(self.masters) > 1:
                        i = (self.masters.index(self.master_url) + 1) \
                            if self.master_url in self.masters else 0
                        self.master_url = self.masters[i % len(self.masters)]  # weedlint: disable=W502 atomic str rebind: heartbeat loop and heartbeat_now converge on the same leader, readers tolerate one stale retry
                    pulse = 0
                    self._stop.wait(self.pulse_seconds)
                    continue
                if resp.get("resync"):
                    # master doesn't know us (restart): full sync right away
                    if delta:
                        self.store.requeue_heartbeat_delta(delta)
                    pulse = 0
                    continue
                self.store.volume_size_limit = int(
                    resp.get("volumeSizeLimit", self.store.volume_size_limit))
            except Exception:
                if delta:
                    self.store.requeue_heartbeat_delta(delta)
                # master down: rotate through the configured list
                if len(self.masters) > 1:
                    i = (self.masters.index(self.master_url) + 1) \
                        if self.master_url in self.masters else 0
                    self.master_url = self.masters[i % len(self.masters)]  # weedlint: disable=W502 atomic str rebind: heartbeat loop and heartbeat_now converge on the same leader, readers tolerate one stale retry
                pulse = 0
                self._stop.wait(self.pulse_seconds)
                continue
            pulse += 1
            self._stop.wait(self.pulse_seconds)

    def heartbeat_payload(self) -> dict:
        hb = self.store.collect_heartbeat()
        hb["data_center"] = self.data_center
        hb["rack"] = self.rack
        return hb

    def heartbeat_now(self) -> None:
        resp = http_json("POST", f"http://{self.master_url}/heartbeat",
                         self.heartbeat_payload(), timeout=30.0)
        if resp.get("not_leader") and resp.get("leader"):
            self.master_url = resp["leader"]  # weedlint: disable=W502 atomic str rebind: heartbeat loop and heartbeat_now converge on the same leader, readers tolerate one stale retry
            http_json("POST", f"http://{self.master_url}/heartbeat",
                      self.heartbeat_payload(), timeout=30.0)

    # --- helpers ----------------------------------------------------------
    def _tcp_replicate_write(self, fid_str: str, data: bytes) -> None:
        """Replica fan-out for the TCP plane (store_replicate.go:23-140
        semantics, carried over HTTP with the replicate loop guard)."""
        vid = int(fid_str.split(",")[0])
        for url in self._lookup_replicas(vid):
            if url == self.url:
                continue
            status, body, _ = http_bytes(
                "POST", f"http://{url}/{fid_str}?type=replicate", data,
                    timeout=60.0)
            if status not in (200, 201):
                raise OSError(f"replication to {url} failed: {status}")

    def _tcp_replicate_delete(self, fid_str: str) -> None:
        vid = int(fid_str.split(",")[0])
        for url in self._lookup_replicas(vid):
            if url == self.url:
                continue
            http_bytes("DELETE", f"http://{url}/{fid_str}?type=replicate",
                timeout=60.0)

    def _lookup_replicas(self, vid: int) -> list[str]:
        """Replica locations with a short TTL cache
        (operation/lookup_vid_cache.go — the reference caches for 10min;
        shorter here because membership changes propagate by heartbeat
        pulses).  Without the cache EVERY replicated write pays a master
        round trip, which caps cluster write throughput at the master."""
        now = time.monotonic()
        with self._vid_lock:
            hit = self._vid_cache.get(vid)
        if hit is not None and hit[1] > now:
            return hit[0]
        from ..utils import eventloop as _eventloop

        if _eventloop.reactor_enabled() \
                and _eventloop.get_reactor().on_loop_thread():
            # a cache-probed fast-path read can race a volume unmount
            # into the replica-redirect branch; the master round trip
            # below must NEVER run on the reactor loop (it would stall
            # every connection) — answer from the cache only, and let
            # the caller 404 so the client re-looks-up
            return []
        try:
            # the master round trip runs OUTSIDE _vid_lock (W504: a
            # slow master would stall every replicated write behind one
            # lookup); racing fills for the same vid are both correct
            r = http_json("GET",
                          f"http://{self.master_url}/dir/lookup?volumeId={vid}",
                              timeout=30.0)
            locs = [loc["url"] for loc in r.get("locations", [])]
        except HttpError:
            return []
        with self._vid_lock:
            self._vid_cache[vid] = (locs, now + self.vid_cache_ttl)
            if len(self._vid_cache) > 10_000:  # bound growth on churn
                self._vid_cache = {k: v for k, v in self._vid_cache.items()
                                   if v[1] > now}
        return locs

    def _verify_copied_shards(self, vid: int, collection: str,
                              base: str, shard_ids: list[int]) -> None:
        """Sidecar-aware cross-server transfer (/admin/ec/copy): check
        every fetched shard's blocks against the `.eci` that rode along
        before the copy is acknowledged.  Remote reads used to trust
        the wire — rot at the source or a mangled transfer became a
        trusted local replica.  A mismatching shard is deleted, counted
        as SeaweedFS_ec_corrupt_shards_total{source="wire"}, journaled
        as a shard_corrupt event, and the whole copy rejected so the
        caller retries from another holder.  No sidecar (pre-sidecar
        volume) or no row for a shard: verification is unavailable,
        the copy proceeds as before."""
        from ..ec.integrity import (EciSidecar, note_corruption,
                                    verify_shard_file)

        sc = EciSidecar.load(base)
        if sc is None:
            return
        bad: list[int] = []
        for sid in shard_ids:
            path = base + to_ext(sid)
            if not os.path.exists(path):
                continue
            try:
                blocks = verify_shard_file(sc, path, sid)
            except OSError:
                continue  # unreadable local disk: not wire corruption
            if blocks:
                note_corruption("wire", sid, base, block=blocks[0])
                bad.append(sid)
        if bad:
            # the whole request is rejected, so clean siblings fetched
            # by it must not be stranded either: the caller treats the
            # copy as failed, nothing will mount them, and an unmounted
            # shard file is invisible to heartbeats AND the scrubber —
            # an orphan forever.  Shards this server already serves
            # (mounted before the request) stay: their overwritten
            # bytes just verified clean.
            ev = self.store.ec_volumes.get(vid)
            mounted = set(ev.shards) if ev is not None else set()
            drop = [s for s in shard_ids
                    if s in bad or s not in mounted]
            self.store.ec_delete_shards(vid, drop, collection)
            raise HttpError(
                502, f"shards {bad} of volume {vid} failed .eci "
                     f"sidecar verification after copy; rejected")

    def _fetch_remote_shard(self, vid: int, shard_id: int, offset: int,
                            length: int) -> bytes:
        """store_ec.go:188-218: remote shard read, falling back to remote
        reconstruction inputs."""
        r = http_json("GET",
                      f"http://{self.master_url}/dir/lookup_ec?volumeId={vid}",
                          timeout=30.0)
        holders = r.get("shards", {}).get(str(shard_id), [])
        for url in holders:
            if url == self.url:
                continue
            status, body, _ = http_bytes(
                "GET",
                f"http://{url}/admin/ec/shard_read?volume_id={vid}"
                f"&shard={shard_id}&offset={offset}&size={length}",
                    timeout=60.0)
            if status == 200:
                return body
        # reconstruct from any data_shards distinct shards, local or remote
        rs = self.store.rs()
        bufs = [None] * TOTAL_SHARDS_COUNT
        have = 0
        ev = self.store.ec_volumes.get(vid)
        for sid in range(TOTAL_SHARDS_COUNT):
            if have >= rs.data_shards:
                break
            if ev is not None and sid in ev.shards:
                from ..utils.ioutil import pread_padded

                bufs[sid] = pread_padded(ev.shards[sid]._f, length, offset)
                have += 1
                continue
            for url in r.get("shards", {}).get(str(sid), []):
                if url == self.url:
                    continue
                status, body, _ = http_bytes(
                    "GET",
                    f"http://{url}/admin/ec/shard_read?volume_id={vid}"
                    f"&shard={sid}&offset={offset}&size={length}",
                        timeout=60.0)
                if status == 200:
                    import numpy as np

                    arr = np.zeros(length, dtype=np.uint8)
                    arr[: len(body)] = np.frombuffer(body, dtype=np.uint8)
                    bufs[sid] = arr
                    have += 1
                    break
        if have < rs.data_shards:
            raise HttpError(404, f"cannot recover shard {shard_id} of {vid}")
        rs.reconstruct(bufs)
        return bufs[shard_id].tobytes()

    def _try_partial_read(self, req, fid, rng_hdr: str):
        """Serve a Range GET by preading ONLY the requested data bytes off
        disk (read_needle_meta/read_needle_data split) — no whole-needle
        read, no CRC pass.  Returns None to fall back to the full-read path
        (v1 volumes, compressed or TTL'd needles, empty bodies, malformed
        range specs)."""
        from ..storage.needle import (FLAG_HAS_MIME, FLAG_HAS_TTL,
                                      FLAG_IS_COMPRESSED)
        from ..storage.types import Version
        from ..utils.httpd import UNSATISFIABLE_RANGE, parse_range

        if self.store.native_plane is not None \
                and self.store.native_plane.has(fid.volume_id):
            # the Python volume's needle map is stale while the native
            # plane owns the volume: fall back to the full-read path,
            # which routes through the plane and slices host-side
            return None
        v = self.store.volumes[fid.volume_id]
        if v.version == Version.V1:
            return None
        try:
            nv, data_size, flags, name, mime = v.read_needle_meta(
                fid.key, fid.cookie)
        except (NotFoundError, DeletedError):
            raise HttpError(404, "not found")
        except CookieMismatchError:
            raise HttpError(404, "cookie mismatch")
        except ValueError:
            return None
        if flags & (FLAG_IS_COMPRESSED | FLAG_HAS_TTL) or data_size == 0:
            return None  # need the full body (decompress / expiry check)
        rng = parse_range(rng_hdr, data_size)
        if rng == UNSATISFIABLE_RANGE:
            return Response(raw=b"", status=416, headers={
                "Content-Range": f"bytes */{data_size}"})
        if rng is None:
            return None
        off, sz = rng
        headers = {
            "Accept-Ranges": "bytes",
            "Content-Range": f"bytes {off}-{off + sz - 1}/{data_size}",
            "Content-Type": (mime.decode(errors="replace")
                             if flags & FLAG_HAS_MIME and mime
                             else "application/octet-stream"),
        }
        if name:
            headers["Content-Disposition"] = \
                f'inline; filename="{name.decode(errors="replace")}"'
        body = b"" if req.handler.command == "HEAD" \
            else v.read_needle_data(nv, off, sz)
        return Response(raw=body, status=206, headers=headers)

    # --- routes -----------------------------------------------------------
    def _register_routes(self) -> None:
        r = self.router

        # object + batch routes FIRST: Router.dispatch matches the
        # route table in registration order, and the hot read path
        # must not pay a failed regex per admin route before its own
        @r.route("GET", FID_PATTERN)
        @r.route("HEAD", FID_PATTERN)
        def read_object(req: Request) -> Response:
            fid = FileId.parse(f"{req.match.group(1)},{req.match.group(2)}")
            err = self.guard.check_read_jwt(
                req, f"{req.match.group(1)},{req.match.group(2)}")
            if err:
                raise HttpError(401, err)
            vid = fid.volume_id
            wants_resize = bool(req.query.get("width")
                                or req.query.get("height"))
            rng_hdr = req.headers.get("Range", "")
            if rng_hdr and not wants_resize and vid in self.store.volumes:
                partial = self._try_partial_read(req, fid, rng_hdr)
                if partial is not None:
                    return partial
            if vid in self.store.volumes:
                try:
                    n = self.store.read_needle(vid, fid.key, fid.cookie)
                except (NotFoundError, DeletedError):
                    raise HttpError(404, "not found")
                except CookieMismatchError:
                    raise HttpError(404, "cookie mismatch")
            elif vid in self.store.ec_volumes:
                try:
                    blob, size = self.store.read_ec_needle(
                        vid, fid.key, self._fetch_remote_shard)
                except NeedleNotFoundError:
                    raise HttpError(404, "not found")
                n = Needle.from_bytes(blob, size, self.store.ec_volumes[vid].version)
                if n.cookie != fid.cookie:
                    raise HttpError(404, "cookie mismatch")
            else:
                replicas = self._lookup_replicas(vid)
                others = [u for u in replicas if u != self.url]
                if not others:
                    raise HttpError(404, f"volume {vid} not found")
                import urllib.parse as _up

                return Response(
                    None, status=302,
                    headers={"Location": "http://%s%s" % (
                        others[0], _up.quote(req.path, safe="/,"))},
                    raw=b"")
            etag = f'"{n.etag()}"'
            if not wants_resize and req.headers.get("If-None-Match") == etag:
                # with resize params the served entity differs from the
                # stored one; the conditional is evaluated against the
                # resize-suffixed tag after the resize below
                return Response(None, status=304, raw=b"")
            headers = {"ETag": etag, "Accept-Ranges": "bytes"}
            if n.has(FLAG_HAS_NAME) and n.name:
                headers["Content-Disposition"] = f'inline; filename="{n.name.decode(errors="replace")}"'
            ctype = "application/octet-stream"
            if n.has(FLAG_HAS_MIME) and n.mime:
                ctype = n.mime.decode(errors="replace")
            headers["Content-Type"] = ctype
            body = n.data
            # FLAG_IS_COMPRESSED needles are stored gzipped: serve raw with
            # Content-Encoding to clients that accept gzip, else decompress
            # (volume_server_handlers_read.go:122-137)
            if n.is_compressed:
                if "gzip" in req.headers.get("Accept-Encoding", ""):
                    headers["Content-Encoding"] = "gzip"
                else:
                    from ..utils.compression import ungzip_data

                    body = ungzip_data(body)
            # on-the-fly image resize (volume_server_handlers_read.go
            # ?width/?height hook -> images/resizing.go; no-op when
            # Pillow is absent or the content is not an image)
            if wants_resize:
                from ..images import resized_from_query

                orig_body = body
                body, new_mime = resized_from_query(body, ctype, req.query)
                headers["Content-Type"] = new_mime
                if body is not orig_body:
                    # a resized representation must not share the
                    # original's cache key (same rule as the filer)
                    etag = '"%s-%sx%s-%s"' % (
                        n.etag(), req.query.get("width", ""),
                        req.query.get("height", ""),
                        req.query.get("mode", ""))
                    headers["ETag"] = etag
                if req.headers.get("If-None-Match") == etag:
                    return Response(None, status=304, raw=b"")
            if rng_hdr and "Content-Encoding" not in headers:
                from ..utils.httpd import UNSATISFIABLE_RANGE, parse_range

                rng = parse_range(rng_hdr, len(body))
                if rng == UNSATISFIABLE_RANGE:
                    return Response(raw=b"", status=416, headers={
                        "Content-Range": f"bytes */{len(body)}"})
                if rng is not None:
                    off, sz = rng
                    headers["Content-Range"] = \
                        f"bytes {off}-{off + sz - 1}/{len(body)}"
                    return Response(raw=body[off:off + sz], status=206,
                                    headers=headers)
            return Response(raw=body, headers=headers)

        @r.route("POST", "/batch/read")
        def batch_read(req: Request) -> Response:
            """Batched GET: one request carries N fids, the response is
            length-prefixed binary — status(1, 0=ok) | u32 len |
            payload per fid, in request order.  The store's ~930k
            ops/s batched read throughput is unreachable one HTTP
            round trip at a time; this amortizes the framing/dispatch
            cost over the whole batch.  Secured clusters (read JWTs)
            refuse: the batch has no per-fid token slot."""
            from ..utils.framing import U32 as _U32

            if self.guard.read_signing_key:
                raise HttpError(401, "batch read unavailable with "
                                     "read JWTs configured")
            fids = req.json().get("fids", [])
            if not isinstance(fids, list) or len(fids) > 10000:
                raise HttpError(400, "fids must be a list of <= 10000")
            heat = self.router.heat
            out = []
            for fid_str in fids:
                try:
                    fid = FileId.parse(str(fid_str))
                    n = self.store.read_needle(fid.volume_id, fid.key,
                                               fid.cookie)
                    data = n.data
                    if n.is_compressed:
                        from ..utils.compression import ungzip_data

                        data = ungzip_data(data)
                    out.append(b"\x00" + _U32.pack(len(data)))
                    out.append(data)
                    if heat is not None:
                        # the /batch/* paths never match the router
                        # hook's fid regex: feed per fid here
                        heat.note_read(fid.volume_id, len(data),
                                       fid=str(fid_str))
                except Exception as e:
                    msg = f"{type(e).__name__}: {e}".encode()[:4096]
                    out.append(b"\x01" + _U32.pack(len(msg)) + msg)
            return Response(raw=b"".join(out), headers={
                "X-Batch-Count": str(len(fids))})

        @r.route("POST", "/batch/write")
        def batch_write(req: Request) -> Response:
            """Batched PUT: body is u16 fid_len | fid | u32 data_len |
            data, repeated; the response lists per-fid results.  Writes
            fan out to replicas volume-by-volume on the same batch
            framing.  Secured clusters (write JWTs) refuse — no per-fid
            token slot."""
            import json as _json

            from ..utils.framing import pack_fid_frames, unpack_fid_frames

            if not self.guard.white_list_ok(req):
                raise HttpError(401, "not in whitelist")
            if self.guard.signing_key:
                raise HttpError(401, "batch write unavailable with "
                                     "write JWTs configured")
            # unpack the WHOLE batch before touching the store: a torn
            # frame must answer 400 with ZERO items applied, never
            # leave hidden local writes the replication loop below
            # would also skip
            try:
                items = unpack_fid_frames(req.body, with_data=True)
            except ValueError as e:
                raise HttpError(400, str(e))
            heat = self.router.heat
            results = []
            by_vid: dict[int, list[tuple[str, bytes]]] = {}
            for fid_str, data in items:
                try:
                    fid = FileId.parse(fid_str)
                    n = Needle(cookie=fid.cookie, id=fid.key, data=data)
                    n.set_flag(FLAG_HAS_LAST_MODIFIED)
                    n.last_modified = int(time.time())
                    size, _unchanged = self.store.write_needle(
                        fid.volume_id, n)
                    results.append({"fid": fid_str, "status": 201,
                                    "size": len(data)})
                    if heat is not None:
                        heat.note_write(fid.volume_id, len(data))
                    if req.query.get("type") != "replicate":
                        by_vid.setdefault(fid.volume_id, []).append(
                            (fid_str, data))
                except Exception as e:
                    results.append({"fid": fid_str, "status": 500,
                                    "error": f"{type(e).__name__}: {e}"})
            for vid, vitems in by_vid.items():
                for url in self._lookup_replicas(vid):
                    if url == self.url:
                        continue
                    status, rbody, _h = http_bytes(
                        "POST",
                        f"http://{url}/batch/write?type=replicate",
                        pack_fid_frames(vitems, with_data=True),
                        timeout=60.0)
                    if status != 200:
                        raise HttpError(
                            500, f"batch replication to {url} failed: "
                                 f"{status}")
                    # the replica answers 200 even with per-fid
                    # failures inside: a diverged replica must fail
                    # the batch loudly, not launder through transport
                    # success
                    try:
                        rres = _json.loads(rbody).get("results", [])
                    except Exception:
                        rres = []
                    bad = [r for r in rres if r.get("status") != 201]
                    if bad or len(rres) != len(vitems):
                        raise HttpError(
                            500, f"batch replication to {url}: "
                                 f"{len(bad) or 'missing'} item(s) "
                                 f"failed on the replica")
            return Response({"results": results})

        @r.route("POST", FID_PATTERN)
        @r.route("PUT", FID_PATTERN)
        def write_object(req: Request) -> Response:
            if not self.guard.white_list_ok(req):
                raise HttpError(401, "not in whitelist")
            err = self.guard.check_write_jwt(
                req, f"{req.match.group(1)},{req.match.group(2)}")
            if err:
                raise HttpError(401, err)
            try:
                fid = FileId.parse(f"{req.match.group(1)},{req.match.group(2)}")
            except ValueError as e:
                raise HttpError(400, str(e))
            # curl -F / form uploads arrive multipart-wrapped; unwrap the
            # file part on POST only (needle_parse_upload.go:46-50 —
            # PUT bodies are raw even when multipart-typed)
            if req.handler.command == "POST":
                data, part_name, part_mime = extract_upload(
                    req.body, req.headers.get("Content-Type") or "")
            else:
                data, part_name, part_mime = req.body, "", ""
            n = Needle(cookie=fid.cookie, id=fid.key, data=data)
            # client pre-gzipped the payload (upload_content.go:116):
            # remember it in the needle flags so reads can undo it
            if req.headers.get("Content-Encoding") == "gzip":
                from ..storage.needle import FLAG_IS_COMPRESSED

                n.set_flag(FLAG_IS_COMPRESSED)
            name = (req.query.get("name") or req.headers.get("X-File-Name")
                    or part_name)
            if name:
                n.set_flag(FLAG_HAS_NAME)
                n.name = name.encode()[:255]
            mime = req.headers.get("Content-Type")
            if mime and mime.lower().startswith("multipart/form-data"):
                mime = part_mime or None
            if mime in ("application/x-www-form-urlencoded",):  # client default
                mime = None
            if mime and mime != "application/octet-stream":
                n.set_flag(FLAG_HAS_MIME)
                n.mime = mime.encode()[:255]
            # every upload is stamped (needle.go:89-92 defaults to now):
            # the volume's last-modified drives ec.encode's quietFor
            # guard and TTL expiry, so an unstamped write would leave
            # the volume looking idle
            n.set_flag(FLAG_HAS_LAST_MODIFIED)
            n.last_modified = qint(req.query, "ts", int(time.time()))
            if req.query.get("ttl"):
                ttl = TTL.parse(req.query["ttl"])
                if ttl.count:
                    from ..storage.needle import FLAG_HAS_TTL

                    n.set_flag(FLAG_HAS_TTL)
                    n.ttl = ttl
            try:
                size, unchanged = self.store.write_needle(
                    fid.volume_id, n, fsync=req.query.get("fsync") == "true")
            except KeyError:
                raise HttpError(404, f"volume {fid.volume_id} not found")
            except PermissionError as e:
                raise HttpError(409, str(e))
            # replication fan-out (store_replicate.go:23-140): forward the
            # original parameters (ttl/ts/name/fsync) so replicas store
            # byte-identical needles
            if req.query.get("type") != "replicate":
                import urllib.parse

                params = {k: v for k, v in req.query.items() if k != "type"}
                params["type"] = "replicate"
                if name and "name" not in params:
                    # a multipart filename must survive the (unwrapped)
                    # replica forward
                    params["name"] = name
                # replicas must store the SAME timestamp, not their own
                # clock (store_replicate.go forwards ts)
                params.setdefault("ts", str(n.last_modified))
                # forward the signed fid token so replicas pass their guard
                from ..security import get_jwt

                token = get_jwt(req.headers, req.query)
                if token:
                    params["jwt"] = token
                qs = urllib.parse.urlencode(params)
                fwd_headers = {"Content-Type": mime or ""}
                if req.headers.get("Content-Encoding"):
                    fwd_headers["Content-Encoding"] = \
                        req.headers["Content-Encoding"]
                for url in self._lookup_replicas(fid.volume_id):
                    if url == self.url:
                        continue
                    status, body, _ = http_bytes(
                        "POST",
                        "http://%s%s?%s" % (
                            url, urllib.parse.quote(req.path, safe="/,"),
                            qs),
                        data, headers=fwd_headers, timeout=60.0)
                    if status != 200 and status != 201:
                        raise HttpError(500,
                                        f"replication to {url} failed: {status}")
            return Response({"name": name or "", "size": len(n.data),
                             "eTag": n.etag()}, status=201)

        @r.route("DELETE", FID_PATTERN)
        def delete_object(req: Request) -> Response:
            if not self.guard.white_list_ok(req):
                raise HttpError(401, "not in whitelist")
            # deletes are mutations: same per-fid write token as POST
            err = self.guard.check_write_jwt(
                req, f"{req.match.group(1)},{req.match.group(2)}")
            if err:
                raise HttpError(401, err)
            fid = FileId.parse(f"{req.match.group(1)},{req.match.group(2)}")
            vid = fid.volume_id
            if vid in self.store.ec_volumes:
                self.store.ec_delete_needle(vid, fid.key)
                size = 0
            else:
                try:
                    size = self.store.delete_needle(
                        vid, Needle(cookie=fid.cookie, id=fid.key),
                        fsync=req.query.get("fsync") == "true")
                except KeyError:
                    raise HttpError(404, f"volume {vid} not found")
            if req.query.get("type") != "replicate":
                from ..security import get_jwt

                token = get_jwt(req.headers, req.query)
                qs = "?type=replicate" + (f"&jwt={token}" if token else "")
                import urllib.parse as _up

                for url in self._lookup_replicas(vid):
                    if url == self.url:
                        continue
                    http_bytes("DELETE", "http://%s%s%s" % (
                        url, _up.quote(req.path, safe="/,"), qs), timeout=60.0)
            return Response({"size": size})


        @r.route("POST", "/admin/leave")
        def leave(req: Request) -> Response:
            """volume.server.leave: stop heartbeating so the master's
            janitor unregisters this node; data and the HTTP surface stay
            up until the process exits (VolumeServerLeave RPC)."""
            self._stop.set()
            return Response({"left": True})

        @r.route("POST", "/admin/heartbeat_now")
        def heartbeat_now(req: Request) -> Response:
            self.heartbeat_now()
            return Response({})

        @r.route("GET", "/metrics")
        def metrics(req: Request) -> Response:
            from ..stats import REGISTRY

            # refresh gauges from the live store (volume + EC-shard counts,
            # disk usage per collection — stats/metrics.go gauge family)
            self.metrics.volume_counter.clear()
            self.metrics.disk_size_gauge.clear()
            for v in list(self.store.volumes.values()):
                self.metrics.volume_counter.add(v.collection, "volume", 1)
                try:
                    size = v.data_size
                except Exception:
                    continue  # mid-compaction-commit swap (closed .dat):
                    # skip this scrape's sample rather than 500 the
                    # whole exposition (same guard as status_doc)
                self.metrics.disk_size_gauge.add(
                    v.collection, "volume", size)
            for vid, ev in list(self.store.ec_volumes.items()):
                self.metrics.volume_counter.add(
                    self.store.ec_collections.get(vid, ""), "ec_shards",
                    len(ev.shards))
            plane = self.store.native_plane
            self.metrics.native_plane_gauge.clear()
            if plane is not None:
                for vid, (ds, fc, _mk, db, sp) in \
                        plane.stats_all().items():
                    g = self.metrics.native_plane_gauge
                    g.set(str(vid), "size_bytes", ds)
                    g.set(str(vid), "live_files", fc)
                    g.set(str(vid), "deleted_bytes", db)
                    g.set(str(vid), "fsync_passes", sp)
            from ..stats.metrics import exemplars_requested

            return Response(
                raw=REGISTRY.expose(
                    exemplars=exemplars_requested(req)).encode(),
                headers={
                "Content-Type": "text/plain; version=0.0.4; charset=utf-8"})

        def status_doc() -> dict:
            volumes = []
            for v in list(self.store.volumes.values()):  # snapshot: races
                try:                                     # assign/delete
                    volumes.append(self.store._volume_info(v))
                except Exception:
                    # mid-swap (compaction/tier commit): report the plain
                    # attributes rather than dropping the volume — the
                    # copy protocol's was_readonly probe must still see
                    # an operator fence
                    volumes.append({"id": v.id, "collection": v.collection,
                                    "read_only": v.read_only,
                                    "mid_swap": True})
            from ..stats import ec_pipeline_metrics

            doc = {
                "Version": "seaweedfs-tpu 0.1",
                "Volumes": volumes,
                "EcVolumes": sorted(list(self.store.ec_volumes)),
                # self-healing pipeline health: nonzero fallbacks mean
                # dispatches degraded to the CPU codec (worker_restarts
                # stays 0: no parity worker process exists) — encodes
                # still completed byte-identical, but perf numbers from
                # this server may reflect degraded runs
                "EcPipeline": ec_pipeline_metrics().totals(),
            }
            if self.store.ec_engine_name in ("tpu", "mesh"):
                # the device the EC engines compute on, and how many
                # codec matmuls (degraded reads) reached it
                from ..ops.device import device_doc

                rs = self.store.rs()
                doc["EcDevice"] = {**device_doc(),
                                   "codec_engine": rs.engine.name,
                                   "codec_matmuls": getattr(
                                       rs.engine, "calls", 0),
                                   # the width classes compiled so far
                                   "reconstruct_classes": sorted(getattr(
                                       getattr(rs.engine, "classes", None),
                                       "compiled", ()))}
            from ..stats import ec_integrity_metrics

            # bit-rot defense: nonzero corrupt_shards means sidecar
            # verification demoted shards somewhere on this server
            doc["EcIntegrity"] = ec_integrity_metrics().totals()
            # serving dataplane: popularity-cache occupancy/hit ratio
            # and reactor dispatch/abort accounting
            doc["NeedleCache"] = self.store.needle_cache.status()
            from ..stats import dataplane_metrics

            doc["Dataplane"] = dataplane_metrics().totals()
            # heat telemetry: accumulator occupancy + shipper loss
            doc["Heat"] = {
                **self.heat.status(),
                "shipped": self._heat_shipper.shipped
                if self._heat_shipper is not None else 0,
                "dropped": self._heat_shipper.dropped
                if self._heat_shipper is not None else 0,
            }
            # resource ledger: cost-table occupancy + shipper loss
            if self.ledger is not None:
                doc["Ledger"] = {
                    **self.ledger.status(),
                    "shipped": self._ledger_shipper.shipped
                    if self._ledger_shipper is not None else 0,
                    "dropped": self._ledger_shipper.dropped
                    if self._ledger_shipper is not None else 0,
                }
            scrub_st = self.scrubber.status()  # locked verdict snapshot
            doc["EcScrub"] = {
                "running": scrub_st["running"],
                "passes": scrub_st["passes"],
                "cursor": scrub_st["cursor"],
                "verdicts": {v: d.get("status", "?")
                             for v, d in scrub_st["verdicts"].items()},
            }
            plane = self.store.native_plane
            if plane is not None:
                doc["NativeDataPlane"] = {
                    "tcp_port": plane.port,
                    "volumes": {
                        vid: {"size": ds, "file_count": fc,
                              "deleted_bytes": db, "fsync_passes": sp}
                        for vid, (ds, fc, _mk, db, sp)
                        in plane.stats_all().items()},
                }
            return doc

        @r.route("GET", "/status")
        def status(req: Request) -> Response:
            return Response(status_doc())

        @r.route("GET", "/debug/heat")
        def debug_heat(req: Request) -> Response:
            """This server's decayed heat snapshot: per-volume rates,
            the top-K needle sketch, accumulator/shipper accounting —
            the per-peer view the master merges at /cluster/heat."""
            try:
                top = int(req.query.get("top", "64"))
            except (TypeError, ValueError):
                top = 64
            doc = self.heat.snapshot(top_k=max(0, min(top, 1024)))
            doc["status"] = self.heat.status()
            if self._heat_shipper is not None:
                doc["shipper"] = {
                    "shipped": self._heat_shipper.shipped,
                    "dropped": self._heat_shipper.dropped,
                    "interval_s": self._heat_shipper.interval}
            return Response(doc)

        @r.route("GET", "/debug/ledger")
        def debug_ledger(req: Request) -> Response:
            """This server's resource-ledger snapshot: decayed
            per-route / per-client CPU, byte and queue-wait rates,
            loop saturation stats, and the continuous profiler's
            current top/rising stacks — the per-peer view the master
            merges at /cluster/ledger."""
            if self.ledger is None:
                return Response({"error": "ledger disabled"},
                                status=404)
            doc = self.ledger.snapshot()
            if self._ledger_shipper is not None:
                doc["shipper"] = {
                    "shipped": self._ledger_shipper.shipped,
                    "dropped": self._ledger_shipper.dropped,
                    "interval_s": self._ledger_shipper.interval}
            return Response(doc)

        @r.route("GET", "/stats/counter")
        def stats_counter(req: Request) -> Response:
            """statsCounterHandler (common.go:228): per-operation request
            counts, rendered from the same collectors /metrics exposes."""
            counters = {
                labels[0] if labels else "": int(v)
                for labels, v
                in self.metrics.request_counter.snapshot().items()}
            return Response({"Version": "seaweedfs-tpu 0.1",
                             "Counters": counters})

        @r.route("GET", "/stats/memory")
        def stats_memory(req: Request) -> Response:
            import resource
            import sys as _sys

            ru = resource.getrusage(resource.RUSAGE_SELF)
            # ru_maxrss is KB on Linux but BYTES on macOS
            rss_kb = (ru.ru_maxrss // 1024 if _sys.platform == "darwin"
                      else ru.ru_maxrss)
            return Response({"Version": "seaweedfs-tpu 0.1",
                             "Memory": {"MaxRssKb": rss_kb,
                                        "UserSeconds": ru.ru_utime,
                                        "SystemSeconds": ru.ru_stime}})

        @r.route("GET", "/stats/disk")
        def stats_disk(req: Request) -> Response:
            """statsDiskHandler: statvfs per volume directory."""
            ds = []
            for loc in self.store.locations:
                st = os.statvfs(loc.directory)
                total = st.f_frsize * st.f_blocks
                free = st.f_frsize * st.f_bavail
                ds.append({"dir": os.path.abspath(loc.directory),
                           "all": total, "free": free,
                           "used": total - free,
                           "percent_free": round(100.0 * free /
                                                 max(total, 1), 2)})
            return Response({"Version": "seaweedfs-tpu 0.1",
                             "DiskStatuses": ds})

        from ..utils.debug import register_debug_routes

        register_debug_routes(r, name=f"volume server {self.url}",
                              status_fn=lambda: {
                                  **status_doc(),
                                  "Master": self.master_url,
                                  "DataCenter": self.data_center,
                                  "Rack": self.rack,
                              })

        # --- admin: volume lifecycle ---------------------------------
        @r.route("POST", "/admin/assign_volume")
        def assign_volume(req: Request) -> Response:
            b = req.json()
            self.store.add_volume(int(b["volume_id"]), b.get("collection", ""),
                                  b.get("replication", "000"), b.get("ttl", ""),
                                  offset_5=bool(b.get("offset_5", False)))
            return Response({})

        @r.route("POST", "/admin/delete_volume")
        def delete_volume(req: Request) -> Response:
            self.store.delete_volume(int(req.json()["volume_id"]))
            return Response({})

        @r.route("POST", "/admin/mount")
        def mount(req: Request) -> Response:
            self.store.mount_volume(int(req.json()["volume_id"]))
            return Response({})

        @r.route("POST", "/admin/unmount")
        def unmount(req: Request) -> Response:
            self.store.unmount_volume(int(req.json()["volume_id"]))
            return Response({})

        @r.route("POST", "/admin/readonly")
        def readonly(req: Request) -> Response:
            b = req.json()
            vid = int(b["volume_id"])
            self.store.get_volume(vid).read_only = bool(
                b.get("readonly", True))
            # writable-set change must reach the master within one pulse,
            # not wait for the next periodic full sync
            self.store.note_volume_change(vid)
            # refresh the native plane's read_only flag (no-op while a
            # vacuum/tier hold is outstanding — re-registering mid-compact
            # would put the plane back under files about to be swapped)
            self.store.native_refresh(vid)
            return Response({})

        # --- admin: vacuum -------------------------------------------
        @r.route("POST", "/admin/vacuum_check")
        def vacuum_check(req: Request) -> Response:
            vid = int(req.json()["volume_id"])
            v = self.store.get_volume(vid)
            plane = self.store.native_plane
            if plane is not None and plane.has(vid):
                # the Python map's deletion counters are frozen while the
                # plane owns the volume; its own counters know the truth
                st = plane.stat(vid)
                if st is not None:
                    dat_size, _fc, _mk, deleted_bytes = st
                    return Response({"garbage_ratio":
                                     deleted_bytes / dat_size
                                     if dat_size else 0.0})
            return Response({"garbage_ratio": v.garbage_ratio()})

        @r.route("POST", "/admin/vacuum_compact")
        def vacuum_compact(req: Request) -> Response:
            vid = int(req.json()["volume_id"])
            # quiesce the native plane for the whole compact->commit
            # window; writes fall back to the reopened Python engine so
            # the makeup-diff replay sees them
            self.store.native_detach(vid)
            try:
                with self.store.volume_locks[vid]:
                    self.store.get_volume(vid).compact()
            except BaseException:
                # a failed compact gets no commit/cleanup from the
                # master: reattach here or the volume is stuck on the
                # slow path until restart
                self.store.native_reattach(vid)
                raise
            return Response({})

        @r.route("POST", "/admin/vacuum_commit")
        def vacuum_commit(req: Request) -> Response:
            vid = int(req.json()["volume_id"])
            with self.store.volume_locks[vid]:
                self.store.get_volume(vid).commit_compact()
            # compaction dropped deleted/expired needles the per-key
            # hooks never saw: the whole volume leaves the read cache
            self.store.needle_cache.invalidate_volume(vid, "vacuum")
            self.store.native_reattach(vid)
            return Response({})

        @r.route("POST", "/admin/vacuum_cleanup")
        def vacuum_cleanup(req: Request) -> Response:
            vid = int(req.json()["volume_id"])
            self.store.get_volume(vid).cleanup_compact()
            self.store.native_reattach(vid)
            return Response({})

        # --- admin: volume copy/move (volume_grpc_copy.go) -------------
        @r.route("GET", "/admin/volume_download")
        def volume_download(req: Request) -> Response:
            vid = qint(req.query, "volume_id")
            ext = req.query["ext"]
            if ext not in (".dat", ".idx", ".vif"):
                raise HttpError(400, f"bad ext {ext}")
            v = self.store.get_volume(vid)
            path = v.file_prefix + ext
            if not os.path.exists(path):
                raise HttpError(404, f"{path} not found")
            # streamed in bounded chunks (the CopyFile streaming RPC,
            # volume_grpc_copy.go): a 30GB .dat never lands in memory.
            # The source is readonly during copies, so no lock is held
            # across the transfer.
            return Response(file_path=path)

        @r.route("POST", "/admin/volume_copy")
        def volume_copy(req: Request) -> Response:
            """VolumeCopy: pull .dat/.idx from the source server, then mount.
            The source is marked readonly for a consistent snapshot."""
            b = req.json()
            vid = int(b["volume_id"])
            collection = b.get("collection", "")
            source = b["source_data_node"]
            if vid in self.store.volumes:
                raise HttpError(409, f"volume {vid} already here")
            # remember the source's current readonly state and restore it —
            # an operator-fenced volume must stay fenced after the copy
            src_status = http_json("GET", f"http://{source}/status",
                timeout=30.0)
            was_readonly = any(v["id"] == vid and v["read_only"]
                               for v in src_status.get("Volumes", []))
            http_json("POST", f"http://{source}/admin/readonly",
                      {"volume_id": vid, "readonly": True}, timeout=30.0)
            try:
                from ..utils.httpd import http_download

                base = volume_file_prefix(self.store.locations[0].directory,
                                          collection, vid)
                for ext in (".dat", ".idx"):
                    status = http_download(
                        "GET", f"http://{source}/admin/volume_download"
                               f"?volume_id={vid}&ext={ext}",
                        base + ext, timeout=3600)
                    if status != 200:
                        raise HttpError(500, f"download {ext}: {status}")
                self.store._open_volume(
                    os.path.dirname(base), collection, vid)
            finally:
                http_json("POST", f"http://{source}/admin/readonly",
                          {"volume_id": vid, "readonly": was_readonly},
                              timeout=30.0)
            return Response({})

        @r.route("POST", "/admin/batch_delete")
        def batch_delete(req: Request) -> Response:
            """POST /delete multi-fid (volume_grpc_batch_delete.go), with
            replica fan-out unless the request is itself a replicate.
            On secured clusters each fid must carry a master-signed write
            token (body "jwts": {fid: token}) — same per-fid authorization
            as single DELETE, so this endpoint cannot bypass it."""
            if not self.guard.white_list_ok(req):
                raise HttpError(401, "not in whitelist")
            body = req.json()
            is_replicate = bool(body.get("replicate"))
            jwts = body.get("jwts", {})
            results = []
            fanned: dict[str, list[str]] = {}
            for fid_str in body.get("fids", []):
                if self.guard.signing_key:
                    from ..security.jwt import JwtError, decode_jwt

                    try:
                        claims = decode_jwt(self.guard.signing_key,
                                            jwts.get(fid_str, ""))
                        if claims.get("fid") != fid_str:
                            raise JwtError("fid mismatch")
                    except JwtError as e:
                        results.append({"fid": fid_str, "error": str(e),
                                        "status": 401})
                        continue
                try:
                    fid = FileId.parse(fid_str)
                    if fid.volume_id in self.store.ec_volumes:
                        self.store.ec_delete_needle(fid.volume_id, fid.key)
                        size = 0
                    else:
                        size = self.store.delete_needle(
                            fid.volume_id,
                            Needle(cookie=fid.cookie, id=fid.key))
                    results.append({"fid": fid_str, "status": 202, "size": size})
                    if not is_replicate:
                        for url in self._lookup_replicas(fid.volume_id):
                            if url != self.url:
                                fanned.setdefault(url, []).append(fid_str)
                except Exception as e:
                    results.append({"fid": fid_str, "status": 404,
                                    "error": str(e)})
            for url, fids in fanned.items():
                http_json("POST", f"http://{url}/admin/batch_delete",
                          {"fids": fids, "replicate": True,
                           "jwts": {f: jwts[f] for f in fids if f in jwts}},
                               timeout=30.0)
            return Response({"results": results})

        @r.route("GET", "/admin/tail")
        def tail(req: Request) -> Response:
            """VolumeIncrementalCopy / VolumeTailSender: raw needle records
            appended after ?since_ns (volume_backup.go:66, the follower
            re-requests with the returned X-Last-Append-At-Ns until empty)."""
            from ..storage.volume_backup import records_since

            vid = qint(req.query, "volume_id")
            since_ns = qint(req.query, "since_ns", 0)
            try:
                v = self.store.get_volume(vid)
            except KeyError:
                raise HttpError(404, f"volume {vid} not found")
            blob, last_ts = records_since(
                v, since_ns,
                max_bytes=qint(req.query, "max_bytes", 64 << 20))
            return Response(raw=blob, headers={
                "X-Last-Append-At-Ns": str(last_ts),
                "X-Volume-Version": str(int(v.version))})

        @r.route("POST", "/admin/tier_upload")
        def tier_upload(req: Request) -> Response:
            """VolumeTierMoveDatToRemote (volume_grpc_tier_upload.go).
            With ``two_phase`` the call stops after the verified upload
            (manifest `pending`, local .dat retained, writes frozen):
            the control plane journals its tier_committed raft record
            and then POSTs /admin/tier_commit — the crash-safe
            autoscaler protocol.  Without it, the legacy one-shot."""
            b = req.json()
            vid = int(b["volume_id"])
            if bool(b.get("two_phase")):
                try:
                    v = self.store.get_volume(vid)
                except KeyError:
                    raise HttpError(404, f"volume {vid} not found")
                with self.store.volume_locks[vid]:
                    manifest = v.tier_upload_begin(b["backend"])
                return Response({"manifest": manifest})
            self.store.native_detach(vid)  # tiered .dat leaves the plane
            try:
                try:
                    v = self.store.get_volume(vid)
                except KeyError:
                    raise HttpError(404, f"volume {vid} not found")
                with self.store.volume_locks[vid]:
                    remote = v.tier_upload(
                        b["backend"], keep_local=bool(b.get("keep_local")))
            finally:
                # no-op when the upload succeeded (the volume is now
                # tiered, which _native_add skips); a failure reattaches
                self.store.native_reattach(vid)
            return Response({"remote": remote})

        @r.route("POST", "/admin/tier_commit")
        def tier_commit(req: Request) -> Response:
            """Phase 2 of the two-phase tier move: the control plane
            already journaled tier_committed on the raft log — persist
            `committed` locally, write the .vif, drop the local .dat
            and reopen tiered.  Idempotent (safe to re-issue after a
            master failover); 404s when no manifest is pending (a
            crash-recovered volume GC'd an uncommitted upload)."""
            vid = int(req.json()["volume_id"])
            self.store.native_detach(vid)  # tiered .dat leaves the plane
            try:
                try:
                    v = self.store.get_volume(vid)
                except KeyError:
                    raise HttpError(404, f"volume {vid} not found")
                try:
                    with self.store.volume_locks[vid]:
                        manifest = v.tier_commit()
                except FileNotFoundError as e:
                    raise HttpError(404, str(e))
                except PermissionError as e:
                    raise HttpError(409, str(e))
            finally:
                self.store.native_reattach(vid)
            return Response({"manifest": manifest})

        @r.route("POST", "/admin/tier_abort")
        def tier_abort(req: Request) -> Response:
            """Roll back an uncommitted two-phase upload: delete the
            remote object, drop the manifest, thaw writes."""
            vid = int(req.json()["volume_id"])
            try:
                v = self.store.get_volume(vid)
            except KeyError:
                raise HttpError(404, f"volume {vid} not found")
            try:
                with self.store.volume_locks[vid]:
                    v.tier_abort()
            except PermissionError as e:
                raise HttpError(409, str(e))
            return Response({})

        @r.route("POST", "/admin/tier_download")
        def tier_download(req: Request) -> Response:
            """VolumeTierMoveDatFromRemote (volume_grpc_tier_download.go):
            the verified recall — downloads to a temp file, checks size
            + crc32 against the tier manifest, atomically swaps."""
            vid = int(req.json()["volume_id"])
            try:
                v = self.store.get_volume(vid)
            except KeyError:
                raise HttpError(404, f"volume {vid} not found")
            with self.store.volume_locks[vid]:
                v.tier_download()
            self.store.native_register(vid)  # local .dat again
            return Response({})

        @r.route("POST", "/admin/configure_replication")
        def configure_replication(req: Request) -> Response:
            """VolumeConfigure (volume_grpc_admin.go): rewrite the
            superblock's replica placement in place."""
            from ..storage.super_block import ReplicaPlacement

            b = req.json()
            vid = int(b["volume_id"])
            try:
                v = self.store.get_volume(vid)
            except KeyError:
                raise HttpError(404, f"volume {vid} not found")
            rp = ReplicaPlacement.parse(b["replication"])
            with self.store.volume_locks[vid]:
                if v.tiered:
                    raise HttpError(
                        409, f"volume {vid} is tiered (read-only); "
                        "tier.download before reconfiguring")
                # persist FIRST: if the write fails, memory still matches
                # what is on disk
                old_rp = v.super_block.replica_placement
                v.super_block.replica_placement = rp
                try:
                    v._dat.write_at(v.super_block.to_bytes(), 0)
                except Exception:
                    v.super_block.replica_placement = old_rp
                    raise
            self.heartbeat_now()
            return Response({"replication": str(rp)})

        @r.route("POST", "/query")
        def query(req: Request) -> Response:
            """Query RPC (volume_grpc_query.go): filter + project stored
            JSON/CSV objects server-side; body carries from_file_ids,
            selection, filter, and input serialization."""
            from ..query import execute_query

            b = req.json()
            rows = []
            for fid_str in b.get("from_file_ids", []):
                fid = FileId.parse(fid_str)
                try:
                    n = self.store.read_needle(fid.volume_id, fid.key,
                                               fid.cookie)
                except Exception as e:
                    raise HttpError(404, f"{fid_str}: {e}")
                rows.extend(execute_query(
                    n.data, b.get("selections"), b.get("filter"),
                    b.get("input_format", "json")))
            return Response({"rows": rows})

        @r.route("POST", "/admin/volume_check")
        def volume_check(req: Request) -> Response:
            """fsck backend: scan the volume, verify needle CRCs against the
            index (volume.fsck / volume.check.disk analog)."""
            vid = int(req.json()["volume_id"])
            v = self.store.get_volume(vid)
            indexed = len(v.nm)
            scanned, crc_errors = 0, 0
            with self.store.volume_locks[vid]:
                for nv in list(v.nm):
                    scanned += 1
                    try:
                        # full record parse verifies the STORED crc against
                        # the data bytes (needle_read_write.go:238-244)
                        v._read_needle_at(nv.offset, nv.size)
                    except Exception:
                        crc_errors += 1
            return Response({"indexed": indexed, "scanned_live": scanned,
                             "crc_errors": crc_errors})

        # --- admin: EC (volume_grpc_erasure_coding.go) ----------------
        def _ec_pipeline_snapshot() -> dict:
            from ..stats import ec_pipeline_metrics

            return ec_pipeline_metrics().totals()

        def _ec_pipeline_health(before: dict) -> dict:
            """Delta of the self-healing counters across one admin EC
            operation: the caller (shell, maintenance script) can tell a
            clean run from one that survived worker restarts or degraded
            to the CPU codec.  Best-effort attribution — the counters
            are process-global, so EC operations running concurrently on
            OTHER volumes can leak into each other's deltas (a false
            "degraded" flag, never a false "clean")."""
            now = _ec_pipeline_snapshot()
            return {"worker_restarts":
                        now["worker_restarts"] - before["worker_restarts"],
                    "engine_fallbacks":
                        now["engine_fallbacks"] - before["engine_fallbacks"]}

        # --- EC bit-rot scrubber (scrubber.py) -------------------------
        @r.route("POST", "/ec/scrub/start")
        def ec_scrub_start(req: Request) -> Response:
            """Launch (or re-launch) the background scan.  Body knobs:
            rate_mb_s (IO cap, 0 unthrottled), interval_s (0 = one
            pass then stop, >0 = loop), backfill (compute sidecars for
            pre-sidecar shard sets), volume_id (targeted one-pass
            verification of just that volume — the coordinator's
            post-repair re-scrub; the pass adopts THIS request's trace
            context, so the verdict flip journals under the repair)."""
            try:
                b = req.json()
            except Exception:
                b = {}
            vid = b.get("volume_id")
            try:
                vid = int(vid) if vid is not None else None
            except (TypeError, ValueError):
                raise HttpError(400, f"bad volume_id {vid!r}")
            ctx = None
            if vid is not None:
                from ..observability import context as _trace_context

                ctx = _trace_context.fork_for_thread()
            started = self.scrubber.start(
                rate_mb_s=(float(b["rate_mb_s"])
                           if "rate_mb_s" in b else None),
                interval_s=(float(b["interval_s"])
                            if "interval_s" in b else None),
                backfill=(bool(b["backfill"]) if "backfill" in b else None),
                volume_id=vid, ctx=ctx)
            return Response({"started": started, **self.scrubber.status()})

        @r.route("POST", "/ec/scrub/stop")
        def ec_scrub_stop(req: Request) -> Response:
            """Stop the scan; the cursor survives, so the next start
            resumes from the same (volume, shard)."""
            self.scrubber.stop()
            return Response(self.scrubber.status())

        @r.route("GET", "/ec/scrub/status")
        def ec_scrub_status(req: Request) -> Response:
            return Response(self.scrubber.status())

        def _ec_device_report(engine: Optional[str]) -> dict:
            """Where a tpu/mesh EC operation ran: the device as JAX
            reports it in THIS process (the one holding the chip) and
            the streaming encoder's per-call counters."""
            if (engine or self.store.ec_engine_name) not in ("tpu", "mesh"):
                return {}
            from ..ops.device import device_doc

            st = self.store.last_ec_stats
            return {"device": device_doc(),
                    "encoder": {k: st[k] for k in (
                        "dispatches", "mapped_dispatches", "bytes_in",
                        "wall_s", "retries",
                        "fallbacks", "worker_restarts", "devices",
                        "per_device") if k in st}}

        @r.route("POST", "/admin/ec/generate")
        def ec_generate(req: Request) -> Response:
            b = req.json()
            before = _ec_pipeline_snapshot()
            self.store.ec_generate(int(b["volume_id"]), b.get("collection", ""),
                                   b.get("engine"))
            return Response({"pipeline": _ec_pipeline_health(before),
                             **_ec_device_report(b.get("engine"))})

        @r.route("POST", "/admin/ec/rebuild")
        def ec_rebuild(req: Request) -> Response:
            b = req.json()
            before = _ec_pipeline_snapshot()
            rebuilt = self.store.ec_rebuild(int(b["volume_id"]),
                                            b.get("collection", ""),
                                            b.get("engine"))
            return Response({"rebuilt_shard_ids": rebuilt,
                             "pipeline": _ec_pipeline_health(before),
                             **_ec_device_report(b.get("engine"))})

        @r.route("POST", "/admin/ec/copy")
        def ec_copy(req: Request) -> Response:
            """VolumeEcShardsCopy: pull shard files from source server.
            Each fetched shard is verified block-by-block against the
            `.eci` sidecar it ships with BEFORE anything can mount it —
            a mismatch (rot at the source, bytes mangled on the wire)
            rejects the copy instead of laundering bad bytes into a
            fresh replica."""
            b = req.json()
            vid = int(b["volume_id"])
            collection = b.get("collection", "")
            source = b["source_data_node"]
            base = volume_file_prefix(self.store.locations[0].directory,
                                      collection, vid)
            shard_ids = [int(s) for s in b.get("shard_ids", [])]
            exts = [to_ext(s) for s in shard_ids]
            if b.get("copy_ecx_file", True):
                exts.append(".ecx")
            if b.get("copy_ecj_file", True):
                exts.append(".ecj")
            # the block-crc sidecar travels with the shards so the
            # destination can verify-on-arrival, verify-on-use and
            # scrub them; absence is fine (pre-sidecar volume —
            # backfill can adopt it later)
            exts.append(".eci")
            from ..utils.httpd import http_download

            for ext in exts:
                status = http_download(
                    "GET", f"http://{source}/admin/ec/download?volume_id={vid}"
                           f"&collection={collection}&ext={ext}",
                    base + ext, timeout=3600)
                if status != 200 and ext not in (".ecj", ".eci"):
                    raise HttpError(500, f"copy {ext} from {source}: {status}")
            self._verify_copied_shards(vid, collection, base, shard_ids)
            return Response({})

        @r.route("GET", "/admin/ec/download")
        def ec_download(req: Request) -> Response:
            vid = qint(req.query, "volume_id")
            base = self.store._ec_base(vid, req.query.get("collection", ""))
            path = base + req.query["ext"]
            if not os.path.exists(path):
                raise HttpError(404, f"{path} not found")
            # streamed (VolumeEcShardRead streaming semantics,
            # volume_grpc_erasure_coding.go:284-350)
            return Response(file_path=path)

        @r.route("POST", "/admin/ec/delete")
        def ec_delete(req: Request) -> Response:
            b = req.json()
            self.store.ec_delete_shards(int(b["volume_id"]),
                                        [int(s) for s in b.get("shard_ids", [])],
                                        b.get("collection", ""))
            return Response({})

        @r.route("POST", "/admin/ec/mount")
        def ec_mount(req: Request) -> Response:
            b = req.json()
            self.store.ec_mount(int(b["volume_id"]), b.get("collection", ""))
            return Response({})

        @r.route("POST", "/admin/ec/unmount")
        def ec_unmount(req: Request) -> Response:
            self.store.ec_unmount(int(req.json()["volume_id"]))
            return Response({})

        @r.route("GET", "/admin/ec/shard_read")
        def ec_shard_read(req: Request) -> Response:
            try:
                data = self.store.ec_shard_read(
                    qint(req.query, "volume_id"), qint(req.query, "shard"),
                    qint(req.query, "offset"), qint(req.query, "size"))
            except NeedleNotFoundError as e:
                raise HttpError(404, str(e))
            return Response(raw=data)

        @r.route("POST", "/admin/ec/blob_delete")
        def ec_blob_delete(req: Request) -> Response:
            b = req.json()
            self.store.ec_delete_needle(int(b["volume_id"]), int(b["key"]))
            return Response({})

        @r.route("POST", "/admin/ec/to_volume")
        def ec_to_volume(req: Request) -> Response:
            b = req.json()
            self.store.ec_to_volume(int(b["volume_id"]), b.get("collection", ""))
            return Response({})
