"""Shared event-loop serving dataplane for the HTTP and framed-TCP fronts.

The thread-per-connection servers (utils/httpd.FastHTTPServer, the
framing.FramedServer accept loop) spend the hot read path's budget on
thread spawns and blocking socket writes: every accepted connection
costs a fresh `threading.Thread`, and a slow client reading a streamed
response pins a whole thread for the duration of the transfer.  The
bench trajectory shows the ceiling clearly — the framing/dispatch layer
caps HTTP reads around ~4k rps while the needle store itself does
~930k 4KB ops/s in batched microbenches.

This module replaces that layer with ONE selector-driven reactor per
process, shared by every server front in it:

  - the loop owns accept + readable/writable readiness for every
    connection, parses HTTP/1.1 requests and framed-TCP frames
    non-blockingly, and keeps connections alive across requests
    (keep-alive and pipelining are the default, not one-thread-one-
    connection);
  - parsed requests dispatch onto a SMALL bounded worker pool
    (`-dataplane.workers`) that runs the untouched `Router.dispatch`
    chokepoint — tracing, deadline adoption, admission control and the
    workload recorder all ride exactly the code they always rode;
  - responses flush on the loop via gather writes (`socket.sendmsg`
    over memoryview slices — response bodies are enqueued as the
    handler's own `bytes` objects, never joined or copied) and
    `os.sendfile` for `Response(file_path=...)` streams, with
    partial-write readiness: a slow client costs one outbox entry, not
    a blocked thread;
  - GET/HEAD object reads whose needle the popularity cache already
    holds (volume_server/needle_cache.py) dispatch INLINE on the loop
    — a cache-hit read completes entirely on the loop with zero
    thread handoffs (the one audited, waived exception to the W505
    no-blocking-on-the-loop lint: the probe guarantees a memory hit,
    and a raced invalidation degrades to one bounded 4KB pread).

Loop-side methods are marked `# loop-callback`; the weedlint W505 rule
walks the call graph from those roots and fails the build if anything
classified blocking by the W504 tables (HTTP egress, time.sleep,
timeout-less queue ops, disk helpers) becomes reachable from the loop.

Ops teams get `SeaweedFS_dataplane_*` metrics (connections, workers,
dispatches, aborts); aborted connections (slow-client outbox overflow,
bounded-deadline stop teardown) count into the
`dataplane_conn_aborts` HEALTH_FAMILIES key and journal a rate-limited
`dataplane_conn_abort` event.

Knobs: `weed -dataplane.workers N <role>` (WEED_DATAPLANE_WORKERS),
and WEED_DATAPLANE=threaded to fall back to the thread-per-connection
servers wholesale.
"""

from __future__ import annotations

import io
import os
import selectors
import socket
import threading
import time
from collections import deque
from typing import Callable, Optional

from ..observability.tracer import now as _trace_now

# per-connection bounds, mirroring the threaded servers' guards
MAX_HEADER_BYTES = 1 << 16          # 64KB request line + headers
MAX_HEADERS = 100
MAX_BODY_BYTES = 1 << 30            # buffered request body cap (413 past it)
MAX_OUT_BUFFERED = 64 << 20         # queued response BYTES before a slow
#                                     client is aborted (sendfile regions
#                                     stream from disk and don't count)
FLUSH_THRESHOLD = 1 << 20           # past this, enqueue() drains the
#                                     socket inline so big responses
#                                     stream instead of accumulating
SLOW_CLIENT_GRACE_S = 30.0          # a backpressured writer waits this
#                                     long for the client to drain the
#                                     outbox before the conn is aborted
RECV_CHUNK = 1 << 16

# absolute ceiling on dispatch workers (core + overflow): far above any
# steady state, just a runaway backstop — overflow workers retire after
# ~2s idle
HARD_WORKER_CAP = 128

# requests on these paths ride the priority dispatch lane: control-
# plane liveness (heartbeats!) and operator visibility must never queue
# behind a burst of bulk object writes (same prefix philosophy as
# utils/admission.DEFAULT_EXEMPT_PREFIXES)
OPS_PRIORITY_PREFIXES = (
    "/metrics", "/debug", "/cluster", "/ec/scrub", "/admin",
    "/heartbeat", "/dir/status", "/status", "/stats", "/raft",
)

_EVENT_MIN_INTERVAL_S = 5.0


def _metrics():
    from ..stats import dataplane_metrics

    return dataplane_metrics()


class _FileSend:
    """One sendfile region queued on a connection's outbox."""

    __slots__ = ("fd", "offset", "remaining")

    def __init__(self, fd: int, offset: int, length: int):
        self.fd = fd
        self.offset = offset
        self.remaining = length

    def close(self) -> None:
        try:
            os.close(self.fd)
        except OSError:
            pass


class _ConnWriter:
    """The `wfile` handed to Router._send on the worker pool: every
    write enqueues the caller's own bytes object on the connection's
    outbox (no join, no copy) and the loop flushes it when the socket
    is writable."""

    __slots__ = ("conn",)

    def __init__(self, conn: "_Conn"):
        self.conn = conn

    def write(self, data) -> int:
        self.conn.enqueue(data)
        return len(data)

    def flush(self) -> None:
        pass


class _LoopHandler:
    """Per-request handler exposing exactly the BaseHTTPRequestHandler
    surface Router.dispatch uses (same contract as httpd._FastHandler),
    with the response side backed by the connection outbox and
    `sendfile` support for Response(file_path=...) streams."""

    __slots__ = ("server", "rfile", "wfile", "client_address", "command",
                 "path", "headers", "close_connection", "_out", "_conn",
                 "queue_t0", "queue_wait_s")

    def __init__(self, server, conn: "_Conn", body: bytes, peer):
        self.server = server
        self._conn = conn
        # dispatch-queue wait: stamped by the worker-handoff closure
        # (loop-enqueue at queue_t0 -> worker pickup, on the tracer's
        # clock); the inline fast path leaves it None / 0.  Router.dispatch
        # feeds it to the resource ledger and the http.queue_wait span.
        self.queue_t0 = None
        self.queue_wait_s = 0.0
        self.rfile = io.BytesIO(body)
        self.wfile = _ConnWriter(conn)
        self.client_address = peer
        self.command = ""
        self.path = ""
        self.headers = None
        self.close_connection = True
        self._out: list = []

    def send_response(self, status: int, message: str = "") -> None:
        from .httpd import _REASONS, _http_date

        self._out = [b"HTTP/1.1 %d %s\r\nDate: %s\r\n"
                     % (status,
                        (message or _REASONS.get(status, "OK")).encode(),
                        _http_date().encode())]

    def send_header(self, key: str, value) -> None:
        self._out.append(f"{key}: {value}\r\n".encode())
        if key.lower() == "connection" and str(value).lower() == "close":
            self.close_connection = True

    def end_headers(self) -> None:
        self._out.append(b"\r\n")
        self._conn.enqueue(b"".join(self._out))
        self._out = []

    def sendfile(self, path: str, offset: int, length: int) -> bool:
        """Queue a zero-copy file region for the loop to os.sendfile.
        Returns False when the platform/file cannot sendfile so the
        caller falls back to chunked reads through wfile."""
        if not _HAS_SENDFILE:
            return False
        try:
            fd = os.open(path, os.O_RDONLY)
        except OSError:
            return False
        self._conn.enqueue_file(_FileSend(fd, offset, length))
        return True


_HAS_SENDFILE = hasattr(os, "sendfile")


class _Conn:
    """One reactor-owned connection (HTTP or framed-TCP).

    Loop-thread-only state (parse buffers, selector registration) is
    unlocked by design; the outbox and lifecycle flags are shared with
    the worker pool and ride `_lock`."""

    __slots__ = ("reactor", "listener", "sock", "fileno", "peer",
                 "inbuf", "body_needed", "pending", "want_events",
                 "_lock", "outq", "out_bytes", "busy", "closing",
                 "aborted", "flushing")

    def __init__(self, reactor: "Reactor", listener: "_Listener",
                 sock: socket.socket, peer):
        self.reactor = reactor
        self.listener = listener
        self.sock = sock
        self.fileno = sock.fileno()
        self.peer = peer
        # loop-thread-only parse state
        self.inbuf = bytearray()
        self.body_needed = -1      # >=0: header parsed, awaiting body
        self.pending = None        # parsed head awaiting its body
        self.want_events = selectors.EVENT_READ
        # shared with the worker pool (a Condition: flushers notify
        # when the outbox shrinks so a backpressured writer can resume)
        self._lock = threading.Condition()
        self.outq: list = []       # guarded-by: _lock
        self.out_bytes = 0         # guarded-by: _lock
        self.busy = False          # guarded-by: _lock
        self.closing = False       # guarded-by: _lock
        self.aborted = False       # guarded-by: _lock
        self.flushing = False      # single-flusher claim  # guarded-by: _lock

    # --- worker-side API ---------------------------------------------------
    def enqueue(self, data) -> None:
        """Queue response bytes; called from worker threads (via
        Router._send) or from the loop's inline fast path.  Small
        responses accumulate and flush once at request_done; past
        FLUSH_THRESHOLD the enqueuing thread drains the socket AS IT
        WRITES, so a multi-hundred-MB response streams through a
        bounded outbox — only a client that stops reading (kernel
        buffer full, flush cannot drain) ever hits the overflow abort."""
        if not len(data):
            # empty writes (302/204 bodies) must never reach the
            # outbox: an all-empty sendmsg batch returns 0 sent, which
            # the consume loop could not distinguish from "no
            # progress" — the flusher would spin on it forever
            return
        with self._lock:
            if self.aborted:
                return
            self.outq.append(data)
            self.out_bytes += len(data)
            big = self.out_bytes >= FLUSH_THRESHOLD
        if not big:
            return
        self.reactor.flush_conn(self)
        with self._lock:
            over = not self.aborted and self.out_bytes > MAX_OUT_BUFFERED
        if not over:
            return
        if self.reactor.on_loop_thread():
            # the loop must never park.  Crossing the cap here means a
            # pipelining client amassed 64MB+ of unread fast-path
            # responses — the slow-client condition, aborted at once
            # (no grace: the loop cannot wait for a drain)
            with self._lock:
                self.aborted = True
            self.reactor.note_abort("slow_client")
            self.reactor.mark_dirty(self)
            return
        # worker-side BACKPRESSURE — the reactor's equivalent of the
        # threaded server blocking in sendall: hand the socket to the
        # loop (EVENT_WRITE) and wait for the client to drain; only a
        # client that stops reading altogether is aborted
        self.reactor.mark_dirty(self)
        deadline = time.monotonic() + SLOW_CLIENT_GRACE_S
        overflow = False
        with self._lock:
            while not self.aborted and \
                    self.out_bytes > MAX_OUT_BUFFERED:
                if time.monotonic() >= deadline:
                    self.aborted = True
                    overflow = True
                    break
                self._lock.wait(timeout=0.5)
        if overflow:
            self.reactor.note_abort("slow_client")
            self.reactor.mark_dirty(self)

    def enqueue_file(self, fs: _FileSend) -> None:
        with self._lock:
            if self.aborted:
                fs.close()
                return
            self.outq.append(fs)

    def request_done(self, close: bool) -> None:
        """The worker finished one dispatch: flush the response from
        THIS thread (the common whole-response-fits send needs no loop
        round trip at all), then wake the loop only when it has work —
        leftover output to watch for writability, buffered pipelined
        input to parse, or a close to run."""
        with self._lock:
            self.busy = False
            if close:
                self.closing = True
        self.reactor.flush_conn(self)
        with self._lock:
            need_loop = (bool(self.outq) or self.closing
                         or self.aborted)
        # len() on the loop-owned buffer is a GIL-atomic heuristic:
        # a pipelined request that lands AFTER this check re-fires
        # EVENT_READ on its own, so a stale 0 can never strand one
        if need_loop or len(self.inbuf) > 0:
            self.reactor.mark_dirty(self)

    # --- loop-side helpers -------------------------------------------------
    def drain_out(self) -> None:  # loop-callback
        """Release queued output without sending (abort path).  The
        sendfile fds close UNDER the lock — a flusher's send iteration
        holds the same lock, so no stale fd can be mid-sendfile."""
        with self._lock:
            items, self.outq = self.outq, []
            self.out_bytes = 0
            for item in items:
                if isinstance(item, _FileSend):
                    item.close()
            self._lock.notify_all()  # wake backpressured writers


class _Listener:
    """One listening socket registered on the reactor."""

    __slots__ = ("sock", "kind", "router", "handler", "name", "owner",
                 "conns")

    def __init__(self, sock: socket.socket, kind: str, owner,
                 router=None, handler=None, name: str = ""):
        self.sock = sock
        self.kind = kind              # "http" | "framed"
        self.owner = owner            # facade server (_stopping flag)
        self.router = router
        self.handler = handler        # framed: fn(op, key, body) -> bytes
        self.name = name
        self.conns: set = set()       # loop-thread-only


class Reactor:
    """The process-wide selector loop + bounded dispatch worker pool.

    The pool has two lanes and an elastic overflow: operator/control
    requests (heartbeats, /metrics, /cluster, admin) take a PRIORITY
    lane so a burst of bulk object writes can never queue a heartbeat
    into the master's janitor window (a load problem must not
    masquerade as a topology problem — the admission controller's
    rule, applied to scheduling).  When every worker is busy (e.g.
    long-poll subscribe handlers legitimately parked in cond.wait) and
    work is waiting, overflow workers spawn up to a hard cap and
    retire after idling — steady state stays small, blocking handlers
    cannot deadlock the plane."""

    def __init__(self, workers: int = 0):
        self.workers = int(workers) if workers and int(workers) > 0 \
            else max(4, min(16, (os.cpu_count() or 4) * 2))
        self._sel = selectors.DefaultSelector()   # loop-thread-only
        self._lock = threading.Lock()
        # loop-thread-only: mutated exclusively inside _apply_pending
        # (listener add/remove requests travel through _pending)
        self._listeners: dict[int, _Listener] = {}
        self._pending: list = []    # add/remove ops for the loop  # guarded-by: _lock
        self._dirty: set = set()    # conns needing interest recompute  # guarded-by: _lock
        # two-lane dispatch queue + worker accounting, all under _qcond
        self._qcond = threading.Condition()
        self._q_ops: list = []      # control-plane lane  # guarded-by: _qcond
        self._q_data: list = []     # object/data lane  # guarded-by: _qcond
        self._idle = 0              # workers parked in wait  # guarded-by: _qcond
        self._alive = 0             # workers running (core+overflow)  # guarded-by: _qcond
        self._wake_r, self._wake_w = socket.socketpair()
        self._wake_r.setblocking(False)
        self._started = False       # guarded-by: _lock
        self._last_abort_event = 0.0  # guarded-by: _lock
        self._threads: list[threading.Thread] = []  # guarded-by: _lock
        # hook-style handoff: written once in start() before the loop
        # thread runs, read lock-free by on_loop_thread()
        self._loop_thread: Optional[threading.Thread] = None
        # --- loop saturation telemetry (the resource-ledger plane) ---
        # pre-select tick stamp: the watchdog reads it to detect a loop
        # blocked mid-iteration (a torn read of a float is impossible
        # in CPython; staleness of one tick is the measurement)
        self._tick_ts = time.monotonic()
        # the inline fast-path request currently holding the loop
        # (path str), so a watchdog-detected stall can NAME the route
        self._loop_busy: Optional[str] = None
        # per-iteration loop busy time samples: (monotonic ts, busy_s),
        # appended by the loop, read by loop_lag_stats()
        self._lag_samples: deque = deque(maxlen=512)  # guarded-by: _lock
        self._last_stall_note = 0.0   # watchdog fallback rate limit
        # servers wire their RequestLedger.note_stall here so a stall
        # is recorded with route + trace; None = count-only fallback
        self.stall_hook = None

    # --- lifecycle ---------------------------------------------------------
    def start(self) -> "Reactor":
        with self._lock:
            if self._started:
                return self
            self._started = True
            t = threading.Thread(target=self._run, daemon=True,
                                 name="dataplane-loop")
            self._loop_thread = t
            self._threads.append(t)
            # saturation watchdog: pages (via the ledger stall hook or
            # the loop_stalls counter) when the LOOP ITSELF is blocked
            # — the loop cannot report its own hang
            self._threads.append(threading.Thread(
                target=self._watch, daemon=True,
                name="dataplane-watchdog"))
            for i in range(self.workers):
                w = threading.Thread(target=self._work, daemon=True,
                                     name=f"dataplane-worker-{i}")
                self._threads.append(w)
            threads = list(self._threads)
        with self._qcond:
            self._alive += self.workers
        m = _metrics()
        m.workers.set(self.workers)
        for t in threads:
            t.start()
        return self

    def on_loop_thread(self) -> bool:
        return threading.current_thread() is self._loop_thread

    def wake(self) -> None:
        try:
            self._wake_w.send(b"\x00")
        except OSError:
            pass

    def mark_dirty(self, conn: _Conn) -> None:
        with self._lock:
            self._dirty.add(conn)
        self.wake()

    def note_abort(self, reason: str) -> None:
        """Count + journal (rate-limited) one aborted connection."""
        _metrics().conn_aborts.inc(reason)
        now = time.monotonic()
        with self._lock:
            emit = now - self._last_abort_event >= _EVENT_MIN_INTERVAL_S
            if emit:
                self._last_abort_event = now
        if emit:
            from ..observability import events as _events

            try:
                _events.emit("dataplane_conn_abort", reason=reason)
            except Exception:
                pass

    # --- listener registration --------------------------------------------
    def add_http_listener(self, sock: socket.socket, router, owner) -> None:
        sock.setblocking(False)
        lst = _Listener(sock, "http", owner, router=router,
                        name=router.name)
        with self._lock:
            self._pending.append(("add", lst))
        self.wake()

    def add_framed_listener(self, sock: socket.socket, handler,
                            name: str, owner) -> None:
        sock.setblocking(False)
        lst = _Listener(sock, "framed", owner, handler=handler, name=name)
        with self._lock:
            self._pending.append(("add", lst))
        self.wake()

    def remove_listener(self, owner, deadline_s: float = 1.5) -> None:
        """Stop accepting for `owner` and abort its connections.  Blocks
        (bounded) until the loop acknowledged the teardown — the caller
        can rebind the port the moment this returns."""
        done = threading.Event()
        with self._lock:
            self._pending.append(("remove", owner, done))
        self.wake()
        done.wait(timeout=max(deadline_s, 0.1))

    # --- worker pool -------------------------------------------------------
    def submit(self, fn: Callable[[], None], ops: bool = False) -> None:
        """Queue one dispatch; never blocks.  `ops` requests take the
        priority lane.  If no worker is idle, an overflow worker spawns
        (bounded by HARD_WORKER_CAP) so handlers that legitimately park
        — long-poll subscribes, slow disks — cannot starve the plane."""
        spawn = False
        with self._qcond:
            (self._q_ops if ops else self._q_data).append(fn)
            if self._idle == 0 and self._alive < HARD_WORKER_CAP:
                self._alive += 1
                spawn = True
            self._qcond.notify()
        if spawn:
            threading.Thread(target=self._work, args=(True,),
                             daemon=True,
                             name="dataplane-worker-extra").start()

    def _work(self, extra: bool = False) -> None:  # thread-entry
        while True:
            with self._qcond:
                self._idle += 1
                try:
                    while not self._q_ops and not self._q_data:
                        if not self._qcond.wait(timeout=2.0) and extra \
                                and not self._q_ops \
                                and not self._q_data:
                            self._alive -= 1
                            return  # overflow worker idled out
                finally:
                    self._idle -= 1
                fn = (self._q_ops.pop(0) if self._q_ops
                      else self._q_data.pop(0))
            try:
                fn()
            except Exception:
                pass  # dispatch wrappers guard themselves; never die

    # --- the loop ----------------------------------------------------------
    def _run(self) -> None:  # thread-entry
        self._sel.register(self._wake_r, selectors.EVENT_READ, "wake")
        # brand the thread object so observability/ledger can answer
        # "am I ON the loop?" with one attribute read, no singleton
        threading.current_thread()._weed_loop = True
        while True:
            self._apply_pending()
            # sentinel-timer drift: the tick stamp freshens every
            # iteration; the watchdog reads (now - tick - select
            # timeout) as the loop's current lag while blocked
            self._tick_ts = time.monotonic()  # weedlint: disable=W502 single writer (the loop thread); the watchdog only READS this float, and a stale read just delays one lag check by a tick
            try:
                events = self._sel.select(timeout=1.0)
            except OSError:
                continue
            t_busy0 = time.monotonic()
            for key, mask in events:
                data = key.data
                try:
                    if data == "wake":
                        try:
                            while self._wake_r.recv(4096):
                                pass
                        except (BlockingIOError, OSError):
                            pass
                    elif isinstance(data, _Listener):
                        self._on_accept(data)
                    elif isinstance(data, _Conn):
                        if mask & selectors.EVENT_READ:
                            self._on_readable(data)
                        if mask & selectors.EVENT_WRITE:
                            self._on_writable(data)
                except Exception:
                    # one connection's parse/flush bug must never take
                    # the whole dataplane down with it
                    if isinstance(data, _Conn):
                        try:
                            self._close_conn(data,
                                             abort_reason="loop_error")
                        except Exception:
                            pass
            busy_s = time.monotonic() - t_busy0
            if busy_s >= 0.001:
                # loop-lag sample: how long THIS iteration held the
                # loop (inline dispatches included) — every connection
                # waited that long.  Sub-ms iterations are free and
                # not worth a lock + histogram touch.
                with self._lock:
                    self._lag_samples.append((t_busy0, busy_s))
                try:
                    _metrics().loop_lag.observe(busy_s)
                except Exception:
                    pass

    def loop_lag_stats(self, window_s: float = 60.0) -> dict:
        """Loop saturation snapshot for /debug/ledger and the shipped
        ledger snapshots: lag percentiles over the recent window plus
        dispatch-queue depth and worker-pool occupancy."""
        now = time.monotonic()
        with self._lock:
            samples = sorted(b for (t, b) in self._lag_samples
                             if now - t <= window_s)
        with self._qcond:
            qdepth = len(self._q_ops) + len(self._q_data)
            alive, idle = self._alive, self._idle

        def pct(p: float) -> float:
            if not samples:
                return 0.0
            return samples[min(int(p * len(samples)),
                               len(samples) - 1)]

        return {
            "lag_p50_ms": round(pct(0.50) * 1000.0, 2),
            "lag_p99_ms": round(pct(0.99) * 1000.0, 2),
            "lag_max_ms": round(samples[-1] * 1000.0, 2)
            if samples else 0.0,
            "samples": len(samples),
            "queue_depth": qdepth,
            "workers": alive,
            "workers_busy": max(alive - idle, 0),
        }

    def _watch(self) -> None:  # thread-entry
        """Saturation watchdog: refreshes the queue-depth / occupancy
        gauges and detects a BLOCKED loop from outside it — the tick
        stamp going stale past the select timeout plus the stall
        threshold means nothing (accepts, parses, flushes) is moving."""
        from ..observability.ledger import LOOP_STALL_THRESHOLD_S

        while True:
            time.sleep(0.25)
            try:
                m = _metrics()
                with self._qcond:
                    qo, qd = len(self._q_ops), len(self._q_data)
                    alive, idle = self._alive, self._idle
                m.queue_depth.set("ops", float(qo))
                m.queue_depth.set("data", float(qd))
                m.workers_busy.set(float(max(alive - idle, 0)))
                # 1.0 = the select timeout: an IDLE loop's stamp is
                # legitimately that old
                lag = time.monotonic() - self._tick_ts - 1.0
                if lag < LOOP_STALL_THRESHOLD_S:
                    continue
                route = self._loop_busy or "(loop)"
                hook = self.stall_hook
                if hook is not None:
                    # the ledger records route + exemplar, counts the
                    # loop_stalls family, and rate-limits repeats
                    hook(route, lag, "")
                    continue
                now = time.monotonic()
                if now - self._last_stall_note >= _EVENT_MIN_INTERVAL_S:
                    self._last_stall_note = now  # weedlint: disable=W502 only the watchdog thread ever touches this rate-limit stamp
                    m.loop_stalls.inc()
            except Exception:
                pass  # the watchdog must never die

    def _apply_pending(self) -> None:  # loop-callback
        with self._lock:
            ops, self._pending = self._pending, []
            dirty, self._dirty = self._dirty, set()
        for op in ops:
            if op[0] == "add":
                lst = op[1]
                self._listeners[lst.sock.fileno()] = lst  # weedlint: disable=W502 loop-thread-only: _apply_pending runs exclusively on the reactor loop thread
                try:
                    self._sel.register(lst.sock, selectors.EVENT_READ, lst)
                except (OSError, ValueError, KeyError):
                    pass
            else:  # ("remove", owner, done)
                _kw, owner, done = op
                for fno, lst in list(self._listeners.items()):
                    if lst.owner is not owner:
                        continue
                    del self._listeners[fno]
                    try:
                        self._sel.unregister(lst.sock)
                    except (OSError, ValueError, KeyError):
                        pass
                    try:
                        lst.sock.close()
                    except OSError:
                        pass
                    for conn in list(lst.conns):
                        self._close_conn(conn, abort_reason="stop")
                done.set()
        for conn in dirty:
            try:
                self._refresh_conn(conn)
            except Exception:
                try:
                    self._close_conn(conn, abort_reason="loop_error")
                except Exception:
                    pass

    def _refresh_conn(self, conn: _Conn) -> None:  # loop-callback
        """Recompute a connection's state after worker activity:
        flush, continue parsing pipelined input, close when drained."""
        if conn not in conn.listener.conns:
            return  # already torn down
        with conn._lock:
            aborted = conn.aborted
        if aborted:
            self._close_conn(conn)
            return
        self.flush_conn(conn)
        self._advance(conn)

    def _on_accept(self, lst: _Listener) -> None:  # loop-callback
        for _ in range(64):  # bounded accept burst per readiness
            try:
                sock, peer = lst.sock.accept()
            except (BlockingIOError, OSError):
                return
            try:
                sock.setblocking(False)
                sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            except OSError:
                sock.close()
                continue
            if lst.kind == "framed" and lst.owner is not None:
                ok_fn = getattr(lst.owner, "_whitelist_ok", None)
                if ok_fn is not None and not ok_fn(peer[0]):
                    sock.close()
                    continue
            conn = _Conn(self, lst, sock, peer)
            lst.conns.add(conn)
            _metrics().connections.add(1)
            try:
                self._sel.register(sock, selectors.EVENT_READ, conn)
            except (OSError, ValueError, KeyError):
                lst.conns.discard(conn)
                _metrics().connections.add(-1)
                sock.close()

    def _on_readable(self, conn: _Conn) -> None:  # loop-callback
        try:
            piece = conn.sock.recv(RECV_CHUNK)
        except (BlockingIOError, InterruptedError):
            return
        except OSError:
            self._close_conn(conn)
            return
        if not piece:
            # peer half-closed: finish what is in flight, then close
            with conn._lock:
                conn.closing = True
                busy = conn.busy
            if not busy and not conn.inbuf:
                self._close_conn(conn)
            else:
                self._refresh_conn(conn)
            return
        conn.inbuf += piece
        if len(conn.inbuf) > MAX_BODY_BYTES + MAX_HEADER_BYTES:
            # a client streaming past every framing bound while a
            # request is in flight is a memory-exhaustion vector
            self._close_conn(conn, abort_reason="overflow")
            return
        self._advance(conn)

    def _advance(self, conn: _Conn) -> None:  # loop-callback
        """Parse as much buffered input as the one-request-in-flight
        discipline allows, then recompute selector interest."""
        while True:
            with conn._lock:
                if conn.busy or conn.closing or conn.aborted:
                    break
            if conn.listener.kind == "http":
                if not self._parse_http(conn):
                    break
            else:
                if not self._parse_frame(conn):
                    break
        self._update_interest(conn)

    # --- HTTP parsing ------------------------------------------------------
    def _parse_http(self, conn: _Conn) -> bool:  # loop-callback
        """One parse step; True when a request was dispatched (the
        caller loops for pipelining)."""
        if conn.body_needed < 0:
            end = conn.inbuf.find(b"\r\n\r\n")
            if end < 0:
                if len(conn.inbuf) > MAX_HEADER_BYTES:
                    # still inside the request LINE -> 414; past it,
                    # an unbounded header block -> 431 (both mirror
                    # the threaded server's guards)
                    if b"\r\n" not in conn.inbuf:
                        self._reject_http(conn, 414, "URI Too Long")
                    else:
                        self._reject_http(
                            conn, 431,
                            "Request Header Fields Too Large")
                return False
            head = bytes(conn.inbuf[:end])
            del conn.inbuf[:end + 4]
            if not self._parse_http_head(conn, head):
                return False
            if conn.body_needed == 0:
                conn.body_needed = -1
                return self._dispatch_http(conn, b"")
            return True  # fall through: body may already be buffered
        if len(conn.inbuf) < conn.body_needed:
            # (oversized Content-Length already answered 413 at head
            # parse — body_needed is always within MAX_BODY_BYTES here)
            return False
        body = bytes(conn.inbuf[:conn.body_needed])
        del conn.inbuf[:conn.body_needed]
        conn.body_needed = -1
        return self._dispatch_http(conn, body)

    def _parse_http_head(self, conn: _Conn, head: bytes) -> bool:  # loop-callback
        from .httpd import CIHeaders

        lines = head.split(b"\r\n")
        try:
            method, _, rest = lines[0].partition(b" ")
            target, _, version = rest.rpartition(b" ")
            command = method.decode("ascii")
            path = target.decode("iso-8859-1")
        except (UnicodeDecodeError, ValueError):
            self._reject_http(conn, 400, "Bad Request")
            return False
        if not command or not path:
            self._reject_http(conn, 400, "Bad Request")
            return False
        if len(lines) - 1 > MAX_HEADERS:
            self._reject_http(conn, 431, "Request Header Fields Too Large")
            return False
        pairs = []
        for hl in lines[1:]:
            if not hl:
                continue
            k, _, v = hl.partition(b":")
            pairs.append((k.decode("iso-8859-1"),
                          v.strip().decode("iso-8859-1")))
        headers = CIHeaders(pairs)
        if "chunked" in (headers.get("Transfer-Encoding") or "").lower():
            # Request.body only frames Content-Length bodies (same
            # refusal as the threaded server)
            self._reject_http(conn, 501, "Not Implemented")
            return False
        try:
            clen = int(headers.get("Content-Length") or 0)
        except (TypeError, ValueError):
            self._reject_http(conn, 400, "Bad Request")
            return False
        if clen < 0:
            # a negative length would read as the awaiting-headers
            # sentinel and silently orphan the request (never
            # dispatched, never answered): malformed framing is 400
            self._reject_http(conn, 400, "Bad Request")
            return False
        if clen > MAX_BODY_BYTES:
            self._reject_http(conn, 413, "Payload Too Large")
            return False
        conn_hdr = (headers.get("Connection") or "").lower()
        close = (conn_hdr == "close"
                 or (version == b"HTTP/1.0" and conn_hdr != "keep-alive"))
        if (headers.get("Expect") or "").lower() == "100-continue":
            conn.enqueue(b"HTTP/1.1 100 Continue\r\n\r\n")
        conn.pending = (command, path, headers, close)
        conn.body_needed = clen
        return True

    def _reject_http(self, conn: _Conn, status: int,
                     reason: str) -> None:  # loop-callback
        conn.enqueue(("HTTP/1.1 %d %s\r\nContent-Length: 0\r\n"
                      "Connection: close\r\n\r\n"
                      % (status, reason)).encode())
        with conn._lock:
            conn.closing = True
        self.flush_conn(conn)
        self._update_interest(conn)

    def _dispatch_http(self, conn: _Conn, body: bytes) -> bool:  # loop-callback
        command, path, headers, close = conn.pending
        conn.pending = None
        lst = conn.listener
        h = _LoopHandler(lst.owner, conn, body, conn.peer)
        h.command = command
        h.path = path
        h.headers = headers
        h.close_connection = close
        router = lst.router
        with conn._lock:
            conn.busy = True
        probe = getattr(router, "loop_fast_probe", None)
        if probe is not None and command in ("GET", "HEAD") \
                and not body and "Range" not in headers \
                and probe(command, path):
            # cache-probed inline fast path: the needle cache holds this
            # object, so the whole dispatch (trace/deadline/admission/
            # reqlog chokepoint included) completes on the loop with no
            # thread handoff.  Lexically Router.dispatch reaches disk
            # helpers, hence the audited waiver: a raced invalidation
            # degrades to ONE bounded needle pread, never unbounded IO.
            self._loop_busy = path  # weedlint: disable=W502 loop-thread-only write; the watchdog's racy read is the point
            from . import faultinject as fi

            if fi._points:
                # the loop-stall drill's injection site: a delay here
                # blocks the WHOLE dataplane, exactly like a handler
                # that sneaks blocking IO onto the inline fast path
                fi.hit("loop.block")  # weedlint: loop-io fault-injection drill point, inert outside tests
            try:
                router.dispatch(h, command)  # weedlint: loop-io cache-probed fast path: needle cache holds the object; a raced invalidation costs one bounded pread
            except Exception:
                with conn._lock:
                    conn.closing = True
            self._loop_busy = None  # weedlint: disable=W502 loop-thread-only write; the watchdog's racy read is the point
            _metrics().fast_dispatches.inc()
            conn.request_done(close=h.close_connection)
            return True

        t_submit = _trace_now()

        def run():
            # queue wait = loop enqueue -> worker pickup; the ledger
            # reads it off the handler at settle
            h.queue_t0 = t_submit
            h.queue_wait_s = _trace_now() - t_submit
            try:
                router.dispatch(h, command)
            except Exception:
                with conn._lock:
                    conn.closing = True
            conn.request_done(close=h.close_connection)

        _metrics().pool_dispatches.inc()
        self.submit(run, ops=path.startswith(OPS_PRIORITY_PREFIXES))
        return True

    # --- framed-TCP parsing ------------------------------------------------
    def _parse_frame(self, conn: _Conn) -> bool:  # loop-callback
        from .framing import U16, U32

        buf = conn.inbuf
        if len(buf) < 3:
            return False
        key_len = U16.unpack_from(buf, 1)[0]
        if len(buf) < 3 + key_len + 4:
            return False
        body_len = U32.unpack_from(buf, 3 + key_len)[0]
        total = 3 + key_len + 4 + body_len
        if len(buf) < total:
            return False
        op = bytes(buf[:1])
        try:
            key = bytes(buf[3:3 + key_len]).decode()
        except UnicodeDecodeError:
            self._close_conn(conn)
            return False
        body = bytes(buf[3 + key_len + 4:total])
        del conn.inbuf[:total]
        lst = conn.listener
        with conn._lock:
            conn.busy = True

        t_submit = _trace_now()

        def run():
            from .framing import serve_frame

            frame = serve_frame(lst.handler, lst.name, op, key, body,
                                conn.peer[0],
                                ledger=getattr(lst.owner, "ledger",
                                               None),
                                queue_wait_s=_trace_now()
                                - t_submit)
            conn.enqueue(frame)
            conn.request_done(close=False)

        _metrics().pool_dispatches.inc()
        self.submit(run)
        return True

    # --- writeback ---------------------------------------------------------
    def flush_conn(self, conn: _Conn) -> None:
        """Send as much queued output as the socket accepts right now.
        Bytes items go out in one gather write (sendmsg over memoryview
        slices); _FileSend items stream via os.sendfile.  Callable from
        ANY thread — the dispatching worker flushes its own response so
        the loop only gets involved on partial writes; the `flushing`
        claim keeps exactly one sender per socket so racing flushers
        cannot interleave bytes."""
        with conn._lock:
            if conn.flushing or conn.aborted:
                return
            conn.flushing = True
        try:
            self._flush_locked_out(conn)
        finally:
            with conn._lock:
                conn.flushing = False

    def _flush_locked_out(self, conn: _Conn) -> None:
        # each iteration — including the send syscall — runs under
        # conn._lock: _close_conn tears the socket and any queued
        # sendfile fds down under the SAME lock after marking the conn
        # aborted, so a flusher can never race a close into a stale-fd
        # write (fd reuse would stream bytes into the wrong client).
        # The sends are non-blocking syscalls, so the lock is held for
        # microseconds, never for a stalled peer.
        while True:
            with conn._lock:
                if not conn.outq or conn.aborted:
                    return
                head = conn.outq[0]
                if isinstance(head, _FileSend):
                    fs: _FileSend = head
                    if fs.remaining <= 0:
                        fs.close()
                        conn.outq.pop(0)
                        continue
                    try:
                        sent = os.sendfile(conn.fileno, fs.fd,
                                           fs.offset,
                                           min(fs.remaining, 1 << 20))
                    except (BlockingIOError, InterruptedError):
                        return
                    except OSError:
                        conn.aborted = True
                        conn._lock.notify_all()
                        self.mark_dirty(conn)
                        return
                    if sent == 0:
                        fs.remaining = 0
                        continue
                    fs.offset += sent
                    fs.remaining -= sent
                    continue
                batch = []
                for item in conn.outq:
                    if isinstance(item, _FileSend):
                        break
                    batch.append(memoryview(item)
                                 if not isinstance(item, memoryview)
                                 else item)
                    if len(batch) >= 32:
                        break
                try:
                    sent = conn.sock.sendmsg(batch)
                except (BlockingIOError, InterruptedError):
                    return
                except OSError:
                    conn.aborted = True
                    conn._lock.notify_all()
                    self.mark_dirty(conn)
                    return
                conn.out_bytes -= sent
                # pop fully-sent items; zero-length leftovers pop
                # unconditionally (they represent no bytes and would
                # otherwise wedge the batch head at sent == 0)
                while conn.outq and not isinstance(conn.outq[0],
                                                   _FileSend):
                    n = len(conn.outq[0])
                    if n == 0:
                        conn.outq.pop(0)
                    elif sent >= n:
                        conn.outq.pop(0)
                        sent -= n
                    elif sent > 0:
                        conn.outq[0] = memoryview(conn.outq[0])[sent:]
                        sent = 0
                    else:
                        break
                conn._lock.notify_all()  # backpressured writers resume


    def _update_interest(self, conn: _Conn) -> None:  # loop-callback
        with conn._lock:
            have_out = bool(conn.outq)
            closing = conn.closing
            busy = conn.busy
            aborted = conn.aborted
        if aborted:
            self._close_conn(conn)
            return
        if closing and not have_out and not busy:
            self._close_conn(conn)
            return
        events = selectors.EVENT_READ | (selectors.EVENT_WRITE
                                         if have_out else 0)
        if events != conn.want_events:
            conn.want_events = events
            try:
                self._sel.modify(conn.sock, events, conn)
            except (OSError, ValueError, KeyError):
                self._close_conn(conn)

    def _on_writable(self, conn: _Conn) -> None:  # loop-callback
        self.flush_conn(conn)
        self._update_interest(conn)

    def _close_conn(self, conn: _Conn,
                    abort_reason: str = "") -> None:  # loop-callback
        if conn not in conn.listener.conns:
            return
        conn.listener.conns.discard(conn)
        _metrics().connections.add(-1)
        with conn._lock:
            had_work = bool(conn.outq) or conn.busy
            conn.aborted = True
        conn.drain_out()
        if abort_reason and had_work:
            self.note_abort(abort_reason)
        try:
            self._sel.unregister(conn.sock)
        except (OSError, ValueError, KeyError):
            pass
        # socket teardown under conn._lock: a flusher's send iteration
        # holds the same lock, so the fd cannot be closed (and reused)
        # out from under an in-flight sendfile/sendmsg.  Best-effort
        # graceful close inside: half-close, then drain what already
        # reached the kernel so the close cannot RST away a just-
        # flushed error response (bounded, non-blocking).
        with conn._lock:
            try:
                conn.sock.shutdown(socket.SHUT_WR)
                for _ in range(64):
                    if not conn.sock.recv(RECV_CHUNK):
                        break
            except (BlockingIOError, OSError):
                pass
            try:
                conn.sock.close()
            except OSError:
                pass


# --- process-global reactor --------------------------------------------------

_reactor: Optional[Reactor] = None
_reactor_lock = threading.Lock()
_configured_workers = 0


def configure(workers: Optional[int] = None) -> None:
    """Apply the -dataplane.workers knob; takes effect at first use
    (the pool is sized once per process, like the tracer ring)."""
    global _configured_workers
    if workers is not None and int(workers) > 0:
        with _reactor_lock:
            _configured_workers = int(workers)


def get_reactor() -> Reactor:
    global _reactor
    with _reactor_lock:
        if _reactor is None:
            workers = _configured_workers \
                or int(os.environ.get("WEED_DATAPLANE_WORKERS", "0") or 0)
            _reactor = Reactor(workers=workers)
    return _reactor.start()


def reactor_enabled() -> bool:
    """WEED_DATAPLANE=threaded falls the whole process back to the
    thread-per-connection servers (the pre-reactor dataplane)."""
    return os.environ.get("WEED_DATAPLANE", "reactor") != "threaded"


class ReactorHTTPServer:
    """serve() facade over one HTTP listener on the shared reactor.
    Exposes the surface the rest of the codebase touches:
    server_address, _stopping, serve_forever(), shutdown(),
    server_close() — stop_server() works unchanged."""

    def __init__(self, addr, router):
        self.router = router
        self._stopping = False
        self._done = threading.Event()
        self._sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._sock.bind(addr)
        self._sock.listen(512)
        self.server_address = self._sock.getsockname()
        self.socket = self._sock
        self._reactor = get_reactor()
        self._reactor.add_http_listener(self._sock, router, self)

    def serve_forever(self) -> None:
        # the reactor already serves; this blocks for compatibility
        # with callers that dedicate a thread to it
        self._done.wait()

    def shutdown(self) -> None:
        """Stop accepting, abort open keep-alive connections, and
        RELEASE the port — all within a bounded deadline (callers
        immediately rebind on master restart)."""
        self._stopping = True
        self._reactor.remove_listener(self, deadline_s=1.5)
        self._done.set()

    def server_close(self) -> None:
        try:
            self._sock.close()
        except OSError:
            pass
