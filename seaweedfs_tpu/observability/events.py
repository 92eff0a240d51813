"""Structured cluster event journal: the WHAT-happened record.

PRs 1/4/6 made degraded moments *counted* (worker_restarts,
engine_fallbacks, corrupt_shards, ...) and *traced* (pipeline.retry /
pipeline.fallback spans) — but a counter says only "3 since boot" and a
span ring evicts under load, so the operator question "what went wrong
on this cluster in the last hour?" still had no answer.  This module is
that answer: a bounded, thread-safe ring of TYPED events emitted at the
exact chokepoints that already bump the degraded-path counters:

    from seaweedfs_tpu.observability import events as _events
    _events.emit("engine_fallback", reason="drain_fault", engine="device")

Each event carries a type from EVENT_TYPES (with a default severity), a
wall timestamp, the emitting server (from the request thread-local when
inside one), the ACTIVE distributed-trace id (observability/context.py)
when the moment happened under a sampled trace — the join key back to
the stitched cluster trace that explains it — and a small details dict.

Served per server at GET /debug/events (type/severity/since filters)
and shipped master-ward by EventShipper (the PR-6 TraceShipper
transport pattern: chained hook, bounded buffer, batch POST, loss
counted never backpressured) into the master's ClusterEventJournal at
GET /cluster/events — the cluster-wide journal the alerting engine
(observability/alerts.py) annotates with alert_fired/alert_resolved
transitions.

Cost discipline: emit() is only ever called on degraded paths and alert
transitions — never on a clean hot path — so the journal needs no
enable gate; the ring is bounded and eviction is counted (`dropped`).
"""

from __future__ import annotations

import os
import threading
import time
from collections import OrderedDict, deque
from typing import Callable, Optional

from . import context as _trace_context

# severity order matters: min_severity filters compare by rank
SEVERITIES = ("info", "warning", "error", "critical")
SEVERITY_RANK = {s: i for i, s in enumerate(SEVERITIES)}

# the event-type registry: every emit site uses one of these types, and
# each carries its default severity.  tools/check_health_keys.py lints
# this table against stats/aggregate.py HEALTH_FAMILIES and the default
# alert rules so a degraded counter added to one table but not the
# others fails tier-1 instead of drifting silently.
EVENT_TYPES = {
    # degraded-path chokepoints (each shadows a /metrics counter)
    "worker_restart": "warning",        # no emitter: parity workers are gone
    "engine_fallback": "warning",       # ec/streaming.py + ec/codec.py
    "shard_corrupt": "error",           # ec/integrity.py note_corruption
    "scrub_repair": "warning",          # scrubber quarantine+rebuild ok
    "scrub_repair_failed": "error",     # rebuild raised; rot remains
    "scrub_unrepairable": "critical",   # < k clean shards left
    "degraded_bind": "warning",         # TCP plane bind failed
    "peer_stale": "warning",            # master scrape lost a peer
    # alerting engine state transitions (observability/alerts.py)
    "alert_pending": "info",
    "alert_fired": "error",
    "alert_resolved": "info",
    # flight recorder captures (observability/flightrecorder.py)
    "flight_capture": "info",
    # rebuild/rebalance coordinator (ops/coordinator.py, master-side)
    "ec_under_replicated": "error",  # volume dropped below k+1 clean
    "repair_planned": "info",        # coordinator queued + started one
    "repair_done": "info",           # volume back to full shard set
    "repair_failed": "error",        # plan step failed; will re-plan
    "rebalance_move": "info",        # one budgeted shard move executed
    # request-plane graceful degradation (utils/deadline.py,
    # utils/admission.py, utils/backoff.py)
    "load_shed": "warning",          # admission control answered 503
    "deadline_exceeded": "warning",  # X-Weed-Deadline budget spent: 504
    "retry_budget_exhausted": "warning",  # token bucket denied a retry
    # workload flight recorder (observability/reqlog.py)
    "reqlog_dropped": "warning",     # access records lost (ring/ship)
    # event-loop serving dataplane (utils/eventloop.py)
    "dataplane_conn_abort": "warning",  # conn torn down mid-flight
    # heat-telemetry shift detector (observability/heat.py, master):
    # both are watched by default `journal_event` alert rules — the
    # heat.HEAT_EVENT_TYPES tuple is W401-linted against this table
    # and default_rules() so neither side can drift
    "heat_shift": "warning",   # a volume newly entered the Zipf head
    "flash_crowd": "error",    # a COLD volume took the head outright
    # resource-ledger loop-stall relay (observability/ledger.py,
    # master): the ledger.LEDGER_EVENT_TYPES tuple is W401-linted the
    # same way HEAT_EVENT_TYPES is
    "loop_stall": "error",     # reactor loop blocked past threshold
    # heat autoscaler actuations (ops/autoscaler.py, master): every
    # replica_grow carries the causing heat alert id + exemplar trace;
    # tier_committed is journaled only after the raft commit record
    "replica_grow": "info",    # read replica added for a hot volume
    "replica_shrink": "info",  # hold-down elapsed: added replica drops
    "tier_committed": "info",  # cold .dat committed to remote backend
    "tier_recall": "info",     # heat returned: tiered .dat recalled
    "autoscale_failed": "error",  # a grow/shrink/tier/recall leg failed
}

# HEALTH_FAMILIES key (stats/aggregate.py) -> the event type emitted at
# the chokepoint that bumps that family's counter.  The check_health_keys
# lint walks this mapping both ways.
HEALTH_EVENT_TYPES = {
    "worker_restarts": "worker_restart",
    "engine_fallbacks": "engine_fallback",
    "degraded_binds": "degraded_bind",
    "corrupt_shards": "shard_corrupt",
    "scrub_repairs": "scrub_repair",
    "ec_under_replicated": "ec_under_replicated",
    "coordinator_repair_failures": "repair_failed",
    "requests_shed": "load_shed",
    "deadline_exceeded": "deadline_exceeded",
    "retry_budget_exhausted": "retry_budget_exhausted",
    "reqlog_records_dropped": "reqlog_dropped",
    "dataplane_conn_aborts": "dataplane_conn_abort",
    "loop_lag": "loop_stall",
    "autoscale_failures": "autoscale_failed",
}


class Event:
    """One journaled cluster event.  `id` is namespaced like span ids
    (process-unique salt + sequence) so the master-side journal can
    dedup re-ships and co-located in-process shippers."""

    __slots__ = ("type", "severity", "server", "ts", "trace_id",
                 "details", "seq", "id")

    def __init__(self, type_: str, severity: str, server: Optional[str],
                 ts: float, trace_id: Optional[str], details: dict,
                 seq: int, id_: str):
        self.type = type_
        self.severity = severity
        self.server = server
        self.ts = ts
        self.trace_id = trace_id
        self.details = details
        self.seq = seq
        self.id = id_

    def to_dict(self) -> dict:
        d = {"id": self.id, "seq": self.seq, "type": self.type,
             "severity": self.severity, "ts": round(self.ts, 3),
             "details": self.details}
        if self.server:
            d["server"] = self.server
        if self.trace_id:
            d["trace"] = self.trace_id
        return d


def _match(e: dict, type_: Optional[str] = None,
           severity: Optional[str] = None,
           min_severity: Optional[str] = None,
           since_seq: int = 0, since_ts: float = 0.0) -> bool:
    """Shared filter predicate over event DICTS (the wire shape)."""
    if type_ and e.get("type") != type_:
        return False
    if severity and e.get("severity") != severity:
        return False
    if min_severity:
        if SEVERITY_RANK.get(e.get("severity"), 0) < \
                SEVERITY_RANK.get(min_severity, 0):
            return False
    if since_seq and int(e.get("seq") or 0) <= since_seq:
        return False
    if since_ts and float(e.get("ts") or 0.0) <= since_ts:
        return False
    return True


class EventJournal:
    """Bounded thread-safe ring of typed events (one per process)."""

    def __init__(self, capacity: int = 2048,
                 namespace: Optional[str] = None):
        self._events: deque[Event] = deque(maxlen=capacity)  # guarded-by: _lock
        self._lock = threading.Lock()
        self._seq = 0  # guarded-by: _lock
        # same salting rationale as the tracer: bare pids collide across
        # containerized hosts and the master journal dedups by event id
        self.namespace = namespace or (
            f"e{os.getpid():x}x{os.urandom(3).hex()}")
        # ring evictions — a truncated journal says so
        self.dropped = 0  # guarded-by: _lock
        # shipping hook (EventShipper): called with every emitted Event
        self.on_emit: Optional[Callable[[Event], None]] = None
        # server identities of the attached shippers: when exactly ONE
        # server owns this process's journal (the production shape),
        # emits from background threads (drainers, supervisors) that
        # carry no request thread-local still stamp correctly; with
        # co-located servers the stamp is AMBIGUOUS and the event ships
        # unattributed rather than letting whichever shipper's copy
        # wins the collector's dedup claim it
        self._servers: list[str] = []  # guarded-by: _lock

    @property
    def capacity(self) -> int:
        return self._events.maxlen or 0  # weedlint: disable=W501 maxlen is immutable configuration, not ring state

    def register_server(self, server: str) -> None:
        with self._lock:
            self._servers.append(server)

    def unregister_server(self, server: str) -> None:
        with self._lock:
            if server in self._servers:
                self._servers.remove(server)

    def _default_server(self) -> Optional[str]:
        with self._lock:
            unique = set(self._servers)
            return next(iter(unique)) if len(unique) == 1 else None

    def emit(self, type_: str, severity: Optional[str] = None,  # thread-entry
             server: Optional[str] = None,
             trace_id: Optional[str] = None, **details) -> Event:
        """Journal one event — from ANY thread (drainers, supervisors,
        scan loops; the thread-entry annotation makes the lockset
        checker model that).  Severity defaults from EVENT_TYPES; the
        trace id defaults to the calling thread's ACTIVE sampled trace
        context and the server to the request's owning-server identity
        (both thread-local reads — emit sites never plumb identity)."""
        if severity is None:
            severity = EVENT_TYPES.get(type_, "info")
        if trace_id is None:
            ctx = _trace_context.current_sampled()
            trace_id = ctx.trace_id if ctx is not None else None
        if server is None:
            server = _trace_context.current_server() or \
                self._default_server()
        with self._lock:
            self._seq += 1
            ev = Event(type_, severity, server, time.time(), trace_id,
                       details, self._seq,
                       f"{self.namespace}.{self._seq:x}")
            if len(self._events) == self._events.maxlen:
                self.dropped += 1
            self._events.append(ev)
        hook = self.on_emit
        if hook is not None:
            try:
                hook(ev)
            except Exception:
                pass  # shipping must never break the degraded path
        return ev

    def snapshot(self) -> list[Event]:
        with self._lock:
            return list(self._events)

    def clear(self) -> None:
        with self._lock:
            self._events.clear()
            self.dropped = 0

    def query(self, type_: Optional[str] = None,
              severity: Optional[str] = None,
              min_severity: Optional[str] = None,
              since_seq: int = 0, since_ts: float = 0.0,
              limit: int = 256) -> list[dict]:
        """Filtered event dicts in chronological order, keeping the most
        RECENT `limit` matches (a tail, not a head — the fresh end is
        what an operator asks for)."""
        out = [e.to_dict() for e in self.snapshot()]
        out = [e for e in out
               if _match(e, type_, severity, min_severity,
                         since_seq, since_ts)]
        return out[-max(int(limit), 0):] if limit else out


class ClusterEventJournal:  # weedlint: concurrent-class
    """The master's merged journal: per-server journals ship here
    (EventShipper), dedup'd by event id, bounded by oldest-first
    eviction — the /cluster/events store.  Reached concurrently from
    the threaded HTTP router (ingest POSTs + query GETs)."""

    def __init__(self, capacity: int = 8192):
        self.capacity = capacity
        self._events: "OrderedDict[str, dict]" = OrderedDict()  # guarded-by: _lock
        self._lock = threading.Lock()
        self.dropped = 0  # guarded-by: _lock
        # consumer hook: called OUTSIDE the lock with each batch of
        # newly-accepted (non-duplicate) event dicts — the rebuild
        # coordinator subscribes here instead of polling the journal
        self.on_ingest: Optional[Callable[[list[dict]], None]] = None

    def ingest(self, server: str, events: list[dict]) -> int:
        accepted: list[dict] = []
        with self._lock:
            for e in events:
                eid = e.get("id")
                if not eid or eid in self._events:
                    continue  # duplicate ship (chained shippers, retry)
                e = dict(e)
                # the transport's identity is only a LABEL of who
                # shipped, never a claim of who emitted: an event that
                # arrives unattributed (ambiguous co-located journal)
                # stays unattributed
                e["via"] = server
                self._events[eid] = e
                accepted.append(e)
            while len(self._events) > self.capacity:
                self._events.popitem(last=False)
                self.dropped += 1
        hook = self.on_ingest
        if hook is not None and accepted:
            try:
                hook(list(accepted))
            except Exception:
                pass  # a broken consumer must never break ingest
        return len(accepted)

    def query(self, type_: Optional[str] = None,
              severity: Optional[str] = None,
              min_severity: Optional[str] = None,
              since_ts: float = 0.0, server: Optional[str] = None,
              limit: int = 256) -> list[dict]:
        with self._lock:
            events = list(self._events.values())
        out = [e for e in events
               if _match(e, type_, severity, min_severity, 0, since_ts)
               and (not server or e.get("server") == server)]
        # shipped batches interleave across servers: order by time for a
        # coherent cluster timeline (id breaks ts ties stably)
        out.sort(key=lambda e: (float(e.get("ts") or 0.0),
                                str(e.get("id"))))
        return out[-max(int(limit), 0):] if limit else out

    def __len__(self) -> int:
        with self._lock:
            return len(self._events)


class EventShipper:
    """Ship this process's journal to the master's cluster journal —
    the TraceShipper transport pattern (collector.py): chained on_emit
    hook, bounded buffer, batch POST on a flush thread, loss COUNTED
    (never backpressure on the emitting path), `local_journal`
    short-circuit for the master's own events."""

    def __init__(self, journal: EventJournal, server: str,
                 master_url_fn: Optional[Callable[[], str]] = None,
                 local_journal: Optional[ClusterEventJournal] = None,
                 batch_size: int = 64, flush_interval: float = 0.5,
                 buffer_cap: int = 1024):
        self.journal = journal
        self.server = server
        self.master_url_fn = master_url_fn
        self.local_journal = local_journal
        self.batch_size = batch_size
        self.flush_interval = flush_interval
        self.buffer_cap = buffer_cap
        self._buf: deque[Event] = deque()  # guarded-by: _lock
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._wake = threading.Event()
        self._thread: Optional[threading.Thread] = None
        # hook-chain handoff: written by attach()/detach() on the
        # server's lifecycle thread before the flush thread starts /
        # after it stops; read lock-free on every emit
        self._prev_hook: Optional[Callable[[Event], None]] = None
        # shared leader-follow policy (utils/leader.py) — internally locked
        from ..utils.leader import LeaderFollowingTransport
        self.transport = LeaderFollowingTransport(master_url_fn,
                                                  name=f"events:{server}")
        self.shipped = 0  # guarded-by: _lock
        self.dropped = 0  # guarded-by: _lock

    def attach(self) -> "EventShipper":
        self._prev_hook = self.journal.on_emit  # weedlint: disable=W502 lifecycle handoff: runs before the flush thread starts
        self.journal.on_emit = self._on_event
        self.journal.register_server(self.server)
        self._thread = threading.Thread(target=self._flush_loop,
                                        daemon=True,
                                        name=f"event-ship:{self.server}")
        self._thread.start()
        return self

    def detach(self) -> None:
        self._stop.set()
        self._wake.set()
        if self._thread is not None:
            self._thread.join(timeout=2.0)
        if self.journal.on_emit is self._on_event:
            self.journal.on_emit = self._prev_hook
        self.journal.unregister_server(self.server)
        # final flush with a sub-second timeout: at cluster teardown the
        # master is often already gone and stop() must not hang
        self._flush(timeout=0.5)

    def _on_event(self, ev: Event) -> None:  # thread-entry
        # called on whatever thread emitted (drainers, scan loops);
        # a detached shipper left mid-chain degrades to a pass-through
        if not self._stop.is_set():
            with self._lock:
                if len(self._buf) >= self.buffer_cap:
                    self.dropped += 1
                else:
                    self._buf.append(ev)
                    if len(self._buf) >= self.batch_size:
                        self._wake.set()
        prev = self._prev_hook
        if prev is not None:
            prev(ev)

    def _flush_loop(self) -> None:
        while not self._stop.is_set():
            self._wake.wait(self.flush_interval)
            self._wake.clear()
            self._flush()

    def _flush(self, timeout: float = 5.0) -> None:
        with self._lock:
            if not self._buf:
                return
            batch = list(self._buf)
            self._buf.clear()
        # the server stamp is decided at EMIT time (thread-local or the
        # journal's sole-shipper default) — a shipper must not claim
        # unattributed events as its own: with co-located servers both
        # chained shippers ship the same event id and whichever copy
        # wins the collector's dedup would misattribute it
        docs = [ev.to_dict() for ev in batch]
        if self.local_journal is not None:
            self.local_journal.ingest(self.server, docs)
            with self._lock:
                self.shipped += len(docs)
            return
        try:
            # shipping must never trace itself (same rule as spans)
            with _trace_context.scope(_trace_context.NOT_SAMPLED):
                self.transport.post("/cluster/events/ingest",
                                    {"server": self.server, "events": docs},
                                    timeout=timeout)
            with self._lock:
                self.shipped += len(docs)
        except Exception:
            # master down / not elected: the batch is LOST and counted;
            # the transport rotated to the next configured master and
            # re-learns the leader from ingest replies post-election.
            # Counter updates ride _lock: the flush thread and the
            # detach()-time final flush race these read-modify-writes
            with self._lock:
                self.dropped += len(docs)


# --- process-global journal --------------------------------------------------
# Every layer emits into ONE journal per process (like the tracer), so
# /debug/events and the shipper see worker restarts from ec/, scrub
# verdicts from volume_server/, and alert transitions from the master
# without plumbing a journal handle through each constructor.

_GLOBAL = EventJournal()


def get_journal() -> EventJournal:
    return _GLOBAL


def emit(type_: str, severity: Optional[str] = None,
         server: Optional[str] = None, trace_id: Optional[str] = None,
         **details) -> Event:
    """Module-level convenience: journal one event on the process-global
    journal (the one-liner the degraded-path chokepoints call)."""
    return _GLOBAL.emit(type_, severity=severity, server=server,
                        trace_id=trace_id, **details)
