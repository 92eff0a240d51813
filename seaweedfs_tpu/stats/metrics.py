"""Prometheus-format metrics: counters, gauges, histograms + exposition.

Equivalent of weed/stats/metrics.go:23-330 — the same collector families
(MasterReceivedHeartbeatCounter, VolumeServerRequestCounter/Histogram,
FilerRequestCounter/Histogram, S3RequestCounter, volume/EC-shard gauges),
exposed as text/plain; version=0.0.4 on each server's /metrics and
optionally pushed to a pushgateway (stats/metrics.go:300+). Implemented on
stdlib only; the exposition format is the wire contract, so any Prometheus
scraper works unchanged.
"""

from __future__ import annotations

import threading
import time
from bisect import bisect_left
from typing import Optional

from ..storage.crc import block_crc_path

DEFAULT_BUCKETS = (0.0001, 0.0003, 0.001, 0.003, 0.01, 0.03,
                   0.1, 0.3, 1.0, 3.0, 10.0)


def _escape_label_value(v: str) -> str:
    """Prometheus text-format label escaping (backslash, quote, newline):
    a label value carrying any of them would otherwise corrupt the whole
    exposition for every scraper."""
    return v.replace("\\", "\\\\").replace('"', '\\"').replace("\n", "\\n")


def _fmt_labels(label_names: tuple, label_values: tuple) -> str:
    if not label_names:
        return ""
    pairs = ",".join(f'{k}="{_escape_label_value(str(v))}"'
                     for k, v in zip(label_names, label_values))
    return "{" + pairs + "}"


class Counter:
    def __init__(self, name: str, help_: str = "", labels: tuple = ()):
        self.name, self.help = name, help_
        self.label_names = tuple(labels)
        self._values: dict[tuple, float] = {}
        self._lock = threading.Lock()

    def inc(self, *label_values, amount: float = 1.0) -> None:
        key = tuple(str(v) for v in label_values)
        with self._lock:
            self._values[key] = self._values.get(key, 0.0) + amount

    def labels(self, *label_values) -> "_BoundCounter":
        """Pre-touch a label set (exposes a 0 sample immediately, like
        prometheus client_golang's GetMetricWithLabelValues) and return
        a bound child."""
        key = tuple(str(v) for v in label_values)
        with self._lock:
            self._values.setdefault(key, 0.0)
        return _BoundCounter(self, key)

    def value(self, *label_values) -> float:
        return self._values.get(tuple(str(v) for v in label_values), 0.0)

    def snapshot(self) -> dict[tuple, float]:
        """Point-in-time copy, safe against concurrent inc()."""
        with self._lock:
            return dict(self._values)

    def merge(self, other: "Counter") -> None:
        """Fold another counter's samples in (per-label-set sum) — the
        cluster aggregator's cross-peer combine."""
        for key, v in other.snapshot().items():
            with self._lock:
                self._values[key] = self._values.get(key, 0.0) + v

    def expose(self) -> list[str]:
        out = [f"# HELP {self.name} {self.help}",
               f"# TYPE {self.name} counter"]
        for lv, v in sorted(self._values.items()):
            out.append(f"{self.name}{_fmt_labels(self.label_names, lv)} {_num(v)}")
        return out


class Gauge:
    def __init__(self, name: str, help_: str = "", labels: tuple = ()):
        self.name, self.help = name, help_
        self.label_names = tuple(labels)
        self._values: dict[tuple, float] = {}
        self._lock = threading.Lock()

    def set(self, *label_values_then_value) -> None:
        *label_values, value = label_values_then_value
        key = tuple(str(v) for v in label_values)
        with self._lock:
            self._values[key] = float(value)

    def add(self, *label_values_then_delta) -> None:
        *label_values, delta = label_values_then_delta
        key = tuple(str(v) for v in label_values)
        with self._lock:
            self._values[key] = self._values.get(key, 0.0) + float(delta)

    def labels(self, *label_values) -> "_BoundGauge":
        key = tuple(str(v) for v in label_values)
        with self._lock:
            self._values.setdefault(key, 0.0)
        return _BoundGauge(self, key)

    def value(self, *label_values) -> float:
        return self._values.get(tuple(str(v) for v in label_values), 0.0)

    def snapshot(self) -> dict[tuple, float]:
        with self._lock:
            return dict(self._values)

    def merge(self, other: "Gauge") -> None:
        """Per-label-set SUM: cluster gauges (volume counts, disk bytes)
        aggregate additively across peers."""
        for key, v in other.snapshot().items():
            with self._lock:
                self._values[key] = self._values.get(key, 0.0) + v

    def clear(self) -> None:
        with self._lock:
            self._values.clear()

    def expose(self) -> list[str]:
        out = [f"# HELP {self.name} {self.help}",
               f"# TYPE {self.name} gauge"]
        for lv, v in sorted(self._values.items()):
            out.append(f"{self.name}{_fmt_labels(self.label_names, lv)} {_num(v)}")
        return out


class Histogram:
    def __init__(self, name: str, help_: str = "", labels: tuple = (),
                 buckets: tuple = DEFAULT_BUCKETS):
        self.name, self.help = name, help_
        self.label_names = tuple(labels)
        self.buckets = tuple(buckets)
        self._counts: dict[tuple, list[int]] = {}
        self._sums: dict[tuple, float] = {}
        self._totals: dict[tuple, int] = {}
        # last exemplar per label set: (bucket index or None for +Inf,
        # observed value, trace id, unix ts) — the OpenMetrics bridge
        # from a latency bucket to the distributed trace that landed in it
        self._exemplars: dict[tuple, tuple[Optional[int], float, str,
                                           float]] = {}
        self._lock = threading.Lock()

    def observe(self, *label_values_then_obs,
                exemplar: Optional[str] = None) -> None:
        *label_values, obs = label_values_then_obs
        key = tuple(str(v) for v in label_values)
        with self._lock:
            counts = self._counts.setdefault(key, [0] * len(self.buckets))
            # smallest bucket whose le >= obs owns the observation; the
            # cumulative (le-inclusive) form is computed at exposition time
            i = bisect_left(self.buckets, obs)
            if i < len(self.buckets):
                counts[i] += 1
            self._sums[key] = self._sums.get(key, 0.0) + obs
            self._totals[key] = self._totals.get(key, 0) + 1
            if exemplar:
                self._exemplars[key] = (
                    i if i < len(self.buckets) else None,
                    obs, exemplar, time.time())

    def labels(self, *label_values) -> "_BoundHistogram":
        """Pre-touch a label set: the exposition emits every bucket
        (including +Inf) plus _sum/_count at 0 even before the first
        observe() — scrapers see the series exists rather than a gap."""
        key = tuple(str(v) for v in label_values)
        with self._lock:
            self._counts.setdefault(key, [0] * len(self.buckets))
            self._sums.setdefault(key, 0.0)
            self._totals.setdefault(key, 0)
        return _BoundHistogram(self, key)

    def snapshot(self) -> dict[tuple, tuple[list[int], float, int]]:
        """Per-label-set (bucket_counts, sum, count) copy, safe against
        concurrent observe()."""
        with self._lock:
            return {key: (list(self._counts[key]),
                          self._sums.get(key, 0.0),
                          self._totals.get(key, 0))
                    for key in self._counts}

    def merge(self, other: "Histogram") -> None:
        """Fold another histogram in: per-label-set elementwise bucket
        sums plus _sum/_count sums — by construction identical to having
        observed the union of both sample streams (each observation
        lands in exactly one bucket and contributes once to sum/count).
        Requires identical bucket boundaries; merging mismatched grids
        would silently misbin, so it raises instead."""
        if tuple(other.buckets) != self.buckets:
            raise ValueError(
                f"bucket mismatch: {other.buckets} vs {self.buckets}")
        for key, (counts, s, total) in other.snapshot().items():
            with self._lock:
                mine = self._counts.setdefault(key,
                                               [0] * len(self.buckets))
                for i, c in enumerate(counts):
                    mine[i] += c
                self._sums[key] = self._sums.get(key, 0.0) + s
                self._totals[key] = self._totals.get(key, 0) + total

    def time(self, *label_values):
        """Context manager: observes elapsed seconds."""
        hist = self

        class _Timer:
            def __enter__(self):
                self.t0 = time.perf_counter()
                return self

            def __exit__(self, *exc):
                hist.observe(*label_values, time.perf_counter() - self.t0)
                return False

        return _Timer()

    def expose(self, exemplars: bool = False) -> list[str]:
        """`exemplars=True` appends OpenMetrics exemplar suffixes to the
        owning bucket lines.  Off by default: exemplar syntax is ILLEGAL
        in the classic text format 0.0.4 this exposition is served and
        pushed as — a strict Prometheus/pushgateway parser would reject
        the whole scrape.  Endpoints turn it on only when the scraper
        asks (?exemplars=1 / an OpenMetrics Accept header)."""
        out = [f"# HELP {self.name} {self.help}",
               f"# TYPE {self.name} histogram"]
        for lv in sorted(self._counts):
            ex = self._exemplars.get(lv) if exemplars else None
            cumulative = 0
            for i, bound in enumerate(self.buckets):
                cumulative += self._counts[lv][i]
                labels = dict(zip(self.label_names, lv))
                labels["le"] = _num(bound)
                pairs = ",".join(
                    f'{k}="{_escape_label_value(str(v))}"'
                    for k, v in labels.items())
                line = f"{self.name}_bucket{{{pairs}}} {cumulative}"
                if ex is not None and ex[0] == i:
                    line += _fmt_exemplar(ex)
                out.append(line)
            labels = dict(zip(self.label_names, lv))
            labels["le"] = "+Inf"
            pairs = ",".join(f'{k}="{_escape_label_value(str(v))}"'
                             for k, v in labels.items())
            line = f"{self.name}_bucket{{{pairs}}} {self._totals[lv]}"
            if ex is not None and ex[0] is None:
                line += _fmt_exemplar(ex)
            out.append(line)
            plain = _fmt_labels(self.label_names, lv)
            out.append(f"{self.name}_sum{plain} {_num(self._sums[lv])}")
            out.append(f"{self.name}_count{plain} {self._totals[lv]}")
        return out


def _num(v: float) -> str:
    return str(int(v)) if float(v).is_integer() else repr(float(v))


def _fmt_exemplar(ex: tuple) -> str:
    """OpenMetrics exemplar suffix on the owning bucket line:
    ` # {trace_id="…"} value ts`.  Links the latency bucket to one
    sampled distributed trace; our own exposition parser
    (stats/aggregate.py) and Prometheus both tolerate/consume it."""
    _i, value, trace_id, ts = ex
    return (f' # {{trace_id="{_escape_label_value(trace_id)}"}} '
            f"{_num(value)} {_num(round(ts, 3))}")


class _BoundCounter:
    """Counter child bound to one label set (labels() result)."""

    __slots__ = ("_c", "_lv")

    def __init__(self, c: Counter, lv: tuple):
        self._c, self._lv = c, lv

    def inc(self, amount: float = 1.0) -> None:
        self._c.inc(*self._lv, amount=amount)

    def value(self) -> float:
        return self._c.value(*self._lv)


class _BoundGauge:
    __slots__ = ("_g", "_lv")

    def __init__(self, g: Gauge, lv: tuple):
        self._g, self._lv = g, lv

    def set(self, value: float) -> None:
        self._g.set(*self._lv, value)

    def add(self, delta: float) -> None:
        self._g.add(*self._lv, delta)

    def value(self) -> float:
        return self._g.value(*self._lv)


class _BoundHistogram:
    __slots__ = ("_h", "_lv")

    def __init__(self, h: Histogram, lv: tuple):
        self._h, self._lv = h, lv

    def observe(self, obs: float) -> None:
        self._h.observe(*self._lv, obs)

    def time(self):
        return self._h.time(*self._lv)


class Registry:
    def __init__(self):
        self._collectors: list = []
        self._lock = threading.Lock()

    def register(self, collector):
        with self._lock:
            self._collectors.append(collector)
        return collector

    def counter(self, name, help_="", labels=()):
        return self.register(Counter(name, help_, labels))

    def gauge(self, name, help_="", labels=()):
        return self.register(Gauge(name, help_, labels))

    def histogram(self, name, help_="", labels=(), buckets=DEFAULT_BUCKETS):
        return self.register(Histogram(name, help_, labels, buckets))

    def expose(self, exemplars: bool = False) -> str:
        lines: list[str] = []
        with self._lock:
            collectors = list(self._collectors)
        for c in collectors:
            if exemplars and isinstance(c, Histogram):
                lines.extend(c.expose(exemplars=True))
            else:
                lines.extend(c.expose())
        return "\n".join(lines) + "\n"


REGISTRY = Registry()


def exemplars_requested(req) -> bool:
    """Should this /metrics request get OpenMetrics exemplar suffixes?
    Only on the explicit ?exemplars=1 opt-in.  NOT on an OpenMetrics
    Accept header: modern Prometheus offers openmetrics-text by default,
    and honoring it here without also switching the response to the full
    OpenMetrics framing (content type + `# EOF` terminator) would hand a
    strict parser exemplar syntax inside a text/plain 0.0.4 body and
    fail the whole scrape."""
    try:
        return req.query.get("exemplars", "").lower() in ("1", "true",
                                                          "yes", "on")
    except Exception:
        return False


# --- the reference's collector families (stats/metrics.go:23-130) -----------

class _ServerMetrics:
    """Per-role bundle; namespaced like SeaweedFS_{master,volumeServer,...}."""

    def __init__(self, subsystem: str, registry: Registry):
        ns = f"SeaweedFS_{subsystem}"
        self.request_counter = registry.counter(
            f"{ns}_request_total", f"Counter of {subsystem} requests.",
            labels=("type",))
        self.request_histogram = registry.histogram(
            f"{ns}_request_seconds", f"Bucketed {subsystem} request latency.",
            labels=("type",))
        # 5xx responses per route: the numerator of the alerting
        # engine's error-ratio burn-rate SLO (observability/alerts.py) —
        # 4xx are client mistakes and never count against the budget
        self.request_errors = registry.counter(
            f"{ns}_request_errors_total",
            f"Counter of {subsystem} requests answered 5xx.",
            labels=("type",))


class MasterMetrics(_ServerMetrics):
    def __init__(self, registry: Registry = REGISTRY):
        super().__init__("master", registry)
        self.received_heartbeats = registry.counter(
            "SeaweedFS_master_received_heartbeats",
            "Counter of master received heartbeat.", labels=("type",))
        self.leader_gauge = registry.gauge(
            "SeaweedFS_master_is_leader", "1 if this master is raft leader.")


class VolumeServerMetrics(_ServerMetrics):
    def __init__(self, registry: Registry = REGISTRY):
        super().__init__("volumeServer", registry)
        self.volume_counter = registry.gauge(
            "SeaweedFS_volumeServer_volumes",
            "Number of volumes or EC shards.",
            labels=("collection", "type"))
        self.max_volume_counter = registry.gauge(
            "SeaweedFS_volumeServer_max_volumes", "Maximum volume count.")
        self.disk_size_gauge = registry.gauge(
            "SeaweedFS_volumeServer_total_disk_size",
            "Actual disk size used by volumes.",
            labels=("collection", "type"))
        self.native_plane_gauge = registry.gauge(
            "SeaweedFS_volumeServer_native_plane",
            "Native C++ data plane per-volume state.",
            labels=("volume", "stat"))


class FilerMetrics(_ServerMetrics):
    def __init__(self, registry: Registry = REGISTRY):
        super().__init__("filer", registry)
        # per-store-op collectors (stats.FilerStoreCounter/Histogram,
        # observed by the MeteredStore wrapper around every backend)
        self.store_counter = registry.counter(
            "SeaweedFS_filerStore_request_total",
            "Counter of filer store requests.", labels=("store", "type"))
        self.store_histogram = registry.histogram(
            "SeaweedFS_filerStore_request_seconds",
            "Bucketed filer store request latency.",
            labels=("store", "type"))


class S3Metrics(_ServerMetrics):
    def __init__(self, registry: Registry = REGISTRY):
        super().__init__("s3", registry)


class ECPipelineMetrics:
    """Self-healing EC pipeline counters: per-dispatch engine fallbacks
    to the CPU codec (ec/streaming.py, ec/codec.py), the dispatches by
    input source and the data-shard bytes the write lanes wrote; with
    them the path the sidecar's block crcs take.
    worker_restarts stays registered at 0 so /status and the alert plane
    keep their shape: no parity worker process is left to restart.
    Separate from the per-role bundles because the pipeline runs inside
    whatever process invoked the encode — volume server or shell tool."""

    def __init__(self, registry: Registry = REGISTRY):
        self.worker_restarts = registry.counter(
            "SeaweedFS_ec_worker_restarts_total",
            "Parity worker processes respawned (none exist: always 0).",
            labels=("kind",))
        self.engine_fallbacks = registry.counter(
            "SeaweedFS_ec_engine_fallbacks_total",
            "EC dispatches that fell back to the CPU codec.",
            labels=("reason",))
        self.dispatches = registry.counter(
            "SeaweedFS_ec_dispatches_total",
            "Staged and per-device-queue EC encode dispatches, by where "
            "their input came from: map (rows viewed in the mapped .dat) "
            "or slot (filled into a staging buffer).",
            labels=("source",))
        self.lane_data_bytes = registry.counter(
            "SeaweedFS_ec_lane_data_bytes_total",
            "Data-shard bytes EC encodes wrote on their shard-owned "
            "write lanes.")
        self.degraded_binds = registry.counter(
            "SeaweedFS_server_degraded_binds_total",
            "Servers that came up without their framed-TCP plane "
            "(bind failed; HTTP still serves).",
            labels=("role",))

    def totals(self) -> dict[str, int]:
        """Label-summed snapshot of every family — the one shape /status,
        the EC admin routes, encode stats, and bench health all consume."""
        return {
            "worker_restarts":
                int(sum(self.worker_restarts.snapshot().values())),
            "engine_fallbacks":
                int(sum(self.engine_fallbacks.snapshot().values())),
            "degraded_binds":
                int(sum(self.degraded_binds.snapshot().values())),
            "dispatches": int(sum(self.dispatches.snapshot().values())),
            "mapped_dispatches": int(self.dispatches.value("map")),
            "lane_data_bytes": int(self.lane_data_bytes.value()),
            # the path of the .eci block crcs: native (no copy, no GIL)
            # or the fallback's name
            "block_crc": block_crc_path(),
        }


class ECIntegrityMetrics:
    """Shard bit-rot defense counters (ec/integrity.py sidecars + the
    volume server scrubber).  corrupt_shards counts every detection,
    labeled by WHERE the rot was caught (scrub pass, rebuild survivor
    verify, or a read-path interval verify); repairs counts the
    scrubber's quarantine+rebuild outcomes.  All three fold into the
    master's /cluster/health (stats/aggregate.py HEALTH_FAMILIES) so a
    repaired-during-bench run can never pass as clean."""

    def __init__(self, registry: Registry = REGISTRY):
        self.scrub_blocks = registry.counter(
            "SeaweedFS_ec_scrub_blocks_total",
            "EC shard blocks verified against .eci sidecars.",
            labels=("verdict",))
        self.corrupt_shards = registry.counter(
            "SeaweedFS_ec_corrupt_shards_total",
            "Corrupt EC shards detected (sidecar block crc mismatch).",
            labels=("source",))
        self.repairs = registry.counter(
            "SeaweedFS_ec_scrub_repairs_total",
            "Corrupt EC shards quarantined and rebuilt by the scrubber.",
            labels=("outcome",))

    def totals(self) -> dict[str, int]:
        """Label-summed snapshot — the shape /status, the scrub routes,
        and bench scrub_health consume."""
        return {
            "scrub_blocks":
                int(sum(self.scrub_blocks.snapshot().values())),
            "corrupt_shards":
                int(sum(self.corrupt_shards.snapshot().values())),
            "scrub_repairs":
                int(sum(self.repairs.snapshot().values())),
        }


class CoordinatorMetrics:
    """Autonomous EC rebuild/rebalance coordinator counters
    (ops/coordinator.py, master-side).  `under_replicated` is the gauge
    behind the ec_under_replicated health family — volumes below k+1
    clean shards, which only the master (who holds the shard registry)
    can count; `repair_failures` is its coordinator_repair_failures
    companion.  Both fold into /cluster/health through the aggregator's
    local_fn hook, since no volume-server scrape can carry them."""

    def __init__(self, registry: Registry = REGISTRY):
        self.repairs = registry.counter(
            "SeaweedFS_coordinator_repairs_total",
            "EC volume repairs the coordinator executed.",
            labels=("outcome",))
        self.repair_failures = registry.counter(
            "SeaweedFS_coordinator_repair_failures_total",
            "Coordinator repair attempts that failed (by error type).",
            labels=("reason",))
        self.moves = registry.counter(
            "SeaweedFS_coordinator_moves_total",
            "EC shard moves the coordinator executed "
            "(dedupe/rack/skew/spread).",
            labels=("reason",))
        self.cycles = registry.counter(
            "SeaweedFS_coordinator_cycles_total",
            "Coordinator planning cycles.", labels=("outcome",))
        self.under_replicated = registry.gauge(
            "SeaweedFS_ec_under_replicated",
            "EC volumes below k+1 clean reachable shards.")
        self.queue_depth = registry.gauge(
            "SeaweedFS_coordinator_queue_depth",
            "EC volumes queued for repair.")

    def totals(self) -> dict[str, int]:
        return {
            "repairs": int(sum(self.repairs.snapshot().values())),
            "repair_failures":
                int(sum(self.repair_failures.snapshot().values())),
            "moves": int(sum(self.moves.snapshot().values())),
            "under_replicated": int(self.under_replicated.value()),
        }


class RequestPlaneMetrics:
    """Deadline / retry-budget / load-shedding counters — the graceful-
    degradation plane (utils/deadline.py, utils/admission.py,
    utils/backoff.py).  shed counts requests the admission controller
    answered 503 without running the handler; deadline_exceeded counts
    requests answered 504 because their X-Weed-Deadline budget was
    spent; retry_budget_exhausted counts retries a drained
    per-destination token bucket denied.  All three fold into the
    master's /cluster/health (stats/aggregate.py HEALTH_FAMILIES) so a
    cluster that is shedding or timing out pages instead of quietly
    failing its callers."""

    def __init__(self, registry: Registry = REGISTRY):
        self.shed = registry.counter(
            "SeaweedFS_requests_shed_total",
            "Requests shed by admission control (answered 503 early).",
            labels=("role",))
        self.deadline_exceeded = registry.counter(
            "SeaweedFS_deadline_exceeded_total",
            "Requests answered 504 because the propagated "
            "X-Weed-Deadline budget was exhausted.",
            labels=("role",))
        self.retry_budget_exhausted = registry.counter(
            "SeaweedFS_retry_budget_exhausted_total",
            "Retries denied by a drained per-destination retry budget.",
            labels=("kind",))

    def totals(self) -> dict[str, int]:
        return {
            "requests_shed": int(sum(self.shed.snapshot().values())),
            "deadline_exceeded":
                int(sum(self.deadline_exceeded.snapshot().values())),
            "retry_budget_exhausted":
                int(sum(self.retry_budget_exhausted.snapshot().values())),
        }


class DataplaneMetrics:
    """Event-loop serving dataplane (utils/eventloop.py): connection
    and dispatch accounting for the shared reactor.  conn_aborts counts
    connections the loop tore down abnormally (slow_client = outbox
    overflow, overflow = unframed input flood, send_error, stop =
    bounded-deadline teardown with work still in flight) — it feeds the
    `dataplane_conn_aborts` HEALTH_FAMILIES key, because a sustained
    abort rate means clients are losing in-flight responses."""

    def __init__(self, registry: Registry = REGISTRY):
        self.conn_aborts = registry.counter(
            "SeaweedFS_dataplane_conn_aborts_total",
            "Connections the reactor aborted with work in flight.",
            labels=("reason",))
        self.connections = registry.gauge(
            "SeaweedFS_dataplane_connections",
            "Connections currently owned by the reactor loop.")
        self.workers = registry.gauge(
            "SeaweedFS_dataplane_workers",
            "Dispatch worker pool size (-dataplane.workers).")
        self.pool_dispatches = registry.counter(
            "SeaweedFS_dataplane_pool_dispatches_total",
            "Requests dispatched onto the worker pool.")
        self.fast_dispatches = registry.counter(
            "SeaweedFS_dataplane_fast_dispatches_total",
            "Cache-probed reads dispatched inline on the loop.")
        # loop saturation telemetry (the resource-ledger plane): how
        # long each loop iteration held every connection hostage, and
        # the stall counter behind the `loop_lag` HEALTH_FAMILIES key
        self.loop_lag = registry.histogram(
            "SeaweedFS_dataplane_loop_lag_seconds",
            "Reactor loop iteration busy time (every connection waits "
            "this long).",
            buckets=(0.001, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
                     1.0, 2.5))
        self.loop_stalls = registry.counter(
            "SeaweedFS_dataplane_loop_stalls_total",
            "Loop-blocked moments past the stall threshold (the "
            "loop_lag health key — a blocked loop pages).")
        self.queue_depth = registry.gauge(
            "SeaweedFS_dataplane_queue_depth",
            "Dispatch queue depth per lane (watchdog-sampled).",
            labels=("lane",))
        self.workers_busy = registry.gauge(
            "SeaweedFS_dataplane_workers_busy",
            "Dispatch workers currently running handlers "
            "(watchdog-sampled).")

    def totals(self) -> dict[str, int]:
        return {
            "dataplane_conn_aborts":
                int(sum(self.conn_aborts.snapshot().values())),
            "pool_dispatches":
                int(sum(self.pool_dispatches.snapshot().values())),
            "fast_dispatches":
                int(sum(self.fast_dispatches.snapshot().values())),
            "loop_stalls":
                int(sum(self.loop_stalls.snapshot().values())),
        }


class NeedleCacheMetrics:
    """Popularity-aware needle read cache
    (volume_server/needle_cache.py): admission/eviction/invalidation
    accounting plus the resident-bytes gauge.  hit_ratio() is the
    bench `capacity` section's needle_cache_hit_ratio key."""

    def __init__(self, registry: Registry = REGISTRY):
        self.hits = registry.counter(
            "SeaweedFS_needle_cache_hits_total",
            "Needle reads served from the popularity cache.")
        self.misses = registry.counter(
            "SeaweedFS_needle_cache_misses_total",
            "Needle reads that went to the store.")
        self.admissions = registry.counter(
            "SeaweedFS_needle_cache_admissions_total",
            "Needles admitted after clearing the frequency bar.")
        self.rejections = registry.counter(
            "SeaweedFS_needle_cache_rejections_total",
            "Needle offers rejected by the admission policy.")
        self.evictions = registry.counter(
            "SeaweedFS_needle_cache_evictions_total",
            "Needles evicted to honor the byte bound.")
        self.invalidations = registry.counter(
            "SeaweedFS_needle_cache_invalidations_total",
            "Cache entries dropped by write/delete/vacuum.",
            labels=("reason",))
        self.bytes = registry.gauge(
            "SeaweedFS_needle_cache_bytes",
            "Resident cached needle bytes.")
        # per-volume split (heat attribution: the aggregate ratio
        # cannot say WHICH volume's working set the cache absorbs)
        self.volume_hits = registry.counter(
            "SeaweedFS_needle_cache_volume_hits_total",
            "Needle cache hits per volume.", labels=("volume",))
        self.volume_misses = registry.counter(
            "SeaweedFS_needle_cache_volume_misses_total",
            "Needle cache misses per volume.", labels=("volume",))

    def hit_ratio(self) -> float:
        hits = sum(self.hits.snapshot().values())
        misses = sum(self.misses.snapshot().values())
        total = hits + misses
        return round(hits / total, 4) if total else 0.0

    def totals(self) -> dict:
        return {
            "hits": int(sum(self.hits.snapshot().values())),
            "misses": int(sum(self.misses.snapshot().values())),
            "admissions": int(sum(self.admissions.snapshot().values())),
            "evictions": int(sum(self.evictions.snapshot().values())),
            "invalidations":
                int(sum(self.invalidations.snapshot().values())),
            "bytes": int(self.bytes.value()),
            "hit_ratio": self.hit_ratio(),
        }


class HeatMetrics:
    """Cluster heat-telemetry plane (observability/heat.py).  The two
    gauge families are master-side (set on /cluster/heat/ingest); the
    drop counter is volume-side shipper loss.  Family names live in
    heat.HEAT_METRIC_FAMILIES and W401 checks they stay registered."""

    def __init__(self, registry: Registry = REGISTRY):
        self.volume_heat = registry.gauge(
            "SeaweedFS_volume_heat",
            "Merged decayed read+cache-hit heat per volume (1/s).",
            labels=("volume",))
        self.imbalance = registry.gauge(
            "SeaweedFS_heat_imbalance_ratio",
            "max/mean heat ratio across a scope (server, rack).",
            labels=("scope",))
        self.snapshots_dropped = registry.counter(
            "SeaweedFS_heat_snapshots_dropped_total",
            "Heat snapshots lost by the shipper (master unreachable "
            "or buffer superseded).")


class LedgerMetrics:
    """Cluster resource-ledger plane (observability/ledger.py).  The
    per-route gauge families are refreshed by the LedgerShipper at
    ship cadence (never on the request path); the drop counter is
    shipper loss.  Family names live in ledger.LEDGER_METRIC_FAMILIES
    and W401 checks they stay registered."""

    def __init__(self, registry: Registry = REGISTRY):
        self.route_cpu = registry.gauge(
            "SeaweedFS_ledger_route_cpu_rate",
            "Decayed thread-CPU seconds/second per route class.",
            labels=("route",))
        self.route_qwait = registry.gauge(
            "SeaweedFS_ledger_route_queue_wait_rate",
            "Decayed dispatch-queue-wait seconds/second per route "
            "class.",
            labels=("route",))
        self.route_bytes = registry.gauge(
            "SeaweedFS_ledger_route_bytes_rate",
            "Decayed bytes/second per route class and direction.",
            labels=("route", "dir"))
        self.snapshots_dropped = registry.counter(
            "SeaweedFS_ledger_snapshots_dropped_total",
            "Ledger snapshots lost by the shipper (master unreachable "
            "or buffer superseded).")


_singletons: dict[str, object] = {}
_singleton_lock = threading.Lock()


def _singleton(name, cls):
    with _singleton_lock:
        if name not in _singletons:
            _singletons[name] = cls()
        return _singletons[name]


def master_metrics() -> MasterMetrics:
    return _singleton("master", MasterMetrics)


def volume_server_metrics() -> VolumeServerMetrics:
    return _singleton("volume", VolumeServerMetrics)


def filer_metrics() -> FilerMetrics:
    return _singleton("filer", FilerMetrics)


def s3_metrics() -> S3Metrics:
    return _singleton("s3", S3Metrics)


def ec_pipeline_metrics() -> ECPipelineMetrics:
    return _singleton("ec_pipeline", ECPipelineMetrics)


def ec_integrity_metrics() -> ECIntegrityMetrics:
    return _singleton("ec_integrity", ECIntegrityMetrics)


def coordinator_metrics() -> CoordinatorMetrics:
    return _singleton("coordinator", CoordinatorMetrics)


def request_plane_metrics() -> RequestPlaneMetrics:
    return _singleton("request_plane", RequestPlaneMetrics)


def dataplane_metrics() -> DataplaneMetrics:
    return _singleton("dataplane", DataplaneMetrics)


def needle_cache_metrics() -> NeedleCacheMetrics:
    return _singleton("needle_cache", NeedleCacheMetrics)


def heat_metrics() -> HeatMetrics:
    return _singleton("heat", HeatMetrics)


def ledger_metrics() -> LedgerMetrics:
    return _singleton("ledger", LedgerMetrics)


def start_push_loop(gateway_url: str, job: str,
                    interval_seconds: float = 15.0,
                    registry: Registry = REGISTRY,
                    stop_event: Optional[threading.Event] = None) -> threading.Thread:
    """stats/metrics.go push mode: PUT the exposition to a pushgateway."""
    stop = stop_event or threading.Event()

    def loop():
        from ..utils.httpd import http_bytes

        while not stop.wait(interval_seconds):
            try:
                http_bytes("PUT", f"{gateway_url}/metrics/job/{job}",
                           registry.expose().encode(),
                           headers={"Content-Type": "text/plain"},
                               timeout=60.0)
            except Exception:
                pass

    t = threading.Thread(target=loop, daemon=True, name="metrics-push")
    t.start()
    return t
