"""Fault injection: named fault points with configurable errors/delays.

SURVEY.md §5 notes the reference has no fault-injection framework and
that the rebuild should carry one.  Fault points are free when disabled
(one dict-emptiness check); tests and chaos drills arm them:

    from seaweedfs_tpu.utils import faultinject as fi

    fi.enable("disk.read", error_rate=0.3)         # 30% of reads raise
    fi.enable("net.request", delay=0.05)           # +50ms per request
    with fi.scoped("disk.sync", error_rate=1.0):   # scoped arming
        ...
    fi.clear()

Instrumented sites (grep for fi.hit to find them all):
    disk.read / disk.write / disk.sync   — DiskFile positional IO
    shard.read                           — EC shard pread
    net.request                          — pooled HTTP client sends
    ec.dispatch / ec.drain               — streaming pipeline submit and
                                           drain; an injected error forces
                                           a per-dispatch CPU fallback
    ec.shard.corrupt                     — deterministic bit flip on EC
                                           shard reads (corrupt_block):
                                           armed with params
                                           {"shard": id, "offset": byte,
                                           "bit": 0-7}, any read of that
                                           shard covering that byte comes
                                           back flipped — the bit-rot
                                           drill behind the sidecar
                                           verify-on-use paths
"""

from __future__ import annotations

import contextlib
import random
import threading
import time
from typing import Optional

_lock = threading.Lock()
_points: dict[str, dict] = {}
_counts: dict[str, int] = {}

# The CENTRAL fault-point registry: every hit()/corrupt_block() site in
# the package names one of these, and every entry is exercised by at
# least one test — both directions machine-checked by weedlint rule
# W701 (tools/weedlint/rules_faults.py), so a typo'd name can't
# silently never fire and a registered point can't silently never run
# its recovery path.  `weed shell fault.list` prints this table.
FAULT_POINTS: dict[str, str] = {
    "disk.read": "DiskFile positional read (storage/backend.py)",
    "disk.write": "DiskFile positional write (storage/backend.py)",
    "disk.sync": "DiskFile fsync (storage/backend.py)",
    "shard.read": "EC shard pread (ec/ec_volume.py)",
    "net.request": "pooled HTTP client send (utils/httpd.py)",
    "ec.dispatch": "streaming pipeline submit — injected error forces "
                   "a per-dispatch CPU fallback (ec/streaming.py)",
    "ec.drain": "streaming pipeline drain — injected error forces a "
                "per-dispatch CPU fallback; delay-only arming drives "
                "the slow-drain attribution drills (ec/streaming.py)",
    "ec.shard.corrupt": "deterministic bit flip on EC shard reads "
                        "(corrupt_block): params {shard, offset, bit} "
                        "— the bit-rot drill behind verify-on-use "
                        "(ec/integrity.py paths)",
    "coord.plan": "rebuild/rebalance coordinator planning cycle — an "
                  "injected error must be contained (loop survives, "
                  "last_error surfaces, next cycle re-plans) "
                  "(ops/coordinator.py)",
    "coord.exec": "coordinator plan-execution step (every "
                  "/admin/ec/* leg) — injected error fails the "
                  "current repair/move so re-plan + no-orphan "
                  "cleanup paths run (ops/coordinator.py)",
    "net.delay": "peer-scoped wire slowdown at the pooled-HTTP and "
                 "framed egress: params={'peer': 'host:port'} (absent "
                 "= all peers) + delay=<s>.  Applied deadline-aware "
                 "(deadline.sleep_within), so a caller with an "
                 "X-Weed-Deadline budget still returns on time — the "
                 "scenario engine's slow-network drill "
                 "(utils/httpd.py, utils/framing.py)",
    "net.drop": "peer-scoped probabilistic request loss at the same "
                "egress points: params={'peer': ...}, error_rate<1 "
                "models packet loss / connection resets "
                "(utils/httpd.py, utils/framing.py)",
    "net.partition": "peer-scoped total partition: arm with "
                     "error_rate=1.0 + params={'peer': ...} and every "
                     "send to that peer fails instantly — the "
                     "failure-under-load scenario's rack-loss stand-in "
                     "(utils/httpd.py, utils/framing.py)",
    "loop.block": "reactor inline fast path, ON the event-loop thread "
                  "— delay-only arming blocks the WHOLE dataplane for "
                  "the duration, the loop-stall drill behind the "
                  "loop_lag health key and the loop_stall alert relay "
                  "(utils/eventloop.py)",
    "tier.upload": "tier phase-1 upload, fired with the .tier manifest "
                   "on disk and no remote byte sent yet — delay-only "
                   "arming opens the mid-upload SIGKILL window the "
                   "crash drill proves survivable: local .dat stays "
                   "authoritative, partial remote object is GC'd "
                   "(storage/volume.py tier_upload_begin)",
    "tier.recall": "tier recall download, fired with the manifest in "
                   "'recalling' and only a temp file partial — the "
                   "mid-recall SIGKILL window: remote copy stays "
                   "authoritative, partial temp is dropped "
                   "(storage/volume.py tier_download)",
}


def list_points() -> list[tuple[str, str]]:
    """The registry as sorted (name, description) pairs — what
    `weed shell fault.list` prints."""
    return sorted(FAULT_POINTS.items())


def enable(name: str, error_rate: float = 0.0,
           error: Optional[BaseException] = None,
           delay: float = 0.0, max_hits: int = 0,
           params: Optional[dict] = None) -> None:
    """Arm a fault point.  error_rate in [0,1]; max_hits>0 auto-disarms
    after that many injected faults (deterministic crash tests).
    params carries site-specific fault data for data-mutation points
    (ec.shard.corrupt's shard/offset/bit targeting)."""
    with _lock:
        _points[name] = {
            "error_rate": error_rate,
            "error": error or OSError(f"fault injected at {name}"),
            "delay": delay,
            "max_hits": max_hits,
            "hits": 0,
            "params": dict(params) if params else None,
        }


def disable(name: str) -> None:
    with _lock:
        _points.pop(name, None)


def clear() -> None:
    with _lock:
        _points.clear()
        _counts.clear()


def fired(name: str) -> int:
    """How many times this point actually injected a fault."""
    return _counts.get(name, 0)


@contextlib.contextmanager
def scoped(name: str, **kwargs):
    enable(name, **kwargs)
    try:
        yield
    finally:
        disable(name)


def hit(name: str) -> None:
    """The instrumented call: no-op unless armed (callers guard with
    `if faultinject._points:` for true zero cost on hot paths)."""
    if not _points:
        return
    with _lock:
        p = _points.get(name)
        if p is None:
            return
        if p["max_hits"] and p["hits"] >= p["max_hits"]:
            return
        inject_error = p["error_rate"] and random.random() < p["error_rate"]
        delay = p["delay"]
        if inject_error or delay:
            p["hits"] += 1
            _counts[name] = _counts.get(name, 0) + 1
        err = p["error"] if inject_error else None
    if delay:
        time.sleep(delay)
    if err is not None:
        raise err


def arm_from_env(spec: Optional[str] = None) -> int:
    """Arm fault points from a WEED_FAULTS-style spec string so chaos
    drills can inject faults into SUBPROCESS servers (spawned via
    weed.py) that they cannot reach through in-process enable() calls.

    Format: ``name:key=val,key=val;name2:...`` — e.g.
    ``WEED_FAULTS="tier.upload:delay=5,max_hits=1"``.  Keys: error_rate
    (float), delay (float, seconds), max_hits (int).  Unknown point
    names still arm (the registry check is weedlint's job, and a drill
    may target a point added in the same change).  Returns the number
    of points armed."""
    import os as _os

    if spec is None:
        spec = _os.environ.get("WEED_FAULTS", "")
    armed = 0
    for part in (spec or "").split(";"):
        part = part.strip()
        if not part:
            continue
        name, _, kvs = part.partition(":")
        kwargs: dict = {}
        for kv in kvs.split(","):
            kv = kv.strip()
            if not kv:
                continue
            k, _, v = kv.partition("=")
            k = k.strip()
            if k == "max_hits":
                kwargs[k] = int(v)
            elif k in ("error_rate", "delay"):
                kwargs[k] = float(v)
        enable(name.strip(), **kwargs)
        armed += 1
    return armed


def _peer_matches(p: Optional[dict], peer: str) -> bool:
    """Does this armed point's scope cover `peer`?  No params or no
    'peer' key = every peer; otherwise exact netloc match."""
    if p is None:
        return False
    prm = p.get("params")
    if not prm or prm.get("peer") is None:
        return True
    return str(prm.get("peer")) == peer


def hit_peer(name: str, peer: str) -> None:
    """Peer-scoped twin of hit(): fires only when the armed point's
    params name this destination (params absent = all peers).  The
    net.drop / net.partition egress sites ride this so a scenario can
    partition ONE peer while the rest of the cluster serves."""
    if not _points:
        return
    with _lock:
        if not _peer_matches(_points.get(name), peer):
            return
    hit(name)


def peer_delay(name: str, peer: str) -> float:
    """Peer-scoped delay QUERY: returns the armed delay (counting a
    hit) instead of sleeping, so the egress can apply it deadline-aware
    (deadline.sleep_within) — a slow wire must not stall a caller past
    its budget, exactly like a real socket timeout firing during a slow
    network.  0.0 when unarmed / out of scope / out of hits."""
    if not _points:
        return 0.0
    with _lock:
        p = _points.get(name)
        if not _peer_matches(p, peer):
            return 0.0
        delay = p["delay"]
        if not delay:
            return 0.0
        if p["max_hits"] and p["hits"] >= p["max_hits"]:
            return 0.0
        p["hits"] += 1
        _counts[name] = _counts.get(name, 0) + 1
        return delay


def corrupt_block(name: str, shard_id: int, data, file_offset: int = 0):
    """Data-mutation fault (ec.shard.corrupt): deterministically flip
    one bit in a shard read.  Armed with
    ``enable(name, params={"shard": id, "offset": byte, "bit": 0-7})``,
    any read of `shard_id` whose [file_offset, file_offset+len) range
    covers `offset` comes back with that bit flipped — exactly what
    on-media bit rot looks like to the reader.  Returns `data` untouched
    when unarmed or out of range; counts a hit only when it flips.
    Accepts bytes or a 1-D uint8 ndarray (flipped in place when
    writable, else on a copy)."""
    if not _points:
        return data
    with _lock:
        p = _points.get(name)
        prm = p.get("params") if p is not None else None
        if not prm or int(prm.get("shard", -1)) != shard_id:
            return data
        if p["max_hits"] and p["hits"] >= p["max_hits"]:
            return data
        target = int(prm.get("offset", 0))
        if not (file_offset <= target < file_offset + len(data)):
            return data
        p["hits"] += 1
        _counts[name] = _counts.get(name, 0) + 1
        bit = int(prm.get("bit", 0)) & 7
    rel = target - file_offset
    if isinstance(data, (bytes, bytearray, memoryview)):
        buf = bytearray(data)
        buf[rel] ^= 1 << bit
        return bytes(buf)
    arr = data if getattr(data.flags, "writeable", False) else data.copy()
    arr[rel] ^= 1 << bit
    return arr
