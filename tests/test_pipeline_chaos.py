"""Chaos drills for the self-healing EC pipeline AND the cluster-level
rebuild/rebalance coordinator.

Process-level contract (ec/overlap.py supervision + ec/streaming.py
per-dispatch retry/fallback): a parity worker dying, stalling, or
faulting mid-encode must NEVER surface as a caller-visible error — the
supervisor respawns the worker and replays in-flight dispatches, and
when the restart budget is exhausted the encode degrades per-dispatch to
the CPU codec and still completes with byte-identical parity.  Faults
are driven two ways: deterministically through the ec.* fault points
(utils/faultinject), and with a real SIGKILL of the worker process.
These drills need the native gf256 engine (overlap workers) and skip
without it.

Cluster-level contract (ops/coordinator.py, TestCoordinatorChaos): with
the coordinator enabled and NO manual intervention, corrupting shards
on two racks, killing a volume server mid-rebuild, or joining a fresh
server must each converge autonomously — every EC volume back to a full
clean shard set, rack diversity respected, no orphan shards — and the
journaled repair events must prove the coordinator reacted to the fired
alert (alert id + causing trace id on every action), not to a test
back-channel.  These drills run on the CPU codec everywhere.

Health is observable: SeaweedFS_ec_worker_restarts_total and
SeaweedFS_ec_engine_fallbacks_total counters, pipeline.retry /
pipeline.fallback spans, and per-call stats (retries / fallbacks /
worker_restarts).
"""

from __future__ import annotations

import os
import signal
import threading
import time

import numpy as np
import pytest

from seaweedfs_tpu.ec import encoder
from seaweedfs_tpu.ec.codec import CpuEngine, ReedSolomon, best_cpu_engine
from seaweedfs_tpu.ec.layout import to_ext
from seaweedfs_tpu.ec.streaming import StreamingEncoder
from seaweedfs_tpu.observability import disable_tracing, enable_tracing
from seaweedfs_tpu.stats import ec_pipeline_metrics
from seaweedfs_tpu.utils import faultinject as fi

from seaweedfs_tpu import native

# the worker drills need the native engine; the coordinator cluster
# drills below run everywhere (CPU codec)
needs_native = pytest.mark.skipif(
    native.load() is None,
    reason="native gf256 engine unavailable: no overlap workers")

K, R, TOTAL = 10, 4, 14
LARGE, SMALL = 100 << 20, 1 << 20  # default small rows for a 64MB volume
SIZE = 64 << 20  # acceptance floor: streaming encode of >= 64MB


def _shards(base: str) -> list[bytes]:
    return [open(base + to_ext(i), "rb").read() for i in range(TOTAL)]


@pytest.fixture(scope="module")
def volume(tmp_path_factory):
    """One 64MB volume + its single-threaded CPU-codec reference shards,
    shared by every drill (the encodes under test write elsewhere)."""
    td = tmp_path_factory.mktemp("chaos")
    base = str(td / "v")
    rng = np.random.default_rng(0xC4A05)
    with open(base + ".dat", "wb") as f:
        for _ in range(SIZE // (8 << 20)):
            f.write(rng.integers(0, 256, 8 << 20, dtype=np.uint8).tobytes())
    encoder.write_ec_files(
        base, ReedSolomon(K, R, engine=best_cpu_engine()),
        large_block_size=LARGE, small_block_size=SMALL)
    return td, base, _shards(base)


@pytest.fixture()
def tracer():
    tr = enable_tracing()
    tr.clear()
    try:
        yield tr
    finally:
        disable_tracing()
        tr.clear()


def _staged_encoder(**kw) -> StreamingEncoder:
    enc = StreamingEncoder(K, R, engine="host", overlap="process",
                           dispatch_mb=1, **kw)
    return enc


def _close(enc: StreamingEncoder) -> None:
    if enc._proc_worker is not None:
        enc._proc_worker.close()
        enc._proc_worker = None


@needs_native
def test_ack_fault_respawns_worker_byte_identical(volume, tracer):
    """ec.worker.ack armed: the supervisor SIGKILLs and respawns the
    real worker process, replays in-flight dispatches, and the encode
    completes without caller-visible error, byte-identical."""
    td, base, ref = volume
    m = ec_pipeline_metrics()
    r0 = m.worker_restarts.value("staged")
    enc = _staged_encoder()
    out = str(td / "ack")
    fi.enable("ec.worker.ack", error_rate=1.0, max_hits=2)
    try:
        enc.encode_file(base + ".dat", out,
                        large_block_size=LARGE, small_block_size=SMALL)
    finally:
        fi.clear()
        _close(enc)
    assert _shards(out) == ref
    delta = m.worker_restarts.value("staged") - r0
    assert delta >= 1  # SeaweedFS_ec_worker_restarts_total > 0
    assert enc.stats["worker_restarts"] >= 1
    # supervision is visible as pipeline.retry spans, not drain-wait
    retries = [s for s in tracer.snapshot() if s.name == "pipeline.retry"]
    assert retries and retries[0].attrs["kind"] == "staged"
    # and on the Prometheus exposition under the contract name
    from seaweedfs_tpu.stats import REGISTRY

    assert "SeaweedFS_ec_worker_restarts_total" in REGISTRY.expose()


@needs_native
def test_sigkill_worker_mid_encode_completes(volume):
    """A real os.kill(SIGKILL) of the parity worker mid-encode: the
    bounded ack read detects the death, the supervisor respawns and
    replays, the encode completes byte-identical."""
    td, base, ref = volume
    m = ec_pipeline_metrics()
    r0 = m.worker_restarts.value("staged")
    enc = _staged_encoder()
    out = str(td / "kill")
    err: list = []
    done = threading.Event()

    def run():
        try:
            # drain delay stretches the encode so the kill lands inside:
            # 7 dispatches at 0.05 s outlast the 0.1 s before the kill
            # on an idle host too (at 0.01 s an unloaded encode could
            # finish first, and no restart was ever seen)
            fi.enable("ec.drain", delay=0.05)
            enc.encode_file(base + ".dat", out,
                            large_block_size=LARGE, small_block_size=SMALL)
        except Exception as e:  # pragma: no cover - the drill's failure
            err.append(e)
        finally:
            fi.clear()
            done.set()

    t = threading.Thread(target=run)
    t.start()
    try:
        deadline = time.monotonic() + 30
        pid = 0
        while time.monotonic() < deadline and not pid:
            w = enc._proc_worker
            pid = getattr(w, "worker_pid", 0) if w is not None else 0
            time.sleep(0.005)
        assert pid, "worker never came up"
        time.sleep(0.1)  # let some dispatches get in flight
        try:
            os.kill(pid, signal.SIGKILL)
        except ProcessLookupError:  # pragma: no cover - already respawned
            pass
        t.join(180)
    finally:
        fi.clear()
        _close(enc)
    assert done.is_set() and not err, err
    assert _shards(out) == ref
    assert m.worker_restarts.value("staged") - r0 >= 1


@needs_native
def test_budget_exhausted_finishes_via_cpu_fallback(volume, tracer):
    """Restart budget 0 + one injected ack fault: the worker path gives
    up immediately and the encode finishes mid-stream on the CPU codec —
    byte-identical, with SeaweedFS_ec_engine_fallbacks_total > 0."""
    td, base, ref = volume
    m = ec_pipeline_metrics()
    f0 = sum(m.engine_fallbacks.snapshot().values())
    enc = _staged_encoder(max_worker_restarts=0)
    out = str(td / "gaveup")
    fi.enable("ec.worker.ack", error_rate=1.0, max_hits=1)
    try:
        enc.encode_file(base + ".dat", out,
                        large_block_size=LARGE, small_block_size=SMALL)
    finally:
        fi.clear()
        _close(enc)
    assert _shards(out) == ref
    assert sum(m.engine_fallbacks.snapshot().values()) - f0 > 0
    assert enc.stats["fallbacks"] > 0
    names = {s.name for s in tracer.snapshot()}
    assert "pipeline.fallback" in names
    from seaweedfs_tpu.stats import REGISTRY

    assert "SeaweedFS_ec_engine_fallbacks_total" in REGISTRY.expose()


@needs_native
def test_dispatch_and_drain_faults_fall_back_per_dispatch(tmp_path):
    """One-shot ec.dispatch / ec.drain faults degrade exactly the hit
    dispatches to the CPU codec; the worker stays alive and keeps the
    rest of the encode."""
    base = str(tmp_path / "v")
    rng = np.random.default_rng(7)
    open(base + ".dat", "wb").write(
        rng.integers(0, 256, 3_200_000, dtype=np.uint8).tobytes())
    encoder.write_ec_files(base, ReedSolomon(K, R, engine=CpuEngine()),
                           large_block_size=100_000, small_block_size=10_000)
    ref = _shards(base)
    enc = _staged_encoder()
    enc.dispatch_b = 65536
    out = str(tmp_path / "o")
    fi.enable("ec.dispatch", error_rate=1.0, max_hits=1)
    fi.enable("ec.drain", error_rate=1.0, max_hits=1)
    try:
        enc.encode_file(base + ".dat", out,
                        large_block_size=100_000, small_block_size=10_000)
        alive = enc._proc_worker is not None
    finally:
        fi.clear()
        _close(enc)
    assert _shards(out) == ref
    assert enc.stats["fallbacks"] == 2
    assert alive  # per-dispatch fallback, not whole-pipeline degradation


@needs_native
def test_mmap_worker_sigkill_respawns_and_replays(tmp_path):
    """The zero-copy mmap path's FileParityWorker: a real SIGKILL mid-
    encode respawns the worker (which re-opens the input file) and the
    shards stay byte-identical."""
    base = str(tmp_path / "v")
    rng = np.random.default_rng(8)
    open(base + ".dat", "wb").write(
        rng.integers(0, 256, 8_000_000, dtype=np.uint8).tobytes())
    encoder.write_ec_files(base, ReedSolomon(K, R, engine=CpuEngine()),
                           large_block_size=200_000, small_block_size=20_000)
    ref = _shards(base)
    m = ec_pipeline_metrics()
    r0 = m.worker_restarts.value("mmap")
    enc = StreamingEncoder(K, R, engine="host", overlap="mmap-process",
                           dispatch_mb=1, max_worker_restarts=5)
    enc.dispatch_b = 65536
    out = str(tmp_path / "o")
    err: list = []
    done = threading.Event()

    def run():
        try:
            fi.enable("ec.drain", delay=0.01)
            enc.encode_file(base + ".dat", out,
                            large_block_size=200_000,
                            small_block_size=20_000)
        except Exception as e:  # pragma: no cover
            err.append(e)
        finally:
            fi.clear()
            done.set()

    t = threading.Thread(target=run)
    t.start()
    try:
        deadline = time.monotonic() + 30
        pid = 0
        while time.monotonic() < deadline and not pid:
            w = enc._file_worker
            pid = getattr(w, "worker_pid", 0) if w else 0
            time.sleep(0.005)
        assert pid, "file worker never came up"
        time.sleep(0.1)
        try:
            os.kill(pid, signal.SIGKILL)
        except ProcessLookupError:  # pragma: no cover
            pass
        t.join(180)
    finally:
        fi.clear()
        enc._drop_file_worker()
    assert done.is_set() and not err, err
    assert _shards(out) == ref
    assert m.worker_restarts.value("mmap") - r0 >= 1


@needs_native
def test_mid_encode_failure_resumes_from_checkpoint(tmp_path, tracer,
                                                    monkeypatch):
    """A fill-phase IO error mid-encode retries the call, RESUMING from
    the last drained-and-written dispatch instead of byte 0 — and the
    resumed output is byte-identical to a clean encode."""
    import seaweedfs_tpu.ec.streaming as streaming_mod

    base = str(tmp_path / "v")
    rng = np.random.default_rng(9)
    open(base + ".dat", "wb").write(
        rng.integers(0, 256, 2_000_000, dtype=np.uint8).tobytes())
    encoder.write_ec_files(base, ReedSolomon(K, R, engine=CpuEngine()),
                           large_block_size=1_000_000,
                           small_block_size=10_000)
    ref = _shards(base)
    real = streaming_mod.preadv_into
    calls = {"n": 0}

    def flaky(f, views, off):
        calls["n"] += 1
        if calls["n"] == 15:
            raise OSError("injected fill IO error")
        return real(f, views, off)

    monkeypatch.setattr(streaming_mod, "preadv_into", flaky)
    # large=1MB keeps every row a small 10_000-byte block (uniform
    # entries), depth=1 drains early so the checkpoint has advanced
    # past byte 0 when the 15th fill (dispatch 2) faults
    enc = StreamingEncoder(K, R, engine="host", zero_copy=False,
                           overlap="none", dispatch_mb=1, depth=1)
    enc.dispatch_b = 65536
    out = str(tmp_path / "o")
    enc.encode_file(base + ".dat", out,
                    large_block_size=1_000_000, small_block_size=10_000)
    assert _shards(out) == ref
    assert enc.stats["retries"] == 1
    retries = [s for s in tracer.snapshot()
               if s.name == "pipeline.retry"
               and s.attrs.get("scope") == "encode_file"]
    assert retries and retries[0].attrs["resume_byte"] > 0


@needs_native
def test_staged_resume_entrypoint_is_byte_exact(tmp_path):
    """The resume machinery itself: corrupt every shard past a dispatch
    boundary, re-enter _encode_file_staged at that checkpoint, and the
    repaired shards must match a clean encode bit-for-bit (dispatch
    packing after a resume may differ; bytes may not)."""
    base = str(tmp_path / "v")
    rng = np.random.default_rng(10)
    open(base + ".dat", "wb").write(
        rng.integers(0, 256, 1_500_000, dtype=np.uint8).tobytes())
    enc = StreamingEncoder(K, R, engine="host", zero_copy=False,
                           overlap="none", dispatch_mb=1)
    enc.dispatch_b = 65536
    out = str(tmp_path / "o")
    # large=1MB keeps every plan entry a whole small block: entry e is
    # exactly shard bytes [e*10_000, (e+1)*10_000)
    enc.encode_file(base + ".dat", out,
                    large_block_size=1_000_000, small_block_size=10_000)
    ref = _shards(out)
    # entries are whole 10_000-byte small blocks: entry e ends at byte
    # (e+1)*10_000 on every shard — pick a mid-file checkpoint and wreck
    # everything past it
    ck_entry, ck_byte = 7, 7 * 10_000
    for i in range(TOTAL):
        with open(out + to_ext(i), "r+b") as f:
            f.seek(ck_byte)
            tail = len(f.read())
            f.seek(ck_byte)
            f.write(b"\xAA" * tail)
    enc._encode_file_staged(base + ".dat", out, 1_000_000, 10_000,
                            start_entry=ck_entry, start_byte=ck_byte)
    assert _shards(out) == ref


@needs_native
def test_async_drain_deep_buffers_byte_identical(tmp_path):
    """The async multi-buffered drain at depth=4 (5 slots in flight),
    staged-process AND mmap-process: FIFO writer order must keep shards
    and the write-order-crc `.eci` sidecar byte-identical to the CPU
    reference while fetch/write run off the critical thread."""
    from seaweedfs_tpu.ec.integrity import sidecar_path

    base = str(tmp_path / "v")
    rng = np.random.default_rng(12)
    open(base + ".dat", "wb").write(
        rng.integers(0, 256, 6_000_000, dtype=np.uint8).tobytes())
    encoder.write_ec_files(base, ReedSolomon(K, R, engine=CpuEngine()),
                           large_block_size=200_000, small_block_size=20_000)
    ref = _shards(base)
    ref_eci = open(sidecar_path(base), "rb").read()
    for name, overlap in (("st", "process"), ("mm", "mmap-process")):
        enc = StreamingEncoder(K, R, engine="host", overlap=overlap,
                               dispatch_mb=1, depth=4)
        enc.dispatch_b = 65536
        out = str(tmp_path / name)
        try:
            enc.encode_file(base + ".dat", out,
                            large_block_size=200_000,
                            small_block_size=20_000)
        finally:
            _close(enc)
            enc._drop_file_worker()
        assert _shards(out) == ref, overlap
        assert open(sidecar_path(out), "rb").read() == ref_eci, overlap
        assert enc.stats["drain_pool"] >= 1, overlap
        assert enc.stats["parity_bytes_drained"] > 0, overlap
        assert enc.stats["fallbacks"] == 0, overlap


@needs_native
def test_worker_kill_while_drain_queue_full(volume):
    """SIGKILL the parity worker while the async drain queue is FULL
    (slow drainer via ec.drain delay keeps every slot in flight): the
    drainer-side supervisor respawns, replays the whole in-flight
    window, and the FIFO writer keeps the output byte-identical."""
    td, base, ref = volume
    m = ec_pipeline_metrics()
    r0 = m.worker_restarts.value("staged")
    enc = _staged_encoder(depth=3, max_worker_restarts=5)
    out = str(td / "killfull")
    err: list = []
    done = threading.Event()

    def run():
        try:
            # the throttled drainer keeps every slot in flight, so the
            # producer is still being paced by slot backpressure (jobs
            # still outstanding past the worker) when the kill lands
            fi.enable("ec.drain", delay=0.08)
            enc.encode_file(base + ".dat", out,
                            large_block_size=LARGE, small_block_size=SMALL)
        except Exception as e:  # pragma: no cover - the drill's failure
            err.append(e)
        finally:
            fi.clear()
            done.set()

    t = threading.Thread(target=run)
    t.start()
    try:
        deadline = time.monotonic() + 30
        pid = 0
        while time.monotonic() < deadline and not pid:
            w = enc._proc_worker
            pid = getattr(w, "worker_pid", 0) if w is not None else 0
            time.sleep(0.005)
        assert pid, "worker never came up"
        time.sleep(0.12)  # queue full, later submissions still pending
        try:
            os.kill(pid, signal.SIGKILL)
        except ProcessLookupError:  # pragma: no cover - already respawned
            pass
        t.join(180)
    finally:
        fi.clear()
        _close(enc)
    assert done.is_set() and not err, err
    assert _shards(out) == ref
    assert m.worker_restarts.value("staged") - r0 >= 1


@needs_native
def test_worker_err_ack_recomputes_without_killing_worker(tmp_path):
    """A job that fails INSIDE a live worker is acked ("err", seq) and
    surfaces as WorkerJobError: that dispatch recomputes serially, the
    worker survives, no respawn is burned."""
    from seaweedfs_tpu.ec.overlap import FileParityWorker, WorkerJobError

    rs = ReedSolomon(K, R)
    w = FileParityWorker(K, R, 4096, rs.matrix[K:], nbufs=2,
                         restart_backoff=0.01)
    try:
        p = str(tmp_path / "in.bin")
        rng = np.random.default_rng(11)
        open(p, "wb").write(
            rng.integers(0, 256, K * 4096, dtype=np.uint8).tobytes())
        w.open(p)
        # a poisoned job payload: the worker's slot arithmetic raises a
        # Python-level error -> job-level err ack, not process death
        w.submit("poison", 0, 4096, 4096)
        with pytest.raises(WorkerJobError):
            w.fetch(0)
        # the SAME worker incarnation keeps serving
        pid = w.worker_pid
        w.submit(1, 0, 4096, 4096)
        parity = w.fetch(1)
        data = np.fromfile(p, dtype=np.uint8).reshape(K, 4096)
        want = CpuEngine().matmul(np.ascontiguousarray(rs.matrix[K:]), data)
        assert np.array_equal(parity, want)
        assert w.worker_pid == pid and w.restarts == 0
    finally:
        w.close()


# --- cluster-level coordinator chaos drills --------------------------------
# (ops/coordinator.py; CPU codec — no native engine needed)

def _mk_coord_cluster(tmp_path, racks):
    """Master with the coordinator ENABLED (fast cadences, paused for
    deterministic setup) + one volume server per rack name."""
    from seaweedfs_tpu.master.server import MasterServer
    from seaweedfs_tpu.volume_server.server import VolumeServer
    from tests.conftest import free_port

    master = MasterServer(port=free_port(), pulse_seconds=0.3,
                          metrics_aggregation_seconds=0.2,
                          coordinator_seconds=0.3).start()
    master.aggregator.min_interval = 0.0
    master.alert_engine.min_interval = 0.0
    master.coordinator.pause("setup")
    master.coordinator.move_rate = 100.0  # tests: budget never the wall
    servers = []
    for i, rack in enumerate(racks):
        d = tmp_path / f"vs{i}"
        d.mkdir()
        servers.append(VolumeServer(
            [str(d)], master.url, port=free_port(), rack=rack,
            data_center="dc1", pulse_seconds=0.3).start())
    deadline = time.time() + 10
    while time.time() < deadline and \
            len(master.topo.all_nodes()) < len(servers):
        time.sleep(0.05)
    assert len(master.topo.all_nodes()) == len(servers)
    return master, servers


def _make_ec_volume(vs, needles=40):
    from seaweedfs_tpu.storage.needle import Needle

    v = vs.store.add_volume(1)
    rng = np.random.default_rng(0xEC)
    for i in range(1, needles + 1):
        v.write_needle(Needle(cookie=i, id=i,
                              data=rng.bytes(400 + i * 13)))
    vs.store.ec_generate(1)
    vs.store.ec_mount(1)


def _spread_shards(servers, layout):
    """Place volume 1's shards per {server index: [shard ids]} with real
    cross-server /admin/ec/copy legs (sidecar rides along)."""
    from seaweedfs_tpu.utils.httpd import http_json

    src = servers[0]
    for i, sids in layout.items():
        if i == 0:
            continue
        http_json("POST", f"http://{servers[i].url}/admin/ec/copy",
                  {"volume_id": 1, "shard_ids": sids,
                   "source_data_node": src.url})
        http_json("POST", f"http://{servers[i].url}/admin/ec/mount",
                  {"volume_id": 1})
    keep = layout.get(0, [])
    drop = [s for s in range(TOTAL) if s not in keep]
    if drop:
        http_json("POST", f"http://{src.url}/admin/ec/delete",
                  {"volume_id": 1, "shard_ids": drop})
        if keep:
            http_json("POST", f"http://{src.url}/admin/ec/mount",
                      {"volume_id": 1})
    http_json("POST", f"http://{src.url}/admin/delete_volume",
              {"volume_id": 1})
    for vs in servers:
        vs.heartbeat_now()


def _registry_shards(master):
    with master.topo.lock:
        locs = master.topo.ec_shard_locations.get(1, {})
        return {sid: [n.url for n in nodes]
                for sid, nodes in locs.items() if nodes}


def _wait(cond, timeout, what):
    deadline = time.time() + timeout
    while time.time() < deadline:
        v = cond()
        if v:
            return v
        time.sleep(0.1)
    raise AssertionError(f"timed out waiting for {what}")


def _scrub_once(vs):
    from seaweedfs_tpu.utils.httpd import http_json

    http_json("POST", f"http://{vs.url}/ec/scrub/start",
              {"rate_mb_s": 0, "interval_s": 0})
    _wait(lambda: not http_json(
        "GET", f"http://{vs.url}/ec/scrub/status")["running"],
        20, f"scrub on {vs.url}")


def test_coordinator_heals_corruption_on_two_racks(tmp_path, tracer):
    """The acceptance drill: rot shards on TWO racks, let the scrubbers
    quarantine them (locally unrepairable — each holder has < k local
    shards), and assert the coordinator — triggered by the FIRED alert,
    with no manual intervention — rebuilds cross-server until every
    shard has a clean holder again, journaling the alert id and causing
    trace id on the repair."""
    from seaweedfs_tpu.utils.httpd import http_json

    master, servers = _mk_coord_cluster(
        tmp_path, ["r0", "r0", "r1", "r1"])
    try:
        _make_ec_volume(servers[0])
        _spread_shards(servers, {0: [0, 1, 2, 3], 1: [4, 5, 6],
                                 2: [7, 8, 9, 10], 3: [11, 12, 13]})
        _wait(lambda: len(_registry_shards(master)) == TOTAL, 10,
              "registry to see the spread")
        # counter baselines established before the injection
        _wait(lambda: master.alert_engine.evaluations > 0, 10,
              "first alert evaluation")
        # shard 2 rots on rack r0, shard 8 on rack r1
        for vs, sid in ((servers[0], 2), (servers[2], 8)):
            fi.enable("ec.shard.corrupt",
                      params={"shard": sid, "offset": 0, "bit": 3},
                      max_hits=1)
            _scrub_once(vs)
        fi.clear()
        _wait(lambda: set(_registry_shards(master)) ==
              set(range(TOTAL)) - {2, 8}, 15,
              "quarantined shards to leave the registry")
        # the alert fires autonomously BEFORE the coordinator may act
        firing = _wait(lambda: {
            a["name"] for a in master.alert_engine.to_dict()["alerts"]
            if a["state"] == "firing"} or None, 20, "a firing alert")
        assert firing & {"scrub_unrepairable",
                         "corrupt_shards_increase"}, firing
        master.coordinator.resume()
        # autonomous convergence: all 14 shards, exactly one holder each
        _wait(lambda: set(_registry_shards(master)) ==
              set(range(TOTAL)), 30, "repair to restore all shards")
        _wait(lambda: all(len(u) == 1
                          for u in _registry_shards(master).values()),
              15, "single holder per shard (no orphans)")
        # rack diversity respected — the repair's spread aims for it,
        # and the continuous rebalance pass mops up any placement the
        # spread made against a lagging registry view, so poll
        from seaweedfs_tpu.ops.coordinator import (rack_ceiling,
                                                   view_from_topology)

        def racks_ok():
            view = view_from_topology(master.topo)
            return all(c <= rack_ceiling(view)
                       for c in view.rack_counts(1).values())
        _wait(racks_ok, 20, "rack diversity to converge")
        # the journaled repair carries the alert id and the causing
        # trace id — the proof it reacted to the signal plane, not a
        # test back-channel (the event rides the shipper's flush)
        try:
            evs = _wait(lambda: http_json(
                "GET", f"http://{master.url}/cluster/events"
                       "?type=repair_done&limit=10")["events"] or None,
                10, "repair_done to reach the cluster journal")
        except AssertionError:
            from seaweedfs_tpu.observability import events as _ev

            raise AssertionError(
                "repair_done never reached the cluster journal; "
                f"coordinator={master.coordinator.status()!r} "
                f"global_journal_repairs="
                f"{_ev.get_journal().query(type_='repair_done')!r}")
        d = evs[-1]["details"]
        assert d["vid"] == 1
        assert d["alert"] in firing, d
        unrep = http_json(
            "GET", f"http://{master.url}/cluster/events"
                   "?type=scrub_unrepairable&limit=10")["events"]
        scrub_traces = {e.get("trace", "") for e in unrep}
        assert d["cause_trace"] in scrub_traces and d["cause_trace"]
        # the repair itself ran under its own (stitchable) trace
        assert len(evs[-1].get("trace", "")) == 32
        # and the fired alert auto-captured flight-recorder evidence
        alerts = {a["name"]: a
                  for a in master.alert_engine.to_dict()["alerts"]}
        fired = [alerts[n] for n in firing
                 if alerts[n].get("fired_at")]
        assert fired and all(a["fired_at"] <= evs[-1]["ts"]
                             for a in fired)
    finally:
        fi.clear()
        for vs in servers:
            vs.stop()
        master.stop()


def test_coordinator_replans_after_server_death_mid_rebuild(tmp_path,
                                                            tracer):
    """Kill a volume server mid-rebuild: the first repair attempt fails
    (injected coord.exec fault) and is re-queued; the server holding
    three survivors then dies; the re-planned repair works around the
    dead holder (skips its survivors, regenerates them) and converges
    with no orphan shards on any live server's disk."""
    master, servers = _mk_coord_cluster(
        tmp_path, ["r0", "r0", "r1", "r1", "r2"])
    try:
        _make_ec_volume(servers[0])
        _spread_shards(servers, {0: [0, 1, 2], 1: [3, 4, 5],
                                 2: [6, 7, 8], 3: [9, 10, 11],
                                 4: [12, 13]})
        _wait(lambda: len(_registry_shards(master)) == TOTAL, 10,
              "registry to see the spread")
        # zero the move budget: this drill asserts exact disk layouts,
        # so background rebalance churn is held off
        master.coordinator.move_rate = 0.0
        master.coordinator.move_burst = 0.0
        master.coordinator._tokens = 0.0
        # lose shard 13 so the coordinator has a repair to run, and
        # arm the execution fault across the whole first attempt: all
        # 10 survivor copies to the rebuild host fail (a single
        # injected step failure is absorbed by the per-holder fallback
        # — by design), so the attempt dies mid-plan and is re-queued
        from seaweedfs_tpu.utils.httpd import http_json

        http_json("POST", f"http://{servers[4].url}/admin/ec/delete",
                  {"volume_id": 1, "shard_ids": [13]})
        servers[4].heartbeat_now()
        fi.enable("coord.exec", error_rate=1.0, max_hits=10)
        master.coordinator.resume()
        _wait(lambda: http_json(
            "GET", f"http://{master.url}/cluster/events"
                   "?type=repair_failed&limit=5")["events"], 20,
            "the injected mid-rebuild failure")
        assert fi.fired("coord.exec") >= 1
        # the server holding survivors 3,4,5 dies before the re-plan
        servers[1].stop()
        _wait(lambda: set(_registry_shards(master)) ==
              set(range(TOTAL)), 60,
              "re-planned repair to restore all shards")
        _wait(lambda: all(len(u) == 1
                          for u in _registry_shards(master).values()),
              20, "single holder per shard")
        reg = _registry_shards(master)
        assert not any(servers[1].url in urls for urls in reg.values())
        # no orphan shard files: every live server's disk holds exactly
        # what the registry says it holds (poll — a snapshot taken
        # while a move is mid-flight may transiently disagree)
        import glob as _glob

        from seaweedfs_tpu.storage.volume import volume_file_prefix

        def _disk_matches_registry():
            r = _registry_shards(master)
            for i, vs in enumerate(servers):
                if i == 1:
                    continue
                base = volume_file_prefix(
                    vs.store.locations[0].directory, "", 1)
                on_disk = {int(p[-2:]) for p in
                           _glob.glob(base + ".ec[0-9][0-9]")}
                in_reg = {sid for sid, urls in r.items()
                          if vs.url in urls}
                if on_disk != in_reg:
                    return None
            return True
        try:
            _wait(_disk_matches_registry, 15, "disk == registry")
        except AssertionError:
            raise AssertionError(
                "orphan shards: disk != registry; recent="
                f"{master.coordinator.status()['recent']!r}")
    finally:
        fi.clear()
        for i, vs in enumerate(servers):
            if i != 1:
                vs.stop()
        master.stop()


def test_fresh_server_join_triggers_rack_aware_rebalance(tmp_path):
    """Join a fresh server on a NEW rack: the running coordinator's
    continuous rebalance pass notices (shard-count skew + rack
    diversity now improvable), moves shards within the token budget,
    and CONVERGES — repeated cycles stop producing moves."""
    master, servers = _mk_coord_cluster(tmp_path, ["r0", "r1"])
    try:
        _make_ec_volume(servers[0])
        _spread_shards(servers, {0: [0, 1, 2, 3, 4, 5, 6],
                                 1: [7, 8, 9, 10, 11, 12, 13]})
        _wait(lambda: len(_registry_shards(master)) == TOTAL, 10,
              "registry to see the spread")
        master.coordinator.resume()
        # 7/7 over two racks is stable: no spurious churn
        time.sleep(1.5)
        assert master.coordinator.status()["moves"] == 0
        # a fresh server joins on a third rack
        from seaweedfs_tpu.volume_server.server import VolumeServer
        from tests.conftest import free_port

        d = tmp_path / "vs-new"
        d.mkdir()
        fresh = VolumeServer([str(d)], master.url, port=free_port(),
                             rack="r2", data_center="dc1",
                             pulse_seconds=0.3).start()
        servers.append(fresh)
        _wait(lambda: master.coordinator.status()["moves"] > 0, 30,
              "rebalance moves after the join")
        # convergence: the move count stops growing
        def settled():
            a = master.coordinator.status()["moves"]
            time.sleep(1.2)
            return a == master.coordinator.status()["moves"]
        _wait(settled, 45, "rebalance to converge")
        reg = _registry_shards(master)
        assert set(reg) == set(range(TOTAL))
        assert all(len(u) == 1 for u in reg.values())
        # the fresh rack carries real load now, within the ceiling
        from seaweedfs_tpu.ops.coordinator import (rack_ceiling,
                                                   view_from_topology)

        view = view_from_topology(master.topo)
        counts = view.rack_counts(1)
        assert counts.get(("dc1", "r2"), 0) >= 2
        assert all(c <= rack_ceiling(view) for c in counts.values())
        # journaled, attributed moves
        from seaweedfs_tpu.utils.httpd import http_json

        evs = http_json("GET", f"http://{master.url}/cluster/events"
                               "?type=rebalance_move&limit=50")["events"]
        assert evs and all(e["details"]["reason"] in
                           ("rack", "skew", "dedupe") for e in evs)
    finally:
        for vs in servers:
            vs.stop()
        master.stop()


# --- tier two-phase SIGKILL chaos drills -----------------------------------
# (storage/volume.py tier protocol; real subprocess volume servers so
# the kill -9 exercises the on-disk manifest recovery, not a mock)

def _tier_http(method, url, data=None, timeout=10):
    import urllib.error
    import urllib.request

    req = urllib.request.Request(url, data=data, method=method)
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, r.read()
    except urllib.error.HTTPError as e:
        return e.code, e.read()


def _spawn_tier_vs(vdir, port, mport, remote, faults=""):
    import subprocess
    import sys

    env = dict(os.environ, PYTHONPATH="/root/repo")
    if faults:
        env["WEED_FAULTS"] = faults
    else:
        env.pop("WEED_FAULTS", None)
    return subprocess.Popen(
        [sys.executable, "/root/repo/weed.py", "volume",
         "-dir", vdir, "-port", str(port),
         "-mserver", f"127.0.0.1:{mport}",
         "-tier.backends", f"chaos={remote}"],
        env=env, stdout=subprocess.DEVNULL, stderr=subprocess.STDOUT)


def _wait_vs_up(port, deadline_s=20):
    deadline = time.time() + deadline_s
    while time.time() < deadline:
        try:
            st, _ = _tier_http(
                "GET", f"http://127.0.0.1:{port}/status", timeout=2)
            if st == 200:
                return
        except OSError:
            time.sleep(0.1)
    raise RuntimeError("volume server did not come up")


def _remote_objects(remote):
    return sorted(f for f in os.listdir(remote)
                  if os.path.isfile(os.path.join(remote, f)))


@pytest.fixture()
def tier_chaos_cluster(tmp_path):
    """Subprocess master + volume server with a dir tier backend rooted
    in tmp, volume 1 preloaded with verifiable needles."""
    import json
    import subprocess
    import sys

    from tests.conftest import free_port

    env = dict(os.environ, PYTHONPATH="/root/repo")
    mport, vport = free_port(), free_port()
    remote = str(tmp_path / "remote")
    os.mkdir(remote)
    vdir = str(tmp_path / "v")
    master = subprocess.Popen(
        [sys.executable, "/root/repo/weed.py", "master",
         "-port", str(mport), "-mdir", str(tmp_path / "m")],
        env=env, stdout=subprocess.DEVNULL, stderr=subprocess.STDOUT)
    vs = _spawn_tier_vs(vdir, vport, mport, remote)
    state = {"vs": vs}
    payloads: dict[str, bytes] = {}
    try:
        _wait_vs_up(vport)
        st, _ = _tier_http(
            "POST", f"http://127.0.0.1:{vport}/admin/assign_volume",
            json.dumps({"volume_id": 1}).encode())
        assert st == 200
        rng = np.random.default_rng(0x71E4)
        for i in range(1, 41):
            fid = f"1,{i:08x}000000aa"
            payloads[fid] = rng.bytes(500 + i * 37)
            st, _ = _tier_http(
                "POST", f"http://127.0.0.1:{vport}/{fid}",
                payloads[fid])
            assert st in (200, 201)
        yield state, vport, mport, vdir, remote, payloads
    finally:
        for p in (state["vs"], master):
            p.terminate()
        for p in (state["vs"], master):
            try:
                p.wait(timeout=5)
            except Exception:
                p.kill()


def _assert_byte_identical(vport, payloads):
    for fid, payload in payloads.items():
        st, body = _tier_http("GET", f"http://127.0.0.1:{vport}/{fid}")
        assert st == 200, f"{fid} lost: {st}"
        assert body == payload, f"{fid} corrupt"


def test_sigkill_mid_tier_upload_no_needle_loss(tier_chaos_cluster):
    """kill -9 in BOTH pre-commit windows of the two-phase upload:
    (a) mid-upload — the tier.upload fault (armed via WEED_FAULTS in
    the child) holds the server inside the upload with the manifest on
    disk; (b) uploaded-but-uncommitted — the verified remote copy
    exists, the commit was never issued.  After each restart the local
    .dat is still authoritative (every read byte-identical), the
    manifest is GC'd, and no orphan remote object survives."""
    import glob as _glob
    import json
    import threading as _threading

    state, vport, mport, vdir, remote, payloads = tier_chaos_cluster

    # (a) respawn with the fault armed: upload stalls AT the fault,
    # manifest `uploading` on disk, zero remote bytes sent
    state["vs"].send_signal(signal.SIGKILL)
    state["vs"].wait(timeout=5)
    state["vs"] = _spawn_tier_vs(vdir, vport, mport, remote,
                                 faults="tier.upload:delay=20")
    _wait_vs_up(vport)
    _tier_http("POST", f"http://127.0.0.1:{vport}/admin/mount",
               json.dumps({"volume_id": 1}).encode())

    def begin_upload():
        try:
            _tier_http("POST",
                       f"http://127.0.0.1:{vport}/admin/tier_upload",
                       json.dumps({"volume_id": 1, "backend": "chaos",
                                   "two_phase": True}).encode(),
                       timeout=30)
        except OSError:
            pass  # the kill lands mid-request

    t = _threading.Thread(target=begin_upload, daemon=True)
    t.start()
    deadline = time.time() + 10
    while time.time() < deadline and \
            not _glob.glob(os.path.join(vdir, "*.tier")):
        time.sleep(0.05)
    assert _glob.glob(os.path.join(vdir, "*.tier")), \
        "upload never reached the manifest write"
    state["vs"].send_signal(signal.SIGKILL)  # mid-upload
    state["vs"].wait(timeout=5)
    t.join(timeout=10)

    state["vs"] = _spawn_tier_vs(vdir, vport, mport, remote)
    _wait_vs_up(vport)
    st, _ = _tier_http("POST", f"http://127.0.0.1:{vport}/admin/mount",
                       json.dumps({"volume_id": 1}).encode())
    assert st == 200
    assert not _glob.glob(os.path.join(vdir, "*.tier"))  # manifest GC'd
    assert _glob.glob(os.path.join(vdir, "*.dat"))  # local authoritative
    assert not _remote_objects(remote)              # no orphan remote
    _assert_byte_identical(vport, payloads)

    # (b) a CLEAN two-phase upload (verified remote copy, manifest
    # `pending`, local retained) killed before the commit decision
    st, body = _tier_http(
        "POST", f"http://127.0.0.1:{vport}/admin/tier_upload",
        json.dumps({"volume_id": 1, "backend": "chaos",
                    "two_phase": True}).encode(), timeout=60)
    assert st == 200, body
    manifest = json.loads(body)["manifest"]
    assert manifest["state"] == "pending"
    assert _remote_objects(remote)                  # upload landed
    assert _glob.glob(os.path.join(vdir, "*.dat"))  # local RETAINED
    state["vs"].send_signal(signal.SIGKILL)         # pre-commit
    state["vs"].wait(timeout=5)

    state["vs"] = _spawn_tier_vs(vdir, vport, mport, remote)
    _wait_vs_up(vport)
    st, _ = _tier_http("POST", f"http://127.0.0.1:{vport}/admin/mount",
                       json.dumps({"volume_id": 1}).encode())
    assert st == 200
    assert not _remote_objects(remote)   # uncommitted upload GC'd
    assert not _glob.glob(os.path.join(vdir, "*.tier"))
    _assert_byte_identical(vport, payloads)
    # the thawed volume takes writes again
    st, _ = _tier_http("POST",
                       f"http://127.0.0.1:{vport}/1,deadbeef000000aa",
                       b"post-recovery write")
    assert st in (200, 201)


def test_sigkill_mid_tier_recall_no_needle_loss(tier_chaos_cluster):
    """Tier volume 1 fully (upload + verify + commit: local .dat gone,
    reads read-through the remote), then kill -9 mid-RECALL while the
    tier.recall fault holds the server with only a partial temp file.
    After restart the volume is still cleanly tiered (temp dropped,
    reads byte-identical through the remote), and a clean recall then
    restores the local .dat byte-identically and GCs the remote."""
    import glob as _glob
    import json
    import threading as _threading

    state, vport, mport, vdir, remote, payloads = tier_chaos_cluster

    st, body = _tier_http(
        "POST", f"http://127.0.0.1:{vport}/admin/tier_upload",
        json.dumps({"volume_id": 1, "backend": "chaos",
                    "two_phase": True}).encode(), timeout=60)
    assert st == 200, body
    st, body = _tier_http(
        "POST", f"http://127.0.0.1:{vport}/admin/tier_commit",
        json.dumps({"volume_id": 1}).encode(), timeout=60)
    assert st == 200, body
    assert not _glob.glob(os.path.join(vdir, "*.dat"))
    _assert_byte_identical(vport, payloads)  # read-through serves

    # respawn with the recall fault armed: the download stalls with
    # the manifest `recalling` and (at most) a partial .tierdl temp
    state["vs"].send_signal(signal.SIGKILL)
    state["vs"].wait(timeout=5)
    state["vs"] = _spawn_tier_vs(vdir, vport, mport, remote,
                                 faults="tier.recall:delay=20")
    _wait_vs_up(vport)
    _tier_http("POST", f"http://127.0.0.1:{vport}/admin/mount",
               json.dumps({"volume_id": 1}).encode())

    def recall():
        try:
            _tier_http("POST",
                       f"http://127.0.0.1:{vport}/admin/tier_download",
                       json.dumps({"volume_id": 1}).encode(),
                       timeout=30)
        except OSError:
            pass

    t = _threading.Thread(target=recall, daemon=True)
    t.start()
    time.sleep(1.5)  # inside the recall window
    state["vs"].send_signal(signal.SIGKILL)
    state["vs"].wait(timeout=5)
    t.join(timeout=10)

    state["vs"] = _spawn_tier_vs(vdir, vport, mport, remote)
    _wait_vs_up(vport)
    st, _ = _tier_http("POST", f"http://127.0.0.1:{vport}/admin/mount",
                       json.dumps({"volume_id": 1}).encode())
    assert st == 200
    assert not _glob.glob(os.path.join(vdir, "*.tierdl"))  # temp dropped
    assert len(_remote_objects(remote)) == 1  # committed copy intact
    _assert_byte_identical(vport, payloads)   # still read-through

    # the retried recall completes: local restored, remote GC'd
    st, body = _tier_http(
        "POST", f"http://127.0.0.1:{vport}/admin/tier_download",
        json.dumps({"volume_id": 1}).encode(), timeout=60)
    assert st == 200, body
    assert _glob.glob(os.path.join(vdir, "*.dat"))
    assert not _glob.glob(os.path.join(vdir, "*.tier"))
    assert not _remote_objects(remote)
    _assert_byte_identical(vport, payloads)
