"""Chaos drills for the self-healing EC pipeline AND the cluster-level
rebuild/rebalance coordinator.

Process-level contract (ec/streaming.py retry-from-checkpoint): a fault
mid-encode must NEVER surface as a caller-visible error — the encode
retries, resuming from the last drained-and-written dispatch, and the
output stays byte-identical.  Per-dispatch fallbacks to the CPU codec
are drilled in tests/test_ec_streaming.py (test_fault_falls_back_per_
dispatch).

Cluster-level contract (ops/coordinator.py, TestCoordinatorChaos): with
the coordinator enabled and NO manual intervention, corrupting shards
on two racks, killing a volume server mid-rebuild, or joining a fresh
server must each converge autonomously — every EC volume back to a full
clean shard set, rack diversity respected, no orphan shards — and the
journaled repair events must prove the coordinator reacted to the fired
alert (alert id + causing trace id on every action), not to a test
back-channel.  These drills run on the CPU codec everywhere.

Health is observable: the SeaweedFS_ec_engine_fallbacks_total counter,
pipeline.retry / pipeline.fallback spans, and per-call stats (retries /
fallbacks).
"""

from __future__ import annotations

import os
import signal
import time

import numpy as np
import pytest

from seaweedfs_tpu.ec import encoder
from seaweedfs_tpu.ec.codec import CpuEngine, ReedSolomon
from seaweedfs_tpu.ec.layout import to_ext
from seaweedfs_tpu.ec.streaming import StreamingEncoder
from seaweedfs_tpu.observability import disable_tracing, enable_tracing
from seaweedfs_tpu.utils import faultinject as fi

K, R, TOTAL = 10, 4, 14


def _shards(base: str) -> list[bytes]:
    return [open(base + to_ext(i), "rb").read() for i in range(TOTAL)]


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
    """The staged plane: the device engine on one device."""
    enc = StreamingEncoder(K, R, engine="device", devices="1",
                           dispatch_mb=1, **kw)
    enc.dispatch_b = 65536
    return enc


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
    # large=1MB keeps every row a small 10_000-byte block (uniform
    # entries), depth=1 keeps two dispatches in flight
    enc = _staged_encoder(depth=1)
    real = streaming_mod.preadv_into
    calls = {"n": 0}

    def flaky(f, views, off):
        calls["n"] += 1
        if calls["n"] == 15:
            # the 15th fill (dispatch 2) faults once the writer thread
            # has retired a dispatch, so the checkpoint is past byte 0
            deadline = time.monotonic() + 30
            while enc._ckpt[1] == 0 and time.monotonic() < deadline:
                time.sleep(0.002)
            raise OSError("injected fill IO error")
        return real(f, views, off)

    monkeypatch.setattr(streaming_mod, "preadv_into", flaky)
    out = str(tmp_path / "o")
    enc.encode_file(base + ".dat", out,
                    large_block_size=1_000_000, small_block_size=10_000)
    assert _shards(out) == ref
    assert enc.stats["retries"] == 1
    retries = [s for s in tracer.snapshot()
               if s.name == "pipeline.retry"
               and s.attrs.get("scope") == "encode_file"]
    assert retries and retries[0].attrs["resume_byte"] > 0


def test_staged_resume_entrypoint_is_byte_exact(tmp_path):
    """The resume machinery itself: corrupt every shard past a dispatch
    boundary, re-enter _encode_file_staged at that checkpoint, and the
    repaired shards must match a clean encode bit-for-bit (dispatch
    packing after a resume may differ; bytes may not)."""
    base = str(tmp_path / "v")
    rng = np.random.default_rng(10)
    open(base + ".dat", "wb").write(
        rng.integers(0, 256, 1_500_000, dtype=np.uint8).tobytes())
    enc = _staged_encoder()
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


def test_async_drain_deep_buffers_byte_identical(tmp_path):
    """The async multi-buffered drain at depth=4 (5 slots in flight):
    FIFO writer order must keep shards and the write-order-crc `.eci`
    sidecar byte-identical to the CPU reference while fetch/write run
    off the critical thread."""
    from seaweedfs_tpu.ec.integrity import sidecar_path

    base = str(tmp_path / "v")
    rng = np.random.default_rng(12)
    open(base + ".dat", "wb").write(
        rng.integers(0, 256, 6_000_000, dtype=np.uint8).tobytes())
    encoder.write_ec_files(base, ReedSolomon(K, R, engine=CpuEngine()),
                           large_block_size=200_000, small_block_size=20_000)
    ref = _shards(base)
    ref_eci = open(sidecar_path(base), "rb").read()
    enc = _staged_encoder(depth=4)
    out = str(tmp_path / "st")
    enc.encode_file(base + ".dat", out,
                    large_block_size=200_000, small_block_size=20_000)
    assert _shards(out) == ref
    assert open(sidecar_path(out), "rb").read() == ref_eci
    assert enc.stats["drain_pool"] >= 1
    assert enc.stats["parity_bytes_drained"] > 0
    assert enc.stats["fallbacks"] == 0


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
