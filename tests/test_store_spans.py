"""Store-layer spans, the front's queue wait, and the benchmark readers
that read them.

An in-process master and volume server with the tracer on run an EC
generate, a shard delete, a degraded GET that reconstructs, and a
rebuild; the spans must nest as the store layer calls down.  The four
per-layer readers of benchmark/metrics/ are then fed span lists whose
numbers are known.
"""

from __future__ import annotations

import importlib.util
import os
import time
from types import SimpleNamespace

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")


@pytest.fixture()
def traced_cluster(tmp_path):
    from seaweedfs_tpu.master.server import MasterServer
    from seaweedfs_tpu.observability import (disable_tracing,
                                             enable_tracing, sample_rate,
                                             set_sample_rate)
    from seaweedfs_tpu.utils.httpd import http_json
    from seaweedfs_tpu.volume_server.server import VolumeServer
    from tests.conftest import free_port

    tracer = enable_tracing(capacity=1 << 16)
    tracer.clear()
    prev_rate = sample_rate()
    set_sample_rate(1.0)  # every request traced, whatever ran before
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
        if vs is not None:
            vs.stop()
        if m is not None:
            m.stop()
        disable_tracing()
        set_sample_rate(prev_rate)
        tracer.clear()


def _reader(name: str, monkeypatch):
    """benchmark/metrics/<name>.py as run.py loads it."""
    monkeypatch.syspath_prepend(BENCH)
    spec = importlib.util.spec_from_file_location(
        "metric_" + name.replace(".", "_"),
        os.path.join(BENCH, "metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _ev(name, sid, ts, dur, parent=None, **args):
    """One span as /debug/traces exports it (Chrome "X", microseconds)."""
    a = {"span_id": sid, **args}
    if parent:
        a["parent_id"] = parent
    return {"name": name, "ph": "X", "ts": ts, "dur": dur, "args": a}


def test_store_spans_nest_under_the_admin_and_read_paths(traced_cluster,
                                                        monkeypatch):
    from seaweedfs_tpu.client.operation import WeedClient
    from seaweedfs_tpu.utils.httpd import http_bytes, http_json

    m, vs, tracer = traced_cluster
    data = os.urandom(5000)
    fid = WeedClient(m.url).upload(data)
    vid = int(fid.split(",")[0])

    def post(path, **body):
        return http_json("POST", f"http://{vs.url}{path}",
                         {"volume_id": vid, **body})

    post("/admin/readonly", readonly=True)
    post("/admin/ec/generate", engine="tpu")
    post("/admin/ec/mount")
    post("/admin/delete_volume")
    # the needle sits in the first small block, on shard 0
    post("/admin/ec/delete", shard_ids=[0])
    status, body, _ = http_bytes("GET", f"http://{vs.url}/{fid}")
    assert status == 200 and body == data
    assert post("/admin/ec/rebuild", engine="tpu")["rebuilt_shard_ids"] == [0]

    spans = tracer.snapshot()
    by_id = {s.span_id: s for s in spans}

    def parent_of(name):
        return [by_id[s.parent_id].name for s in spans
                if s.name == name and s.parent_id in by_id]

    assert "store.ec_generate" in parent_of("pipeline.encode_file")
    assert "store.ec_generate" in parent_of("store.ecx_sort")
    assert "store.ec_rebuild" in parent_of("pipeline.rebuild_files")
    assert "store.read_ec_needle" in parent_of("ec.reconstruct")
    sort = next(s for s in spans if s.name == "store.ecx_sort")
    assert sort.attrs["needles"] == 1
    delete = next(s for s in spans if s.name == "store.ec_delete")
    assert delete.attrs["shards"] == 1 and delete.attrs["bytes"] > 0
    read = next(s for s in spans if s.name == "store.read_ec_needle"
                and s.attrs.get("reconstructed"))
    assert read.attrs["intervals"] == read.attrs["local_reads"] \
        + read.attrs["reconstructed"]
    waits = [s for s in spans if s.name == "http.queue_wait"]
    # each wait ends at the worker's pickup, before its request's span
    assert waits and all(by_id[w.parent_id].name.startswith("http.")
                         and w.t1 <= by_id[w.parent_id].t0 for w in waits)
    assert any(by_id[w.parent_id].name == "http.volume.read_object"
               for w in waits)

    # the readers find something to read in the recorded spans
    run = SimpleNamespace(
        spans=[e for e in tracer.to_chrome()["traceEvents"]
               if e.get("ph") == "X"],
        window_s=max(s.t1 for s in spans) - min(s.t0 for s in spans))
    for name in ("store_share.encode", "store_share.rebuild",
                 "store_read_ms.read", "front_queue_ms.read"):
        v = _reader(name, monkeypatch)(run)
        assert v is not None and v > 0, name


@pytest.mark.parametrize("name, spans, window_s, want", [
    # generate 1000 us, its pipeline 700 and sort 100 under it (sort has
    # no child): self 200 + 100; a delete of 300: 600 us of 10 ms
    ("store_share.encode", [
        _ev("store.ec_generate", "g", 0, 1000),
        _ev("pipeline.encode_file", "p", 100, 700, "g"),
        _ev("store.ecx_sort", "x", 850, 100, "g"),
        _ev("store.ec_delete", "d", 1200, 300),
        _ev("pipeline.encode_file", "q", 2000, 5000)], 0.01, 6.0),
    # rebuild 2000 us less its pipeline 1600, and a delete of 100: 500 us
    # of 5 ms
    ("store_share.rebuild", [
        _ev("store.ec_rebuild", "r", 0, 2000),
        _ev("pipeline.rebuild_files", "p", 200, 1600, "r"),
        _ev("store.ec_delete", "d", 2500, 100)], 0.005, 10.0),
    # 1000 less its reconstruct of 600 (the master lookup stays store
    # time), and 200: a mean of 300 us
    ("store_read_ms.read", [
        _ev("store.read_ec_needle", "a", 0, 1000),
        _ev("rpc.client", "c", 10, 100, "a"),
        _ev("ec.reconstruct", "e", 200, 600, "a"),
        _ev("store.read_ec_needle", "b", 2000, 200)], 1.0, 0.3),
    # the GETs' waits 500 and 1500 us; an admin call's wait is not a GET's
    ("front_queue_ms.read", [
        _ev("http.volume.read_object", "r1", 600, 100),
        _ev("http.queue_wait", "q1", 0, 500, "r1"),
        _ev("http.volume.read_object", "r2", 3000, 100),
        _ev("http.queue_wait", "q2", 1400, 1500, "r2"),
        _ev("http.volume.ec_generate", "a", 20000, 100),
        _ev("http.queue_wait", "q3", 10000, 9000, "a")], 1.0, 1.0),
])
def test_readers_give_the_recorded_numbers(name, spans, window_s, want,
                                           monkeypatch):
    read = _reader(name, monkeypatch)
    assert read(SimpleNamespace(spans=spans, window_s=window_s)) \
        == pytest.approx(want)
    # a program that records none of these spans gives nothing to
    # read, and the metric is left out of the line
    others = [e for e in spans if not e["name"].startswith(
        ("store.", "http.queue_wait"))]
    assert read(SimpleNamespace(spans=others, window_s=window_s)) is None
