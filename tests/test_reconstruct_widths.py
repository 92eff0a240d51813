"""Reconstructs of any width from a fixed set of compiled shapes.

TpuEngine runs every codec matmul at its width class (the least class
>= the width) and cuts the valid columns on the host, so a degraded
read of any width runs a program compiled before it; the store compiles
the classes when an EC volume mounts under a device engine.  Checked
against the benchmark's plain reference (benchmark/reference/rs.py,
loaded by path: it imports nothing of the program), at widths 1,
class - 1, class, class + 1 of every class and the small block.

Run as a script on a TPU (``python tests/test_reconstruct_widths.py``)
to compare the compiled Pallas path with the reference at every TPU
class boundary; the tests pin the CPU (conftest.py).
"""

from __future__ import annotations

import importlib.util
import os
import sys
from types import SimpleNamespace

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
SMALL_BLOCK = 1 << 20


def _load(name: str, path: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load("plain_rs_reference", os.path.join(BENCH, "reference", "rs.py"))

# (lost shards, survivors the read path hands over): shard 3 as the
# store reads it (the first 10 others, parity 11-13 unread), and two
# data plus two parity shards lost
PATTERNS = {"shard3": [3], "two_data_two_parity": [1, 6, 11, 12]}


def boundary_widths(classes) -> list[int]:
    ws = {1, SMALL_BLOCK}
    for c in classes.widths:
        ws.update((c - 1, c, c + 1))
    return sorted(w for w in ws if w > 0)


def check_against_reference(engine, widths, lost, seed: int = 30) -> int:
    """Reconstruct `lost` through `engine` at each width from seeded
    survivors; assert every made shard equals the reference's bytes and
    the original's.  Returns the widths checked."""
    from seaweedfs_tpu.ec.codec import ReedSolomon

    rs = ReedSolomon(10, 4, engine=engine)
    g = np.random.default_rng([seed, len(lost)])
    for w in widths:
        data = g.integers(0, 256, (10, w), dtype=np.uint8)
        shards = list(data) + list(ref.encode(data))
        have = [i for i in range(14) if i not in lost][:10]
        bufs = [shards[i].copy() if i in have else None for i in range(14)]
        rs.reconstruct(bufs)
        want = ref.reconstruct({i: shards[i] for i in have},
                               [i for i in range(14) if i not in have])
        for i, v in want.items():
            assert bufs[i].tobytes() == v.tobytes() == shards[i].tobytes(), \
                (engine.name, w, i)
    return len(widths)


@pytest.mark.parametrize("lost", sorted(PATTERNS))
@pytest.mark.parametrize("mode", ["xla", "pallas"])
def test_engine_equals_reference_at_class_boundaries(mode, lost):
    from seaweedfs_tpu.ops.gf_matmul import TpuEngine

    eng = TpuEngine(mode=mode)
    assert check_against_reference(eng, boundary_widths(eng.classes),
                                   PATTERNS[lost]) > 30


def test_classes_are_fixed_by_the_engine():
    from seaweedfs_tpu.ops.gf_matmul import (DEFAULT_TILE_B,
                                             INTERPRET_TILE_B, TpuEngine,
                                             WidthClasses)

    tpu = WidthClasses(DEFAULT_TILE_B, SMALL_BLOCK)
    assert tpu.widths == tuple(range(DEFAULT_TILE_B, SMALL_BLOCK + 1,
                                     DEFAULT_TILE_B))
    assert len(tpu.widths) == 16
    assert [tpu.of(b) for b in (0, 1, 65536, 65537, SMALL_BLOCK)] \
        == [65536, 65536, 65536, 131072, SMALL_BLOCK]
    # wider than the small block: multiples of it
    assert tpu.of(SMALL_BLOCK + 1) == 2 * SMALL_BLOCK
    assert tpu.of(3 * SMALL_BLOCK) == 3 * SMALL_BLOCK
    cpu = TpuEngine(mode="xla")
    assert cpu.classes.widths == tuple(INTERPRET_TILE_B << i
                                       for i in range(11))
    assert cpu.width_class(1025) == 2048
    with pytest.raises(ValueError):
        WidthClasses(3000, SMALL_BLOCK)


@pytest.mark.parametrize("mode", ["xla", "pallas"])
def test_no_compile_after_the_classes(mode):
    """A matrix shape of this test alone ([3, 7]): its first call
    compiles, proving the counter counts; once compile_classes ran, 50
    reads of distinct random widths up to the small block add none."""
    from seaweedfs_tpu.ec.codec import CpuEngine
    from seaweedfs_tpu.ops import device
    from seaweedfs_tpu.ops.gf_matmul import TpuEngine

    eng = TpuEngine(mode=mode)
    g = np.random.default_rng(3)
    m = g.integers(1, 256, (3, 7), dtype=np.uint8)
    c0 = device.device_doc()["compiles"]
    eng.matmul(m, g.integers(0, 256, (7, 5000), dtype=np.uint8))
    assert device.device_doc()["compiles"] > c0
    eng.compile_classes(3, 7)
    assert eng.classes.compiled == set(eng.classes.widths)
    widths = set()
    while len(widths) < 50:  # log-uniform, as the needles are
        widths.add(int(2 ** g.uniform(0, 20)))
    c1 = device.device_doc()["compiles"]
    cpu = CpuEngine()
    for w in sorted(widths):
        x = g.integers(0, 256, (7, w), dtype=np.uint8)
        got = eng.matmul(m, x)
        assert got.shape == (3, w)
        assert np.array_equal(got, cpu.matmul(m, x)), w
    assert device.device_doc()["compiles"] == c1
    # a second request for the same shape compiles nothing
    eng.compile_classes(3, 7)
    assert device.device_doc()["compiles"] == c1


def test_mesh_engine_runs_its_classes():
    """MeshEngine (-ec.engine=mesh) pads to the same classes, each a
    multiple of its dp x sp grid, and compiles them on request."""
    from seaweedfs_tpu.ec.codec import MeshEngine
    from seaweedfs_tpu.ops import device

    eng = MeshEngine()
    q = eng.dims[0] * eng.dims[1]
    assert all(eng.width_class(b) % q == 0 for b in (1, 1000, 70000))
    eng.compile_classes(4, 10)
    c1 = device.device_doc()["compiles"]
    assert check_against_reference(eng, [1, 1023, 1025, 70001],
                                   [3]) == 4
    assert device.device_doc()["compiles"] == c1


def _log_uniform_sizes(seed: int, total: int) -> list[int]:
    g = np.random.default_rng(seed)
    out, t = [], 8
    while t < total:
        s = int(2 ** g.uniform(10, 22))
        out.append(s)
        t += s + 64
    return out


def _classify(ev, key: int) -> str:
    """Where needle `key` lies against shard 3: none, whole, end (one
    end on it) or through (its middle interval rebuilt whole)."""
    _, _, ivs = ev.locate_ec_shard_needle(key)
    sids = [iv.to_shard_id_and_offset(ev.large_block_size,
                                      ev.small_block_size,
                                      ev.data_shards)[0] for iv in ivs]
    if 3 not in sids:
        return "none"
    if set(sids) == {3}:
        return "whole"
    return "end" if 3 in (sids[0], sids[-1]) else "through"


# the sizes' seed: its 16 MiB volume puts needles wholly on shard 3,
# with one end on it, and through it
SIZE_SEED = 5


def test_store_reads_every_needle_with_shard3_lost(tmp_path):
    """A 16 MiB EC volume of log-uniform 1 KiB-4 MiB needles, shard 3
    deleted, mounted under the device engine: every needle read through
    Store.read_ec_needle equals its bytes, no read compiles, and the
    reconstructs ran on the device engine at their width classes."""
    from seaweedfs_tpu.observability import disable_tracing, enable_tracing
    from seaweedfs_tpu.ops import device
    from seaweedfs_tpu.storage.needle import Needle
    from seaweedfs_tpu.volume_server.store import Store

    store = Store([str(tmp_path)], max_volume_count=2, ec_engine="tpu")
    tracer = enable_tracing(capacity=1 << 16)
    tracer.clear()
    try:
        store.add_volume(1)
        g = np.random.default_rng(SIZE_SEED)
        bodies = {}
        for key, size in enumerate(_log_uniform_sizes(SIZE_SEED, 16 << 20),
                                   start=1):
            bodies[key] = g.integers(0, 256, size, np.uint8).tobytes()
            store.write_needle(1, Needle(cookie=key, id=key,
                                         data=bodies[key]))
        store.ec_generate(1)
        store.ec_mount(1)
        store.delete_volume(1)
        store.ec_delete_shards(1, [3])
        ev = store.ec_volumes[1]
        assert 3 not in ev.shards
        eng = store.rs().engine
        assert eng.name.startswith("tpu")
        # compiled at the mount, for the reconstruct's 4 x 10 matrix
        assert eng.classes.compiled == set(eng.classes.widths)
        where = {k: _classify(ev, k) for k in bodies}
        assert {"whole", "end", "through"} <= set(where.values()), where
        calls, c0 = eng.calls, device.device_doc()["compiles"]
        tracer.clear()
        for key, body in bodies.items():
            blob, size = store.read_ec_needle(1, key)
            n = Needle.from_bytes(blob, size, ev.version)
            assert n.data == body, (key, where[key])
        assert device.device_doc()["compiles"] == c0
        lost = sum(1 for v in where.values() if v != "none")
        assert eng.calls - calls >= lost
        spans = [s for s in tracer.snapshot() if s.name == "ec.reconstruct"]
        assert len(spans) == eng.calls - calls
        for s in spans:
            assert 0 < s.attrs["columns"] <= s.attrs["padded"] \
                == eng.width_class(s.attrs["columns"])
        assert any(s.attrs["columns"] == SMALL_BLOCK for s in spans) \
            == ("through" in where.values())
    finally:
        disable_tracing()
        tracer.clear()
        store.close()


def test_mount_compiles_and_status_names_the_classes(tmp_path):
    """POST /admin/ec/mount under -ec.engine=tpu compiles the classes in
    one ec.compile_classes span (its classes and rows) before any GET,
    a remount compiles nothing more, and /status EcDevice lists the
    class widths compiled.  A CPU-engine store has nothing to compile."""
    from seaweedfs_tpu.client.operation import WeedClient
    from seaweedfs_tpu.master.server import MasterServer
    from seaweedfs_tpu.observability import (disable_tracing,
                                             enable_tracing, sample_rate,
                                             set_sample_rate)
    from seaweedfs_tpu.utils.httpd import http_bytes, http_json
    from seaweedfs_tpu.volume_server.server import VolumeServer
    from seaweedfs_tpu.volume_server.store import Store
    from tests.conftest import free_port

    tracer = enable_tracing(capacity=1 << 14)
    tracer.clear()
    prev_rate = sample_rate()
    set_sample_rate(1.0)  # every request traced, whatever ran before
    m = vs = None
    try:
        m = MasterServer(port=free_port()).start()
        vs = VolumeServer([str(tmp_path / "v")], m.url, port=free_port(),
                          ec_engine="tpu").start()
        data = os.urandom(3000)
        fid = WeedClient(m.url).upload(data)
        vid = int(fid.split(",")[0])

        def post(path, **body):
            return http_json("POST", f"http://{vs.url}{path}",
                             {"volume_id": vid, **body})

        post("/admin/readonly", readonly=True)
        post("/admin/ec/generate")
        post("/admin/ec/mount")
        post("/admin/delete_volume")
        post("/admin/ec/delete", shard_ids=[0])  # unmounts, remounts
        status, body, _ = http_bytes("GET", f"http://{vs.url}/{fid}")
        assert status == 200 and body == data
        spans = tracer.snapshot()
        compiles = [s for s in spans if s.name == "ec.compile_classes"]
        eng = vs.store.rs().engine
        assert len(compiles) == 1
        assert compiles[0].attrs["rows"] == 4
        assert compiles[0].attrs["classes"] == len(eng.classes.widths)
        recon = next(s for s in spans if s.name == "ec.reconstruct")
        assert compiles[0].t1 <= recon.t0
        dev = http_json("GET", f"http://{vs.url}/status")["EcDevice"]
        assert dev["reconstruct_classes"] == list(eng.classes.widths)
        cpu_store = Store([str(tmp_path / "c")], ec_engine="cpu")
        try:
            assert not hasattr(cpu_store.rs().engine, "compile_classes")
        finally:
            cpu_store.close()
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
    return _load("metric_" + name.replace(".", "_"),
                 os.path.join(BENCH, "metrics", name + ".py")).read


def _recon(sid, ts, dur, columns=None, padded=None):
    a = {"span_id": sid, "k": 10, "missing": 4, "bytes": 10 * 5000}
    if columns is not None:
        a.update(columns=columns, padded=padded)
    return {"name": "ec.reconstruct", "ph": "X", "ts": ts, "dur": dur,
            "args": a}


def test_pad_share_reader(monkeypatch):
    """reconstruct_pad_share.read_vol4g: sum(padded - columns) over sum
    padded, in %; None where the spans carry no such args (a program
    before the width classes)."""
    read = _reader("reconstruct_pad_share.read_vol4g", monkeypatch)
    spans = [_recon("a", 0, 10, 1000, 65536),
             _recon("b", 20, 10, 65536, 65536),
             _recon("c", 40, 10, 100000, 131072)]
    want = 100.0 * (64536 + 0 + 31072) / (65536 + 65536 + 131072)
    assert read(SimpleNamespace(spans=spans)) == pytest.approx(want)
    assert read(SimpleNamespace(spans=[_recon("d", 0, 10)])) is None
    assert read(SimpleNamespace(spans=[])) is None


if __name__ == "__main__":
    # on a TPU: the compiled Pallas path at every class boundary
    sys.path.insert(0, ROOT)
    import json

    from seaweedfs_tpu.ops import device
    from seaweedfs_tpu.ops.gf_matmul import TpuEngine

    eng = TpuEngine()
    eng.compile_classes(4, 10)
    c0 = device.device_doc()["compiles"]
    n = sum(check_against_reference(eng, boundary_widths(eng.classes), lost)
            for lost in PATTERNS.values())
    print(json.dumps({"engine": eng.name, "platform": device.device_doc()[
        "platform"], "classes": list(eng.classes.widths), "widths": n,
        "compiles_after_classes": device.device_doc()["compiles"] - c0,
        "ok": True}))
