"""Multi-chip sharding tests on the virtual 8-device CPU mesh."""

import numpy as np
import pytest

import jax

from seaweedfs_tpu.ec.codec import CpuEngine, ReedSolomon
from seaweedfs_tpu.ec.gf256 import mat_invert, parity_rows
from seaweedfs_tpu.ops.gf_matmul import expand_matrix_bitplanes
from seaweedfs_tpu.parallel.mesh import (
    make_mesh,
    shard_data,
    sharded_encode_fn,
    training_step_fn,
)

rng = np.random.default_rng(42)

pytestmark = pytest.mark.skipif(len(jax.devices()) < 8,
                                reason="needs 8 virtual devices")


def _planes(d=10, p=4):
    return expand_matrix_bitplanes(parity_rows(d, p))


@pytest.mark.parametrize("dp,sp,tp", [(8, 1, 1), (2, 2, 2), (1, 2, 4), (2, 1, 4)])
def test_sharded_encode_matches_cpu(dp, sp, tp):
    d_shards, p_shards = 10, 4
    mesh = make_mesh(dp, sp, tp)
    a = jax.numpy.asarray(_planes(d_shards, p_shards))
    s, b = 2 * dp, 128 * sp  # tiny but divisible
    data = rng.integers(0, 256, (d_shards, s, b), dtype=np.uint8)
    fn = sharded_encode_fn(mesh)
    got = np.asarray(jax.device_get(fn(a, shard_data(mesh, data))))

    cpu = ReedSolomon(d_shards, p_shards, engine=CpuEngine())
    want = cpu.encode(data.reshape(d_shards, -1)).reshape(p_shards, s, b)
    assert np.array_equal(got, want)


def test_training_step_degraded_check_zero_mismatches():
    d_shards, p_shards = 10, 4
    mesh = make_mesh(2, 2, 2)
    matrix = ReedSolomon(d_shards, p_shards).matrix
    a = jax.numpy.asarray(_planes(d_shards, p_shards))
    # decode row for data shard 0 from survivors [1..9] + parity row 10
    survivors = list(range(1, d_shards)) + [d_shards]
    sub = [[int(v) for v in matrix[i]] for i in survivors]
    decode = np.array(mat_invert(sub), dtype=np.uint8)
    decode_planes = jax.numpy.asarray(expand_matrix_bitplanes(decode[:1]))

    data = rng.integers(0, 256, (d_shards, 4, 256), dtype=np.uint8)
    step = training_step_fn(mesh)
    parity, mismatches = step(a, decode_planes, shard_data(mesh, data))
    assert int(mismatches) == 0
    cpu = ReedSolomon(d_shards, p_shards, engine=CpuEngine())
    want = cpu.encode(data.reshape(d_shards, -1)).reshape(p_shards, 4, 256)
    assert np.array_equal(np.asarray(jax.device_get(parity)), want)


def test_ring_rebuild_matches_cpu_reconstruction():
    """Ring-collective rebuild (ppermute hops, the ring-parallel pattern):
    8 survivors sharded one-per-device reconstruct 2 missing data shards
    byte-identically to the CPU decode."""
    from seaweedfs_tpu.ec.gf256 import mat_mul
    from seaweedfs_tpu.parallel.mesh import ring_rebuild_fn

    d_shards, p_shards = 8, 4
    cpu = ReedSolomon(d_shards, p_shards, engine=CpuEngine())
    b = 256
    data = rng.integers(0, 256, (d_shards, b), dtype=np.uint8)
    parity = cpu.encode(data)
    all_shards = np.concatenate([data, parity])

    missing = [0, 5]
    survivors = [i for i in range(d_shards + p_shards)
                 if i not in missing][:d_shards]
    sub = [[int(v) for v in cpu.matrix[i]] for i in survivors]
    decode = mat_invert(sub)
    rec_rows = np.array([decode[m] for m in missing], dtype=np.uint8)

    from seaweedfs_tpu.parallel.mesh import ring_plane_layout

    mesh = make_mesh(1, 1, 8)  # last axis becomes the ring
    planes = jax.numpy.asarray(ring_plane_layout(
        expand_matrix_bitplanes(rec_rows), d_shards, 8))
    fn = ring_rebuild_fn(mesh)
    got = np.asarray(jax.device_get(
        fn(planes, jax.numpy.asarray(all_shards[survivors]))))
    assert np.array_equal(got, data[missing])


def test_streaming_encoder_uses_mesh_and_matches_cpu(tmp_path):
    """StreamingEncoder(engine='device') on a multi-device backend must
    spread whole dispatches over per-device queues on every visible
    device (the product path reaches every chip) and stay
    byte-identical."""
    import os

    from seaweedfs_tpu.ec import encoder as cpu_encoder
    from seaweedfs_tpu.ec.layout import to_ext
    from seaweedfs_tpu.ec.streaming import StreamingEncoder

    rng = np.random.default_rng(3)
    raw = rng.integers(0, 256, (2 << 20) + 4567, dtype=np.uint8).tobytes()
    dat = tmp_path / "m.dat"
    dat.write_bytes(raw)
    enc = StreamingEncoder(10, 4, engine="device", dispatch_mb=1)
    enc.dispatch_b = 1 << 18  # each 1 MiB row in 4 dispatches
    enc.encode_file(str(dat), str(tmp_path / "m"))
    st = enc.stats
    assert st["devices"] == len(jax.devices())
    assert st["dispatches"] == 4
    per_dev = st["per_device"]
    assert len(per_dev) == len(jax.devices())
    assert sum(1 for v in per_dev.values() if v["dispatches"]) == 4

    (tmp_path / "c.dat").write_bytes(raw)
    cpu_encoder.write_ec_files(str(tmp_path / "c"), ReedSolomon(10, 4))
    for i in range(14):
        assert (tmp_path / f"m{to_ext(i)}").read_bytes() == \
            (tmp_path / f"c{to_ext(i)}").read_bytes(), f"shard {i}"

    # rebuild on the slice's first device
    os.remove(tmp_path / "m.ec02")
    os.remove(tmp_path / "m.ec11")
    assert sorted(enc.rebuild_files(str(tmp_path / "m"))) == [2, 11]
    for i in (2, 11):
        assert (tmp_path / f"m{to_ext(i)}").read_bytes() == \
            (tmp_path / f"c{to_ext(i)}").read_bytes(), f"rebuilt {i}"


def test_store_ec_generate_tpu_takes_mesh_path(tmp_path):
    """-ec.engine=tpu through the volume server's store must reach the
    per-device queues of every device on a multi-device backend."""
    from seaweedfs_tpu.storage.needle import Needle
    from seaweedfs_tpu.volume_server.store import Store

    store = Store([str(tmp_path)], max_volume_count=2, ec_engine="tpu")
    try:
        store.add_volume(1)
        for i in range(1, 20):
            store.write_needle(1, Needle(cookie=i, id=i,
                                         data=bytes([i]) * 997 * i))
        store.ec_generate(1)
        enc = store._stream_enc
        assert enc is not None and enc.engine == "device"
        assert enc.stats["devices"] == len(jax.devices())
        per_dev = enc.stats["per_device"]
        assert sum(v["dispatches"] for v in per_dev.values()) \
            == enc.stats["dispatches"] >= 1
        base = store.get_volume(1).file_prefix
        # shards must be byte-identical to the CPU engine's
        import os

        from seaweedfs_tpu.ec import encoder as cpu_encoder
        from seaweedfs_tpu.ec.layout import to_ext

        os.link(base + ".dat", base + "_cpu.dat")
        cpu_encoder.write_ec_files(base + "_cpu", ReedSolomon(10, 4))
        for i in range(14):
            with open(base + to_ext(i), "rb") as f1, \
                    open(base + "_cpu" + to_ext(i), "rb") as f2:
                assert f1.read() == f2.read(), f"shard {i}"
    finally:
        store.close()


def test_parse_device_spec_vocabulary():
    from seaweedfs_tpu.parallel.mesh import parse_device_spec

    devs = jax.devices()
    assert parse_device_spec(None) == list(devs)
    assert parse_device_spec("") == list(devs)
    assert parse_device_spec("all") == list(devs)
    assert parse_device_spec("3") == list(devs[:3])     # bare int = COUNT
    assert parse_device_spec("3,") == [devs[3]]         # trailing comma = index
    assert parse_device_spec("5,2") == [devs[5], devs[2]]
    for bad in ("0", "9", "x", "1,1", "5,9", ","):
        with pytest.raises(ValueError):
            parse_device_spec(bad)


def test_mesh_engine_matmul_matches_cpu():
    """MeshEngine (shard_map over the full mesh) must be byte-identical
    to the CPU LUT codec, including the pad/unpad path for widths not
    divisible by the dp*sp grid."""
    from seaweedfs_tpu.ec.codec import MeshEngine

    cpu = ReedSolomon(10, 4, engine=CpuEngine())
    mesh_rs = ReedSolomon(10, 4, engine=MeshEngine())
    for width in (1, 7, 64, 1000, 4096):
        data = rng.integers(0, 256, (10, width), dtype=np.uint8)
        assert np.array_equal(mesh_rs.encode(data), cpu.encode(data)), width


def _write_and_compare_mesh(tmp_path, devices, raw, dispatch_mb=1):
    """Encode raw via the per-device-queue mesh engine and assert all 14
    shards AND the `.eci` sidecar match the CPU reference encoder."""
    from seaweedfs_tpu.ec import encoder as cpu_encoder
    from seaweedfs_tpu.ec.layout import to_ext
    from seaweedfs_tpu.ec.streaming import StreamingEncoder

    dat = tmp_path / "m.dat"
    dat.write_bytes(raw)
    enc = StreamingEncoder(10, 4, engine="mesh", devices=devices,
                           dispatch_mb=dispatch_mb)
    enc.encode_file(str(dat), str(tmp_path / "m"))

    (tmp_path / "c.dat").write_bytes(raw)
    cpu_encoder.write_ec_files(str(tmp_path / "c"), ReedSolomon(10, 4))
    for i in range(14):
        assert (tmp_path / f"m{to_ext(i)}").read_bytes() == \
            (tmp_path / f"c{to_ext(i)}").read_bytes(), f"shard {i}"
    assert (tmp_path / "m.eci").read_bytes() == \
        (tmp_path / "c.eci").read_bytes()
    return enc


def test_mesh_streaming_encoder_byte_identical(tmp_path):
    """engine='mesh' on the forced 8-device CPU mesh: whole dispatches
    round-robin across per-device queues, output (shards + sidecar)
    byte-identical to the CPU codec."""
    # dispatch_mb=1 is the PER-SHARD block width: each dispatch covers
    # 10MB of the file, so 42MB -> 5 dispatches over 5 distinct queues
    raw = np.random.default_rng(7).integers(
        0, 256, (42 << 20) + 4567, dtype=np.uint8).tobytes()
    enc = _write_and_compare_mesh(tmp_path, "8", raw)
    st = enc.stats
    assert st["devices"] == 8
    assert st["dispatches"] == 5
    assert st["drain_pool"] == 8          # one drain lane per device
    per_dev = st["per_device"]
    # round-robin: with >= 5 dispatches at least 5 queues saw work
    assert sum(1 for v in per_dev.values() if v["dispatches"]) >= 5
    assert sum(v["dispatches"] for v in per_dev.values()) \
        == st["dispatches"]


def test_mesh_device_index_spec_encodes(tmp_path):
    """'5,2' pins the dispatch queues to exactly those device indices."""
    raw = np.random.default_rng(8).integers(
        0, 256, (2 << 20) + 131, dtype=np.uint8).tobytes()
    enc = _write_and_compare_mesh(tmp_path, "5,2", raw)
    assert enc.stats["devices"] == 2


def test_mesh_encoder_survives_drain_and_dispatch_faults(tmp_path):
    """Worker-kill drill through the per-device queues: injected drain
    fetch errors and a dispatch fault must fall back to CPU parity for
    the affected dispatches and stay byte-identical (PR-3 self-healing
    + PR-7 drain plumbing survive the mesh plane)."""
    from seaweedfs_tpu.utils import faultinject

    # 25MB -> 3 dispatches: the dispatch fault hits the first, the two
    # drain faults hit the first two drain spans
    raw = np.random.default_rng(9).integers(
        0, 256, (25 << 20) + 977, dtype=np.uint8).tobytes()
    faultinject.clear()
    try:
        faultinject.enable("ec.drain", error_rate=1.0, max_hits=2)
        faultinject.enable("ec.dispatch", error_rate=1.0, max_hits=1)
        enc = _write_and_compare_mesh(tmp_path, "4", raw)
    finally:
        faultinject.clear()
    assert enc.stats["fallbacks"] >= 3    # 2 drain-fetch + 1 dispatch
    assert enc.stats["devices"] == 4


def test_store_mesh_bad_device_spec_fails_at_init(tmp_path):
    """A bad -ec.mesh.devices must fail at server START (Store init),
    not at first encode."""
    from seaweedfs_tpu.volume_server.store import Store

    with pytest.raises(ValueError):
        Store([str(tmp_path)], ec_engine="mesh", ec_mesh_devices="99")


def test_store_ec_generate_mesh_path(tmp_path):
    """-ec.engine=mesh through the volume server's store: ec_generate
    must take the per-device-queue streaming path and stay
    byte-identical to the CPU engine."""
    import os

    from seaweedfs_tpu.ec import encoder as cpu_encoder
    from seaweedfs_tpu.ec.layout import to_ext
    from seaweedfs_tpu.storage.needle import Needle
    from seaweedfs_tpu.volume_server.store import Store

    store = Store([str(tmp_path)], max_volume_count=2, ec_engine="mesh")
    try:
        store.add_volume(1)
        for i in range(1, 20):
            store.write_needle(1, Needle(cookie=i, id=i,
                                         data=bytes([i]) * 997 * i))
        store.ec_generate(1)
        enc = store._stream_encs.get("mesh")
        assert enc is not None and enc.engine == "mesh"
        assert enc.stats["devices"] == len(jax.devices())
        base = store.get_volume(1).file_prefix
        os.link(base + ".dat", base + "_cpu.dat")
        cpu_encoder.write_ec_files(base + "_cpu", ReedSolomon(10, 4))
        for i in range(14):
            with open(base + to_ext(i), "rb") as f1, \
                    open(base + "_cpu" + to_ext(i), "rb") as f2:
                assert f1.read() == f2.read(), f"shard {i}"
    finally:
        store.close()
