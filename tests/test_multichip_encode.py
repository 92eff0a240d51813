"""The device engines' encode and rebuild on slices of 1, 2 and 4 of the
forced CPU devices, against the benchmark's plain reference.

One device runs the staged pipeline; more run the per-device dispatch
queues, whole dispatches round-robin over the slice.  Every shard that
an encode or a rebuild writes must equal ``benchmark/reference/rs.py``
(loaded by path: it imports nothing of the program) on seeded random
``.dat`` bytes, and the ``.eci`` sidecar, which the reference does not
make, must equal the one-device encode's.
"""

import importlib.util
import os

import numpy as np
import pytest

from seaweedfs_tpu.ec.layout import to_ext
from seaweedfs_tpu.ec.streaming import StreamingEncoder
from seaweedfs_tpu.observability import Tracer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_spec = importlib.util.spec_from_file_location(
    "bench_reference_rs", os.path.join(ROOT, "benchmark", "reference", "rs.py"))
rs = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(rs)

# three 10 x 10,000-byte large rows, then five small rows of 1,000-byte
# blocks, the last one padded; 4 KiB dispatches make nine of them
LARGE, SMALL, DAT_SIZE, DISPATCH_B = 10_000, 1_000, 345_679, 4096


def _encode(tmp_path, name: str, devices: str, dat: bytes):
    tracer = Tracer(capacity=1 << 14)
    enc = StreamingEncoder(10, 4, engine="device", devices=devices,
                           tracer=tracer)
    enc.dispatch_b = DISPATCH_B
    base = str(tmp_path / name)
    with open(base + ".dat", "wb") as f:
        f.write(dat)
    enc.encode_file(base + ".dat", base, large_block_size=LARGE,
                    small_block_size=SMALL)
    return enc, tracer, base


def _shards(base: str) -> list[bytes]:
    out = []
    for i in range(14):
        with open(base + to_ext(i), "rb") as f:
            out.append(f.read())
    return out


@pytest.mark.parametrize("lost", [[], [3], [1, 8, 10, 13]],
                         ids=["encode", "rebuild-3", "rebuild-2data-2parity"])
@pytest.mark.parametrize("n_dev", [1, 2, 4])
def test_slice_matches_reference(tmp_path, n_dev, lost):
    dat = np.random.default_rng(1000 + n_dev).integers(
        0, 256, DAT_SIZE, dtype=np.uint8)
    enc, tracer, base = _encode(tmp_path, "v", str(n_dev), dat.tobytes())

    roots = [s for s in tracer.snapshot() if s.name == "pipeline.encode_file"]
    assert len(roots) == 1
    assert roots[0].attrs["devices"] == n_dev == enc.stats["devices"]
    assert roots[0].attrs["mode"] == ("staged" if n_dev == 1 else "mesh")
    if n_dev > 1:
        per_dev = enc.stats["per_device"]
        assert sorted(per_dev) == [str(i) for i in range(n_dev)]
        assert all(v["dispatches"] for v in per_dev.values())

    if lost:
        for s in lost:
            os.remove(base + to_ext(s))
        tracer.clear()
        assert sorted(enc.rebuild_files(base)) == lost
        assert [s.attrs["mode"] for s in tracer.snapshot()
                if s.name == "pipeline.rebuild_files"] == ["staged"]

    size = rs.shard_size(DAT_SIZE, LARGE, SMALL)
    want = rs.shard_columns(dat, 0, size, LARGE, SMALL)
    got = _shards(base)
    for i in range(14):
        assert got[i] == want[i].tobytes(), f"shard {i}"

    _, _, one = _encode(tmp_path, "one", "1", dat.tobytes())
    with open(base + ".eci", "rb") as f1, open(one + ".eci", "rb") as f2:
        assert f1.read() == f2.read()


def test_queue_slots_kept_only_after_clean_encodes(tmp_path, monkeypatch):
    """The queue plane keeps its slot buffers for the next encode after
    a clean one, so a fill finds its pages touched already, and drops
    them after a failed attempt, whose transfers may still read them.
    The shards stay the reference's throughout."""
    dat = np.random.default_rng(77).integers(0, 256, DAT_SIZE,
                                             dtype=np.uint8)
    enc, _, base = _encode(tmp_path, "v", "4", dat.tobytes())
    kept = enc._slot_bufs
    assert len(kept) == 4  # one slot pool a device

    size = rs.shard_size(DAT_SIZE, LARGE, SMALL)
    want = [w.tobytes() for w in rs.shard_columns(dat, 0, size, LARGE,
                                                  SMALL)]

    def encode_again():
        enc.encode_file(base + ".dat", base, large_block_size=LARGE,
                        small_block_size=SMALL)
        assert _shards(base) == want

    encode_again()
    assert enc._slot_bufs is kept

    pwrite, failed = os.pwrite, []

    def pwrite_once_failing(fd, data, off):
        if not failed:
            failed.append(off)
            raise OSError("injected parity write failure")
        return pwrite(fd, data, off)

    monkeypatch.setattr(os, "pwrite", pwrite_once_failing)
    encode_again()
    assert failed and enc.stats["retries"] == 1
    assert enc._slot_bufs is not None and enc._slot_bufs is not kept


@pytest.mark.parametrize("n_dev,fault", [(1, None), (4, None),
                                         (4, "ec.dispatch"), (4, "ec.drain")])
def test_mapped_rows_on_slices(tmp_path, n_dev, fault):
    """One device (the staged plane) sends a dispatch of whole
    small-block rows as one view of the mapped .dat (1 KiB rows, 4 KiB
    dispatches: 4 rows a group); the queue plane, round-robin over four
    devices, fills every dispatch into its slots; a fault recomputes the
    dispatch on the CPU from its slot.  Shards equal the reference, the
    sidecar the one-device encode's."""
    from seaweedfs_tpu.utils import faultinject as fi

    small, size = 1024, 7 * 10 * 4096 + 3 * 10 * 1024 + 77
    dat = np.random.default_rng(300 + n_dev).integers(0, 256, size,
                                                      dtype=np.uint8)

    def encode(name, devices):
        tracer = Tracer(capacity=1 << 12)
        enc = StreamingEncoder(10, 4, engine="device", devices=devices,
                               tracer=tracer)
        enc.dispatch_b = DISPATCH_B
        base = str(tmp_path / name)
        with open(base + ".dat", "wb") as f:
            f.write(dat.tobytes())
        enc.encode_file(base + ".dat", base, large_block_size=1 << 20,
                        small_block_size=small)
        return enc, tracer, base

    if fault:
        fi.enable(fault, error_rate=1.0, max_hits=1)
    try:
        enc, tracer, base = encode("v", str(n_dev))
    finally:
        fi.clear()
    mapped = 7 if n_dev == 1 else 0
    assert (enc.stats["dispatches"], enc.stats["mapped_dispatches"]) == \
        (8, mapped)
    assert enc.stats["fallbacks"] == (1 if fault else 0)
    sources = [s.attrs.get("source") for s in tracer.snapshot()
               if s.name == "pipeline.dispatch"]
    assert sources.count("map") == mapped and \
        sources.count("slot") == 8 - mapped
    want = rs.shard_columns(dat, 0, rs.shard_size(size, 1 << 20, small),
                            1 << 20, small)
    assert _shards(base) == [w.tobytes() for w in want]
    _, _, one = encode("one", "1")
    with open(base + ".eci", "rb") as f1, open(one + ".eci", "rb") as f2:
        assert f1.read() == f2.read()
