"""Streaming (overlapped-pipeline) EC encode/rebuild vs the serial CPU path.

The gate: StreamingEncoder output must be byte-identical to
encoder.write_ec_files / rebuild_ec_files for every geometry and file
size, including the strict-`>` large/small row transition and zero-padded
tails (ec_encoder.go:172-231 semantics).
"""

from __future__ import annotations

import contextlib
import functools
import os
import time

import numpy as np
import pytest

from seaweedfs_tpu.ec import encoder
from seaweedfs_tpu.ec.codec import ReedSolomon
from seaweedfs_tpu.ec.layout import to_ext
from seaweedfs_tpu.ec.streaming import (StreamingEncoder, _plan_entries,
                                        default_data_lanes,
                                        default_drain_pool)
from seaweedfs_tpu.observability import Tracer
from seaweedfs_tpu.utils import faultinject as fi

RNG = np.random.default_rng(0x5EA)

ENGINES = ["pallas", "fallback", "device"]


def _interpret_pallas(enc):
    """The chip's word kernel in interpret mode, one tile a block."""
    from seaweedfs_tpu.ops.gf_matmul import (INTERPRET_TILE_B,
                                             gf_matmul_pallas_packed)

    enc._kernel = functools.partial(gf_matmul_pallas_packed,
                                    tile_b=INTERPRET_TILE_B, interpret=True)
    enc._row_q = INTERPRET_TILE_B
    return enc


def make_enc(k, r, engine, **kw):
    """"device" = the XLA kernel on every device of the slice (the
    queue plane on the tests' 8 CPU devices); "pallas" = the word
    kernel in interpret mode on one device; "fallback" = one device,
    every dispatch's parity from the CPU codec (armed by _faults)."""
    if engine == "device":
        return StreamingEncoder(k, r, engine="device", **kw)
    enc = StreamingEncoder(k, r, engine="device", devices="1", **kw)
    return _interpret_pallas(enc) if engine == "pallas" else enc


@contextlib.contextmanager
def _faults(engine):
    """"fallback" arms ec.dispatch at rate 1.0 for the block."""
    if engine != "fallback":
        yield
        return
    fi.enable("ec.dispatch", error_rate=1.0)
    try:
        yield
    finally:
        fi.clear()


def _write_dat(tmp_path, size, name="v"):
    p = tmp_path / f"{name}.dat"
    p.write_bytes(RNG.integers(0, 256, size, dtype=np.uint8).tobytes())
    return str(tmp_path / name)


def _shards(base, total):
    return [open(base + to_ext(i), "rb").read() for i in range(total)]


def _cpu_reference(tmp_path, base, large, small):
    ref = str(tmp_path / "ref")
    os.link(base + ".dat", ref + ".dat")
    encoder.write_ec_files(ref, ReedSolomon(10, 4),
                           large_block_size=large, small_block_size=small,
                           chunk=npchunk(small))
    return ref


def npchunk(small):
    # odd chunk size to exercise output-invariance of the CPU path too
    return max(64, small // 3 * 2)


@pytest.mark.parametrize("engine", ENGINES)
@pytest.mark.parametrize("size,large,small", [
    (0, 10_000, 100),              # empty volume
    (999, 10_000, 100),            # sub-single-row tail
    (10 * 100, 10_000, 100),       # exactly one small row
    (123_457, 10_000, 100),        # large rows + small rows + ragged tail
    (10 * 10_000, 10_000, 100),    # exact large-row multiple -> all small rows
    (3 * 10 * 10_000 + 7, 10_000, 100),
])
def test_streaming_encode_byte_identical(tmp_path, size, large, small, engine):
    base = _write_dat(tmp_path, size)
    ref = _cpu_reference(tmp_path, base, large, small)
    enc = make_enc(10, 4, engine, dispatch_mb=1)
    enc.dispatch_b = 4096  # force multi-dispatch packing paths
    with _faults(engine):
        enc.encode_file(base + ".dat", base,
                        large_block_size=large, small_block_size=small)
    assert _shards(base, 14) == _shards(ref, 14)
    if engine == "fallback":
        assert enc.stats["fallbacks"] == enc.stats["dispatches"]


def test_streaming_encode_default_geometry_small_dispatch(tmp_path):
    # entries larger than one dispatch: small block (1MB-scaled) > buffer
    large, small = 1 << 16, 1 << 12
    base = _write_dat(tmp_path, 3 * 10 * (1 << 16) + 54321)
    ref = _cpu_reference(tmp_path, base, large, small)
    enc = StreamingEncoder(10, 4)
    enc.dispatch_b = 1 << 10  # 1KB buffer < small block -> chunked blocks
    enc.encode_file(base + ".dat", base,
                    large_block_size=large, small_block_size=small)
    assert _shards(base, 14) == _shards(ref, 14)


@pytest.mark.parametrize("engine", ENGINES)
@pytest.mark.parametrize("kill", [
    [0],            # one data shard
    [11],           # one parity shard
    [0, 3, 11, 13],  # worst case: 4 erasures mixed data+parity
])
def test_streaming_rebuild_byte_identical(tmp_path, kill, engine):
    """A rebuild has no per-dispatch fault points: armed as for an
    encode, "fallback" still rebuilds on the device, and nothing
    fires."""
    large, small = 10_000, 100
    base = _write_dat(tmp_path, 123_457)
    encoder.write_ec_files(base, ReedSolomon(10, 4),
                           large_block_size=large, small_block_size=small)
    want = _shards(base, 14)
    for i in kill:
        os.unlink(base + to_ext(i))
    enc = make_enc(10, 4, engine)
    enc.dispatch_b = 4096
    with _faults(engine):
        got_ids = enc.rebuild_files(base)
        assert fi.fired("ec.dispatch") == 0
    assert got_ids == sorted(kill)
    assert _shards(base, 14) == want
    assert enc.stats["fallbacks"] == 0


def test_streaming_rebuild_unrepairable(tmp_path):
    base = _write_dat(tmp_path, 50_000)
    encoder.write_ec_files(base, ReedSolomon(10, 4),
                           large_block_size=10_000, small_block_size=100)
    for i in range(5):  # only 9 of 14 left
        os.unlink(base + to_ext(i))
    with pytest.raises(ValueError, match="unrepairable"):
        StreamingEncoder(10, 4).rebuild_files(base)


@pytest.mark.parametrize("engine", ENGINES)
def test_streaming_alt_geometries(tmp_path, engine):
    for k, r in ((6, 3), (12, 4)):
        base = _write_dat(tmp_path, 77_777, name=f"g{k}{r}{engine[0]}")
        ref = str(tmp_path / f"ref{k}{r}{engine[0]}")
        os.link(base + ".dat", ref + ".dat")
        encoder.write_ec_files(ref, ReedSolomon(k, r),
                               large_block_size=10_000,
                               small_block_size=100, chunk=512)
        enc = make_enc(k, r, engine)
        enc.dispatch_b = 2048
        with _faults(engine):
            enc.encode_file(base + ".dat", base,
                            large_block_size=10_000, small_block_size=100)
        assert _shards(base, k + r) == _shards(ref, k + r)


class _SlowHandle:
    """Fake device result over a pull-model slow link: the transfer
    cost is paid INSIDE fetch (like np.asarray on a device array whose
    async copy never really overlaps), so whoever calls fetch eats
    LINK_S of wire time."""

    def __init__(self, parity, seq):
        self.parity = parity
        self.seq = seq


def test_async_drain_slow_link_overlap_and_fifo(tmp_path):
    """The synthetic-slow-link acceptance drill, deterministic on any
    CPU: every fetch blocks LINK_S (injected copy latency) while the
    host floor per dispatch is HOST_S (injected via the ec.dispatch
    delay fault).  With N=3 buffers the async drain must move that wire
    time onto the drainer thread — overlap_efficiency >= 0.6 where an
    inline drain would measure ~HOST/(HOST+LINK) — while fetch order
    stays FIFO and the output (shards AND the write-order-crc `.eci`
    sidecar) stays byte-identical to the CPU reference."""
    from seaweedfs_tpu.ec.integrity import sidecar_path

    HOST_S, LINK_S = 0.03, 0.03
    large, small = 100 << 20, 1 << 18
    base = _write_dat(tmp_path, 60 << 20, name="slow")
    ref = str(tmp_path / "slowref")
    os.link(base + ".dat", ref + ".dat")
    encoder.write_ec_files(ref, ReedSolomon(10, 4),
                           large_block_size=large, small_block_size=small)

    tracer = Tracer(capacity=1 << 14)
    # one fetch thread: the fetch order itself is FIFO
    enc = StreamingEncoder(10, 4, engine="device", devices="1", depth=2,
                           drain_pool=1, tracer=tracer)  # N = 3 buffers
    enc.dispatch_b = 1 << 18
    order: list[int] = []
    seq = {"n": 0}

    def slow_dispatch(planes, buf):
        # the fake device: parity from the CPU codec, finished on submit
        data = buf if buf.ndim == 2 else \
            buf.transpose(1, 0, 2).reshape(buf.shape[1], -1)
        h = _SlowHandle(enc._cpu_parity(data), seq["n"])
        seq["n"] += 1
        return h

    def slow_fetch(h):
        time.sleep(LINK_S)  # the wire, paid by the fetching thread
        order.append(h.seq)
        return h.parity

    enc._dispatch = slow_dispatch
    enc._fetch = slow_fetch
    out = str(tmp_path / "slow_async")
    fi.enable("ec.dispatch", delay=HOST_S)
    try:
        enc.encode_file(base + ".dat", out,
                        large_block_size=large, small_block_size=small)
    finally:
        fi.clear()
    # host-blocked time: the producer thread's drain stalls
    spans = tracer.snapshot()
    producer = next(s.tid for s in spans
                    if s.name == "pipeline.encode_file")
    blocked = sum(s.duration for s in spans if s.tid == producer
                  and s.name in ("pipeline.drain_wait", "pipeline.drain"))
    eff = 1.0 - blocked / enc.stats["wall_s"]
    n = enc.stats["dispatches"]
    assert n >= 16  # enough dispatches for the pipeline to fill
    # FIFO: the drainer fetches dispatches strictly in submission order
    assert order == list(range(n))
    # the link latency hides under host work: the host was blocked for
    # at most the pipeline tail, not LINK_S per dispatch
    assert eff >= 0.6, enc.stats
    # the concurrent fetch track carries the injected latency (only the
    # RESIDUAL wait: the part that already elapsed under host work is
    # exactly the latency the async drain hid)
    assert enc.stats["drain_s"] >= LINK_S
    assert enc.stats["drain_pool"] == 1
    # parity-only drain: exactly r/k of bytes_in crossed back
    assert enc.stats["parity_bytes_drained"] == \
        enc.stats["bytes_in"] * 4 // 10
    # byte-identical shards AND sidecar (write-order crc stream intact)
    assert _shards(out, 14) == _shards(ref, 14)
    assert open(sidecar_path(out), "rb").read() == \
        open(sidecar_path(ref), "rb").read()


def test_default_data_lanes_bounds():
    assert default_data_lanes(1) == 1
    assert default_data_lanes(2) == 1
    assert default_data_lanes(4) == 3
    assert default_data_lanes(13) == 5
    assert default_data_lanes(64) == 5


def test_default_drain_pool_bounds():
    assert default_drain_pool(1) == 1
    assert default_drain_pool(2) == 1
    assert default_drain_pool(4) == 3
    assert default_drain_pool(64) == 4


def test_async_drain_device_engine_byte_identical(tmp_path):
    """The jax device path (XLA kernel on the CPU backend) through the
    async multi-buffered drain: fetches run on the drainer pool, the
    writer appends FIFO — bytes must not care."""
    base = _write_dat(tmp_path, 123_457, name="adev")
    ref = _cpu_reference(tmp_path, base, 10_000, 100)
    enc = StreamingEncoder(10, 4, engine="device")
    enc.dispatch_b = 4096
    enc.encode_file(base + ".dat", base,
                    large_block_size=10_000, small_block_size=100)
    assert enc.stats["drain_pool"] >= 1
    assert enc.stats["parity_bytes_drained"] > 0
    assert _shards(base, 14) == _shards(ref, 14)


def test_plan_entries_covers_file_exactly():
    k, large, small = 10, 1000, 100
    size = 3 * k * large + 2 * k * small + 57
    seen = 0
    rows = set()
    for n, row_start, block, off in _plan_entries(size, k, large, small, 256):
        assert n <= 256
        seen += n * k
        rows.add((row_start, block))
    # every row contributes exactly k*block bytes of (padded) stripe
    padded = sum(k * b for _, b in rows)
    assert seen == padded
    # rows tile the file: last row start + k*block >= size
    assert max(rs + k * b for rs, b in rows) >= size


def test_reencode_over_stale_shards_byte_identical(tmp_path):
    """The mmap path reuses existing shard files without truncating to
    zero (page-cache preservation); every byte must still come from the
    NEW encode — stale bytes from a previous, different, LARGER encode
    must not leak through, including in zero-padded tail regions."""
    enc = StreamingEncoder(10, 4)
    big = _write_dat(tmp_path, 3 * 1024 * 1024 + 517, name="big")
    enc.encode_file(big + ".dat", str(tmp_path / "out"), 1 << 20, 64 << 10)
    small_size = 700 * 1024 + 13  # shrinks shard files, tail-heavy
    small = _write_dat(tmp_path, small_size, name="small")
    enc.encode_file(small + ".dat", str(tmp_path / "out"), 1 << 20, 64 << 10)
    (tmp_path / "fresh.dat").write_bytes((tmp_path / "small.dat").read_bytes())
    enc.encode_file(str(tmp_path / "fresh.dat"), str(tmp_path / "fresh"),
                    1 << 20, 64 << 10)
    for i in range(14):
        reused = (tmp_path / ("out" + to_ext(i))).read_bytes()
        clean = (tmp_path / ("fresh" + to_ext(i))).read_bytes()
        assert reused == clean, f"shard {i} differs after reuse"


@pytest.mark.parametrize("make", [
    lambda: StreamingEncoder(10, 4),
    None,  # CPU path exercised via encoder.rebuild_ec_files
])
def test_failed_rebuild_leaves_no_empty_shards(tmp_path, make):
    """A rebuild aborted by a survivor size mismatch must NOT leave
    zero-length .ecNN files that mask the missing shards on retry."""
    base = _write_dat(tmp_path, 50_000, name="fr")
    encoder.write_ec_files(base, ReedSolomon(10, 4),
                           large_block_size=10_000, small_block_size=100)
    os.unlink(base + to_ext(2))
    # corrupt a survivor's size so validation fails
    with open(base + to_ext(5), "ab") as f:
        f.write(b"extra")
    with pytest.raises(ValueError, match="size mismatch"):
        if make is None:
            encoder.rebuild_ec_files(base, ReedSolomon(10, 4), chunk=512)
        else:
            make().rebuild_files(base)
    assert not os.path.exists(base + to_ext(2))  # no empty ghost shard


# --- whole groups of small-block rows, dispatched from the mapped .dat ---
# 1 KiB blocks and 4 KiB dispatches: a group is 4 rows, 40 KiB of .dat
MAP_LARGE, MAP_SMALL, MAP_B = 1 << 20, 1024, 4096
GROUP = 10 * MAP_B
ROW = 10 * MAP_SMALL


def _device_enc(kernel="xla", **kw):
    """The staged plane (one device) at the mapped geometry; "pallas"
    runs the chip's word kernel in interpret mode, one tile a block."""
    enc = StreamingEncoder(10, 4, engine="device", devices="1", **kw)
    enc.dispatch_b = MAP_B
    return _interpret_pallas(enc) if kernel == "pallas" else enc


def _files(base, total=14):
    return _shards(base, total) + [open(base + ".eci", "rb").read()]


def _dat_mapped(path) -> bool:
    with open("/proc/self/maps") as f:
        return path in f.read()


@pytest.mark.parametrize("kernel,depth", [
    ("xla", 3), ("pallas", 3), ("xla", 1)],
    ids=["xla", "pallas", "xla-depth1"])
@pytest.mark.parametrize("size,want", [
    (3 * GROUP, (3, 3)),                  # an exact multiple of a group
    (3 * GROUP + 3 * ROW + 77, (4, 3)),   # last group ends past EOF
    (GROUP - 1, (1, 0)),                  # smaller than one group
], ids=["groups", "partial-row", "under-a-group"])
def test_mapped_rows_byte_identical(tmp_path, size, want, kernel, depth):
    """Whole groups go to the kernel as [rows, K, W] views of the mapped
    .dat, the rest through a slot: shards and the .eci sidecar equal the
    CPU encoder's, and each dispatch says where its input came from,
    at the default depth and with one dispatch in flight behind the
    one being filled."""
    base = _write_dat(tmp_path, size)
    ref = _cpu_reference(tmp_path, base, MAP_LARGE, MAP_SMALL)
    tracer = Tracer(capacity=1 << 12)
    enc = _device_enc(kernel, tracer=tracer, depth=depth)
    enc.encode_file(base + ".dat", base, MAP_LARGE, MAP_SMALL)
    assert _files(base) == _files(ref)
    assert (enc.stats["dispatches"], enc.stats["mapped_dispatches"]) == want
    spans = tracer.snapshot()
    sources = [s.attrs["source"] for s in spans
               if s.name == "pipeline.dispatch"]
    assert sources.count("map") == want[1] and len(sources) == want[0]
    assert enc.stats["fallbacks"] == 0
    assert not _dat_mapped(base + ".dat")


def test_mapped_rows_resume_mid_group(tmp_path):
    """A resume from a checkpoint inside a group: the dispatches after it
    pack whole rows again and go mapped, and the shards and sidecar
    repaired past the checkpoint equal a clean encode's."""
    base = _write_dat(tmp_path, 3 * GROUP + 3 * ROW + 77)
    ref = _cpu_reference(tmp_path, base, MAP_LARGE, MAP_SMALL)
    enc = _device_enc()
    enc.encode_file(base + ".dat", base, MAP_LARGE, MAP_SMALL)
    ck_entry, ck_byte = 6, 6 * MAP_SMALL  # group 1 holds entries 4..7
    for i in range(14):
        with open(base + to_ext(i), "r+b") as f:
            f.seek(ck_byte)
            f.write(b"\xAA" * len(f.read()))
    enc._encode_file_staged(base + ".dat", base, MAP_LARGE, MAP_SMALL,
                            start_entry=ck_entry, start_byte=ck_byte)
    assert enc.stats["mapped_dispatches"] == 2  # entries 6..9, 10..13
    assert _files(base) == _files(ref)


def test_large_block_rows_take_slots(tmp_path):
    """Large-block rows chunked across dispatches (8 KiB blocks, 4 KiB
    dispatches) are filled into slots; the small rows after them go
    mapped; bytes as the CPU encoder's."""
    large = 2 * MAP_B
    base = _write_dat(tmp_path, 3 * 10 * large)  # 2 large rows, 8 small
    ref = _cpu_reference(tmp_path, base, large, MAP_SMALL)
    enc = _device_enc()
    enc.encode_file(base + ".dat", base, large, MAP_SMALL)
    assert (enc.stats["dispatches"], enc.stats["mapped_dispatches"]) == (6, 2)
    assert _files(base) == _files(ref)


PLANES = ["slot", "map", "queue2", "queue4", "rebuild"]


@pytest.mark.parametrize("plane", PLANES)
@pytest.mark.parametrize("point", ["ec.dispatch", "ec.drain"])
def test_fault_falls_back_per_dispatch(tmp_path, point, plane):
    """One dispatch or drain fault falls back to the CPU codec for that
    dispatch alone, on every plane: slots and mapped rows on one device,
    per-device queues on two and four.  The shards and the .eci equal
    the CPU encoder's; the fallback is counted once in the call's
    stats, once in engine_fallbacks{reason} and once in the event
    journal.  A rebuild has no fault point of its own: "rebuild"
    repairs the shards such an encode wrote, on the device, with no
    fallback."""
    from seaweedfs_tpu.observability.events import get_journal
    from seaweedfs_tpu.stats import ec_pipeline_metrics

    mapped = plane in ("map", "rebuild")
    large, small = (MAP_LARGE, MAP_SMALL) if mapped else (10_000, 100)
    base = _write_dat(tmp_path, 3 * GROUP + 3 * ROW + 77)
    ref = _cpu_reference(tmp_path, base, large, small)
    tracer = Tracer(capacity=1 << 12)
    devices = {"queue2": "2", "queue4": "4"}.get(plane, "1")
    enc = StreamingEncoder(10, 4, engine="device", devices=devices,
                           tracer=tracer)
    enc.dispatch_b = MAP_B
    reason = point.split(".")[1] + "_fault"
    m = ec_pipeline_metrics()
    f0 = m.engine_fallbacks.value(reason)
    seen = get_journal().query(limit=1)
    seq0 = seen[-1]["seq"] if seen else 0
    fi.enable(point, error_rate=1.0, max_hits=1)
    try:
        enc.encode_file(base + ".dat", base, large, small)
        hits = fi.fired(point)
    finally:
        fi.clear()
    assert _files(base) == _files(ref)
    assert hits == 1
    assert enc.stats["fallbacks"] == hits
    assert m.engine_fallbacks.value(reason) - f0 == hits
    events = get_journal().query(type_="engine_fallback", since_seq=seq0,
                                 limit=0)
    assert [(e["details"]["reason"], e["details"]["engine"])
            for e in events] == [(reason, "device")]
    assert any(s.attrs.get("reason") == reason and s.attrs["dispatch"] == 0
               for s in tracer.snapshot() if s.name == "pipeline.fallback")
    if plane == "map":
        assert enc.stats["mapped_dispatches"] == 3
    if plane == "rebuild":
        for i in (0, 3, 11, 13):
            os.unlink(base + to_ext(i))
        assert enc.rebuild_files(base) == [0, 3, 11, 13]
        assert enc.stats["fallbacks"] == 0
        assert _files(base) == _files(ref)


@pytest.mark.parametrize("engine", ["host", "auto", "cpu"])
def test_removed_engine_values_rejected(engine):
    """The streaming encoder is the device encoder alone: any other
    engine is refused at construction, naming the CPU path."""
    with pytest.raises(ValueError, match="ec/encoder.write_ec_files"):
        StreamingEncoder(10, 4, engine=engine)


@pytest.mark.parametrize("lag_s", [0.0, 0.05], ids=["even", "data-lags"])
def test_map_closed_after_clean_and_failed_attempts(tmp_path, monkeypatch,
                                                    lag_s):
    """The .dat map is closed after a clean encode, and after an attempt
    that failed with a mapped dispatch in flight (the retry resumes from
    the checkpoint): no BufferError, no map of the .dat left, whether the
    data writes keep pace with the drain or lag it."""
    import threading
    import time

    base = _write_dat(tmp_path, 3 * GROUP + 3 * ROW + 77)
    ref = _cpu_reference(tmp_path, base, MAP_LARGE, MAP_SMALL)
    enc = _device_enc(depth=1)
    enc.encode_file(base + ".dat", base, MAP_LARGE, MAP_SMALL)
    assert not _dat_mapped(base + ".dat")

    real, calls, lock = StreamingEncoder._write_data, [], threading.Lock()

    def failing_third(self, outputs, sb, src, used):
        with lock:  # the data lanes call it concurrently
            calls.append(1)
            n = len(calls)
        time.sleep(lag_s)
        if n == 3:  # dispatch 2's data on one lane, an early one on several
            raise OSError("injected data write failure")
        return real(self, outputs, sb, src, used)

    monkeypatch.setattr(StreamingEncoder, "_write_data", failing_third)
    enc.encode_file(base + ".dat", base, MAP_LARGE, MAP_SMALL)
    assert enc.stats["retries"] == 1
    assert not _dat_mapped(base + ".dat")
    assert _files(base) == _files(ref)


def test_volume_geometry_maps_twelve_of_thirteen(tmp_path):
    """The benchmark volume's row arithmetic (1,073,890,064 bytes, 1 MiB
    rows, 8 MiB dispatches) at a 1,024th of its size: 103 rows, 13
    dispatches, the last partial, 12 mapped; the process counters that
    /status reports as EcPipeline count them."""
    from seaweedfs_tpu.stats import ec_pipeline_metrics

    size = 1_073_890_064 // 1024
    base = _write_dat(tmp_path, size)
    enc = _device_enc()
    enc.dispatch_b = 8 * MAP_SMALL
    t0 = ec_pipeline_metrics().totals()
    enc.encode_file(base + ".dat", base, MAP_LARGE, MAP_SMALL)
    t1 = ec_pipeline_metrics().totals()
    assert (enc.stats["dispatches"], enc.stats["mapped_dispatches"]) == (13, 12)
    assert (t1["dispatches"] - t0["dispatches"],
            t1["mapped_dispatches"] - t0["mapped_dispatches"]) == (13, 12)



# --- rebuilds dispatched from the survivors' maps ---
# 1 KiB rows on a 4 KiB dispatch: a shard grows 1 KiB for every 10 KiB
# of .dat; (dat bytes, dispatches, of them mapped)
REBUILD_SIZES = {
    "whole": (12 * ROW - 77, 3, 3),       # 12 KiB shards: whole chunks
    "tail": (13 * ROW - 5, 4, 3),         # 13 KiB: and a 1 KiB tail
    "under": (2 * ROW - 3, 1, 0),         # 2 KiB: the tail alone
}


def _encoded(tmp_path, size, name="r"):
    """A volume encoded by the CPU path, its .eci at 1 KiB blocks, so
    every 4 KiB rebuild chunk is verified inline."""
    base = _write_dat(tmp_path, size, name=name)
    encoder.write_ec_files(base, ReedSolomon(10, 4), MAP_LARGE, MAP_SMALL,
                           sidecar_block_size=MAP_SMALL)
    return base


def _assert_released(enc, base):
    """No survivor map, no descriptor of a shard, and no map left for
    the encoder to reap."""
    assert enc._stale_maps == []
    shards = {os.path.realpath(base + to_ext(i)) for i in range(14)}
    with open("/proc/self/maps") as f:
        maps = f.read()
    assert not [p for p in shards if p in maps]
    held = set()
    for fd in os.listdir("/proc/self/fd"):
        try:
            held.add(os.readlink(f"/proc/self/fd/{fd}"))
        except OSError:
            pass
    assert not shards & held


@pytest.mark.parametrize("kernel", ["xla", "pallas"])
@pytest.mark.parametrize("kill", [[3], [0, 3, 11, 13]], ids=["one", "four"])
@pytest.mark.parametrize("case", sorted(REBUILD_SIZES))
def test_mapped_rebuild_byte_identical(tmp_path, case, kill, kernel):
    """Whole chunks go up as each survivor's span of its map, a tail
    through the one slot: the rebuilt shards equal the original ones
    and the CPU path's rebuild, each dispatch says where its input came
    from and is counted where /status reads EcPipeline, and the inline
    verify ran on the mapped bytes."""
    import shutil

    from seaweedfs_tpu.stats import ec_pipeline_metrics

    size, dispatches, mapped = REBUILD_SIZES[case]
    base = _encoded(tmp_path, size)
    want = _files(base)
    assert len(want[0]) == (size + ROW - 1) // ROW * MAP_SMALL
    ref = str(tmp_path / "ref")
    for ext in [to_ext(i) for i in range(14)] + [".eci"]:
        shutil.copy(base + ext, ref + ext)
    for i in kill:
        os.unlink(base + to_ext(i))
        os.unlink(ref + to_ext(i))
    encoder.rebuild_ec_files(ref, ReedSolomon(10, 4), chunk=MAP_SMALL)
    tracer = Tracer(capacity=1 << 12)
    enc = _device_enc(kernel, tracer=tracer)
    t0 = ec_pipeline_metrics().totals()
    assert enc.rebuild_files(base) == kill
    t1 = ec_pipeline_metrics().totals()
    assert _files(base) == want == _files(ref)
    assert (enc.stats["dispatches"], enc.stats["mapped_dispatches"]) == \
        (dispatches, mapped)
    assert (t1["dispatches"] - t0["dispatches"],
            t1["mapped_dispatches"] - t0["mapped_dispatches"]) == \
        (dispatches, mapped)
    sources = [s.attrs["source"] for s in tracer.snapshot()
               if s.name == "pipeline.dispatch"]
    assert sources == ["map"] * mapped + ["slot"] * (dispatches - mapped)
    fills = [s for s in tracer.snapshot() if s.name == "pipeline.fill"]
    assert len(fills) == dispatches - mapped
    assert enc.stats["verify_s"] > 0
    _assert_released(enc, base)


def test_mapped_rebuild_demotes_corrupt_survivor(tmp_path):
    """A survivor whose mapped chunk fails its crc is demoted before
    that chunk is dispatched (the first attempt sends chunks 0 and 1
    only), and the retry regenerates it and the lost shard byte for
    byte, with every map closed after either attempt."""
    base = _encoded(tmp_path, REBUILD_SIZES["tail"][0])
    want = _files(base)
    os.unlink(base + to_ext(3))
    tracer = Tracer(capacity=1 << 12)
    enc = _device_enc(tracer=tracer)
    fi.enable("ec.shard.corrupt",
              params={"shard": 5, "offset": 2 * MAP_B + 100, "bit": 3},
              max_hits=1)
    try:
        got = enc.rebuild_files(base)
        hits = fi.fired("ec.shard.corrupt")
    finally:
        fi.clear()
    assert hits == 1
    assert got == [3, 5]
    assert _files(base) == want
    assert [s.attrs["dispatch"] for s in tracer.snapshot()
            if s.name == "pipeline.dispatch"] == [0, 1, 0, 1, 2, 3]
    assert enc.stats["mapped_dispatches"] == 3
    _assert_released(enc, base)


@pytest.mark.parametrize("at", [1, 3], ids=["mapped", "tail-slot"])
def test_failed_mapped_rebuild_leaves_nothing(tmp_path, at):
    """A dispatch that raises, a mapped one with one in flight before
    it or the tail's with three: no partial output survives, every map
    and descriptor is released as the call unwinds, and the next
    rebuild is byte-identical."""
    base = _encoded(tmp_path, REBUILD_SIZES["tail"][0])
    want = _files(base)
    for i in (3, 12):
        os.unlink(base + to_ext(i))
    enc = _device_enc()
    real, calls = enc._kernel, []

    def failing(planes, words):
        calls.append(type(words))
        if len(calls) == at + 1:
            raise RuntimeError("injected dispatch failure")
        return real(planes, words)

    enc._kernel = failing
    with pytest.raises(RuntimeError, match="injected dispatch failure"):
        enc.rebuild_files(base)
    assert (calls[-1] is tuple) == (at < 3)  # the tail goes as a slot
    assert not os.path.exists(base + to_ext(3))
    assert not os.path.exists(base + to_ext(12))
    _assert_released(enc, base)
    enc._kernel = real
    assert enc.rebuild_files(base) == [3, 12]
    assert _files(base) == want
    _assert_released(enc, base)


# --- data shards written on shard-owned lanes ---
# (dat bytes, devices): whole groups only, so every dispatch is mapped;
# groups and a tail filled into a slot; the per-device queue plane
LANE_PLANES = {
    "map": (3 * GROUP, "1"),
    "tail": (3 * GROUP + 3 * ROW + 77, "1"),
    "queue": (3 * GROUP + 3 * ROW + 77, "4"),
}


def _data_writes(spans):
    return [s for s in spans
            if s.name == "pipeline.write" and s.attrs.get("kind") == "data"]


@pytest.mark.parametrize("lanes", [1, 2, 5])
@pytest.mark.parametrize("plane", sorted(LANE_PLANES))
def test_laned_encode_byte_identical(tmp_path, plane, lanes):
    """At one, two and five data lanes, on mapped row groups, a slot
    tail and the queue plane: shards and .eci equal the CPU encoder's;
    every dispatch is written once by each lane, under a span that
    says its lane, on the lane's own thread, never the producer's."""
    size, devices = LANE_PLANES[plane]
    base = _write_dat(tmp_path, size)
    ref = _cpu_reference(tmp_path, base, MAP_LARGE, MAP_SMALL)
    tracer = Tracer(capacity=1 << 12)
    enc = StreamingEncoder(10, 4, engine="device", devices=devices,
                           tracer=tracer)
    enc.dispatch_b = MAP_B
    enc._data_lanes = lanes
    enc.encode_file(base + ".dat", base, MAP_LARGE, MAP_SMALL)
    assert _files(base) == _files(ref)
    assert enc.stats["data_lanes"] == lanes
    assert enc.stats["mapped_dispatches"] == \
        (0 if plane == "queue" else 3)
    spans = tracer.snapshot()
    writes = _data_writes(spans)
    d = enc.stats["dispatches"]
    assert sorted((s.attrs["dispatch"], s.attrs["lane"]) for s in writes) \
        == [(i, j) for i in range(d) for j in range(lanes)]
    producer = next(s.tid for s in spans if s.name == "pipeline.encode_file")
    assert not any(s.tid == producer for s in writes)
    assert len({s.tid for s in writes}) == lanes
    assert any(s.name == "pipeline.data_wait" and s.tid == producer
               and s.attrs.get("wait") == "final" for s in spans)


@pytest.mark.parametrize("lanes", [1, 5])
def test_lane_data_bytes_add_up(tmp_path, lanes):
    """The lanes' data-bytes counter adds up to the data shards' bytes,
    at one lane as at five, and /status EcPipeline reports it as
    lane_data_bytes."""
    from seaweedfs_tpu.stats import ec_pipeline_metrics

    base = _write_dat(tmp_path, 3 * GROUP + 3 * ROW + 77)
    enc = _device_enc()
    enc._data_lanes = lanes
    m = ec_pipeline_metrics()
    c0 = m.lane_data_bytes.value()
    t0 = m.totals()
    enc.encode_file(base + ".dat", base, MAP_LARGE, MAP_SMALL)
    shard = os.path.getsize(base + to_ext(0))
    assert m.lane_data_bytes.value() - c0 == 10 * shard
    assert m.totals()["lane_data_bytes"] - t0["lane_data_bytes"] == \
        10 * shard


@pytest.mark.parametrize("lanes", [1, 2, 5])
def test_lane_write_failure_resumes_from_checkpoint(tmp_path, monkeypatch,
                                                    lanes):
    """A data write that raises on a lane, once the checkpoint has
    passed byte 0, fails the attempt as a drain fault would: the encode
    retries from the checkpoint, and shards and .eci come out as the
    CPU encoder's, with the .dat's map closed."""
    import threading

    base = _write_dat(tmp_path, 6 * GROUP + 3 * ROW + 77)
    ref = _cpu_reference(tmp_path, base, MAP_LARGE, MAP_SMALL)
    tracer = Tracer(capacity=1 << 12)
    enc = _device_enc(tracer=tracer, depth=1)
    enc._data_lanes = lanes
    real, lock = StreamingEncoder._write_data, threading.Lock()
    per_thread: dict = {}
    fired = []

    def failing(self, outputs, sb, src, used):
        with lock:
            n = per_thread[threading.get_ident()] = \
                per_thread.get(threading.get_ident(), 0) + 1
            fire = n == 3 and not fired  # this lane's dispatch 2
            if fire:
                fired.append(1)
        if fire:
            deadline = time.monotonic() + 30
            while enc._ckpt[1] == 0 and time.monotonic() < deadline:
                time.sleep(0.002)
            raise OSError("injected lane write failure")
        return real(self, outputs, sb, src, used)

    monkeypatch.setattr(StreamingEncoder, "_write_data", failing)
    enc.encode_file(base + ".dat", base, MAP_LARGE, MAP_SMALL)
    assert fired
    assert enc.stats["retries"] == 1
    retries = [s for s in tracer.snapshot() if s.name == "pipeline.retry"]
    assert len(retries) == 1 and retries[0].attrs["resume_byte"] > 0
    assert "injected lane write failure" in retries[0].attrs["error"]
    assert _files(base) == _files(ref)
    assert not _dat_mapped(base + ".dat")


@pytest.mark.parametrize("plane", ["slot", "queue"])
def test_slow_lanes_hold_slots_until_written(tmp_path, monkeypatch, plane):
    """Lanes that write slower than the device fetches: a slot goes back
    to its pool only once the lanes have written it (a `pipeline.
    data_wait` with wait=slot), so the producer never refills one the
    lanes still read, and the bytes equal the CPU encoder's.  "slot":
    large-block rows chunked into the staged plane's slots; "queue":
    the per-device queue plane, whose every dispatch is a slot."""
    real = StreamingEncoder._write_data

    def slow(self, outputs, sb, src, used):
        time.sleep(0.01)
        return real(self, outputs, sb, src, used)

    monkeypatch.setattr(StreamingEncoder, "_write_data", slow)
    large = 2 * MAP_B
    base = _write_dat(tmp_path, 3 * 10 * large)  # 2 large rows, 8 small
    ref = _cpu_reference(tmp_path, base, large, MAP_SMALL)
    tracer = Tracer(capacity=1 << 12)
    enc = StreamingEncoder(10, 4, engine="device", tracer=tracer,
                           devices="1" if plane == "slot" else "2",
                           depth=1)
    enc.dispatch_b = MAP_B
    enc._data_lanes = 2
    enc.encode_file(base + ".dat", base, large, MAP_SMALL)
    assert _files(base) == _files(ref)
    waits = [s.attrs.get("wait") for s in tracer.snapshot()
             if s.name == "pipeline.data_wait"]
    assert "slot" in waits


def test_lanes_stress_at_short_switch_interval(tmp_path):
    """Ten lanes, more than this host's cores may hold, switching threads
    every few microseconds, over the queue plane's slots and a tail:
    each shard's bytes and crc state stay with their lane, so shards and
    .eci still equal the CPU encoder's, and no data byte is counted
    twice or lost."""
    import sys

    from seaweedfs_tpu.stats import ec_pipeline_metrics

    base = _write_dat(tmp_path, 6 * GROUP + 3 * ROW + 77)
    ref = _cpu_reference(tmp_path, base, MAP_LARGE, MAP_SMALL)
    enc = StreamingEncoder(10, 4, engine="device", devices="2", depth=1)
    enc.dispatch_b = MAP_B
    enc._data_lanes = 10
    lane0 = ec_pipeline_metrics().lane_data_bytes.value()
    interval = sys.getswitchinterval()
    sys.setswitchinterval(5e-6)
    try:
        enc.encode_file(base + ".dat", base, MAP_LARGE, MAP_SMALL)
    finally:
        sys.setswitchinterval(interval)
    assert _files(base) == _files(ref)
    assert ec_pipeline_metrics().lane_data_bytes.value() - lane0 == \
        10 * os.path.getsize(base + to_ext(0))


@pytest.mark.parametrize("native", [True, False])
def test_encode_names_its_block_crc(tmp_path, monkeypatch, native):
    """The path of the .eci block crcs shows where an encode ran: on
    its `pipeline.encode_file` span and in /status EcPipeline, native
    where the data plane library is loaded and the fallback's name where
    it is not; the sidecar is the same either way."""
    from seaweedfs_tpu.stats import ec_pipeline_metrics
    from seaweedfs_tpu.storage import crc

    assert crc.load_block_crc() == "native"  # the library is built here
    if not native:
        monkeypatch.setattr(crc, "_blocks", False)
    want = "native" if native else crc._FALLBACK
    base = _write_dat(tmp_path, 3 * GROUP + 3 * ROW + 77)
    ref = _cpu_reference(tmp_path, base, MAP_LARGE, MAP_SMALL)
    tracer = Tracer(capacity=1 << 12)
    enc = _device_enc(tracer=tracer)
    enc.encode_file(base + ".dat", base, MAP_LARGE, MAP_SMALL)
    assert _files(base) == _files(ref)
    roots = [s for s in tracer.snapshot() if s.name == "pipeline.encode_file"]
    assert [s.attrs["crc"] for s in roots] == [want]
    assert ec_pipeline_metrics().totals()["block_crc"] == want
