"""EC shard bit-rot defense drills: `.eci` sidecars, verify-on-use
rebuild/read, and the scrubber's quarantine+repair loop.

The contract under test (ec/integrity.py + encoder/ec_volume/streaming
verify paths + volume_server/scrubber.py): a bit flip in ANY single
shard — injected on disk or through the ec.shard.corrupt fault point —
is detected, demoted to an erasure, and reconstruction output stays
byte-identical to the clean CPU-codec result; with more than
parity_shards corrupt shards the operation raises ShardCorruptError
instead of emitting silent garbage; the scrubber finds rot at rest,
quarantines `.ecNN` -> `.ecNN.bad`, and repairs via rebuild while
>= data_shards clean shards remain.  All of it observable: the
SeaweedFS_ec_corrupt_shards_total / _scrub_* counters and
pipeline.retry(reason=corrupt_shard) spans.
"""

from __future__ import annotations

import os
import time

import numpy as np
import pytest

from seaweedfs_tpu.ec import encoder as ec_encoder
from seaweedfs_tpu.ec.codec import ReedSolomon
from seaweedfs_tpu.ec.ec_volume import EcVolume
from seaweedfs_tpu.ec.integrity import (EciSidecar, ShardCorruptError,
                                        SidecarBuilder, backfill_sidecar,
                                        verify_shard_file)
from seaweedfs_tpu.ec.layout import to_ext
from seaweedfs_tpu.observability import disable_tracing, enable_tracing
from seaweedfs_tpu.stats import ec_integrity_metrics
from seaweedfs_tpu.storage import idx as idx_mod
from seaweedfs_tpu.storage.needle import Needle, get_actual_size
from seaweedfs_tpu.storage.types import Version, size_is_valid
from seaweedfs_tpu.storage.volume import Volume
from seaweedfs_tpu.utils import faultinject as fi

LARGE, SMALL, CHUNK = 10_000, 100, 50  # ec_test.go shrunk geometry
BS = 512  # sidecar crc block for tests: several blocks per shard

rng = np.random.default_rng(11)


def _write_test_volume(tmp_path, vid=1, n_needles=80):
    v = Volume(str(tmp_path), "", vid)
    for i in range(1, n_needles + 1):
        v.write_needle(Needle(cookie=i, id=i,
                              data=rng.bytes(int(rng.integers(1, 800)))))
    v.close()
    return os.path.join(str(tmp_path), str(vid))


def _encode(base, rs=None):
    rs = rs or ReedSolomon(10, 4)
    ec_encoder.write_ec_files(base, rs, LARGE, SMALL, chunk=CHUNK,
                              sidecar_block_size=BS)
    ec_encoder.write_sorted_file_from_idx(base)
    return rs


def _shards(base):
    return {i: open(base + to_ext(i), "rb").read() for i in range(14)
            if os.path.exists(base + to_ext(i))}


def _flip(path, offset, bit=0):
    with open(path, "r+b") as f:
        f.seek(offset)
        c = f.read(1)
        f.seek(offset)
        f.write(bytes([c[0] ^ (1 << bit)]))


@pytest.fixture()
def tracer():
    tr = enable_tracing()
    tr.clear()
    try:
        yield tr
    finally:
        disable_tracing()
        tr.clear()


# --- sidecar format -------------------------------------------------------

def test_encode_writes_sidecar_matching_backfill(tmp_path):
    """write_ec_files builds the `.eci` incrementally as shards stream
    out; it must equal a from-scratch backfill of the finished files."""
    base = _write_test_volume(tmp_path)
    _encode(base)
    sc = EciSidecar.load(base)
    assert sc is not None and sc.present_mask == (1 << 14) - 1
    assert sc.shard_size == os.path.getsize(base + to_ext(0))
    streamed = sc.crcs.copy()
    sc2 = backfill_sidecar(base, block_size=BS)
    assert np.array_equal(streamed, sc2.crcs)
    assert sc2.shard_size == sc.shard_size
    for i in range(14):
        assert verify_shard_file(sc2, base + to_ext(i), i) == []


def test_rotted_sidecar_reads_as_absent(tmp_path):
    """A corrupt sidecar must fail its own table crc and load as None —
    never mass-demote healthy shards."""
    base = _write_test_volume(tmp_path)
    _encode(base)
    _flip(base + ".eci", 40)
    assert EciSidecar.load(base) is None
    # rebuild still works, just unverified
    want = _shards(base)
    os.remove(base + to_ext(3))
    assert ec_encoder.rebuild_ec_files(base, ReedSolomon(10, 4)) == [3]
    assert _shards(base) == want


def test_sidecar_is_stale_needs_full_disagreement():
    """Stale = EVERY local shard disagrees AND there are >= 2 of them;
    a lone disagreeing shard (single-shard holder included) is
    truncation rot, never grounds to discredit the table."""
    from seaweedfs_tpu.ec.integrity import sidecar_is_stale

    sc = EciSidecar(512, 1000, np.zeros((14, 2), dtype=np.uint32),
                    (1 << 14) - 1)
    assert sidecar_is_stale(sc, [999, 999]) is True
    assert sidecar_is_stale(sc, [1000, 999]) is False  # one truncated
    assert sidecar_is_stale(sc, [999]) is False  # single-shard holder
    assert sidecar_is_stale(sc, []) is False
    assert sidecar_is_stale(None, [999, 999]) is False


def test_sidecar_builder_rejects_unequal_streams():
    b = SidecarBuilder(3, 256)
    b.update(0, b"x" * 100)
    b.update(1, b"y" * 99)
    with pytest.raises(ValueError, match="unequal"):
        b.finalize()


# --- verify-on-use: rebuild ----------------------------------------------

@pytest.mark.parametrize("corrupt_sid", [3, 12])  # one data, one parity
def test_rebuild_demotes_corrupt_survivor(tmp_path, tracer, corrupt_sid):
    """On-disk bit rot in a survivor (data or parity): the rebuild
    detects it, demotes the shard to an erasure, retries with an
    alternate survivor set, REGENERATES the rotted shard, and every
    output is byte-identical to the clean encode."""
    base = _write_test_volume(tmp_path)
    rs = _encode(base)
    orig = _shards(base)
    m = ec_integrity_metrics()
    c0 = m.corrupt_shards.value("rebuild")
    os.remove(base + to_ext(5))
    _flip(base + to_ext(corrupt_sid), 1000, bit=4)
    generated = ec_encoder.rebuild_ec_files(base, rs)
    assert sorted(generated) == sorted({5, corrupt_sid})
    assert _shards(base) == orig  # byte-identical, corruption healed
    assert m.corrupt_shards.value("rebuild") - c0 == 1
    retries = [s for s in tracer.snapshot() if s.name == "pipeline.retry"
               and s.attrs.get("reason") == "corrupt_shard"]
    assert retries and retries[0].attrs["shard"] == corrupt_sid


def test_rebuild_too_many_corrupt_raises(tmp_path):
    """> parity_shards corrupt survivors: clean shards < data_shards, so
    the rebuild must raise ShardCorruptError — never silent garbage."""
    base = _write_test_volume(tmp_path)
    rs = _encode(base)
    os.remove(base + to_ext(13))
    for sid in (1, 2, 3, 6):
        _flip(base + to_ext(sid), 64)
    with pytest.raises(ShardCorruptError) as ei:
        ec_encoder.rebuild_ec_files(base, rs)
    assert set(ei.value.corrupt_shards) == {1, 2, 3, 6}
    # the missing shard must NOT have been produced from poisoned math
    assert not os.path.exists(base + to_ext(13))


def test_rebuild_faultpoint_bit_flip(tmp_path):
    """The ec.shard.corrupt fault point: an in-memory deterministic flip
    on the read path is detected exactly like on-media rot."""
    base = _write_test_volume(tmp_path)
    rs = _encode(base)
    orig = _shards(base)
    os.remove(base + to_ext(0))
    fi.enable("ec.shard.corrupt", params={"shard": 4, "offset": 777,
                                          "bit": 6})
    try:
        generated = ec_encoder.rebuild_ec_files(base, rs)
        assert fi.fired("ec.shard.corrupt") >= 1  # the flip really landed
    finally:
        fi.clear()
    assert sorted(generated) == [0, 4]
    assert _shards(base) == orig


# --- verify-on-use: EcVolume reads ---------------------------------------

def _live_needles(base):
    return [(k, o, s) for k, o, s in idx_mod.iter_index_file(base + ".idx")
            if o != 0 and size_is_valid(s)]


def test_read_detects_flip_and_reconstructs(tmp_path, tracer):
    """A flipped bit in the shard serving a needle: the verified read
    demotes the shard and the needle reconstructs byte-identical from
    the other 13."""
    base = _write_test_volume(tmp_path)
    rs = _encode(base)
    with open(base + ".dat", "rb") as f:
        dat = f.read()
    live = _live_needles(base)
    ev = EcVolume(base, large_block_size=LARGE, small_block_size=SMALL)
    try:
        key, offset, size = live[5]
        _, _, ivs = ev.locate_ec_shard_needle(key)
        sid, soff = ivs[0].to_shard_id_and_offset(LARGE, SMALL, 10)
        fi.enable("ec.shard.corrupt",
                  params={"shard": sid, "offset": soff, "bit": 1})
        try:
            blob = ev.read_needle(key, rs)
        finally:
            fi.clear()
        actual = get_actual_size(size, Version.V3)
        assert blob == dat[offset:offset + actual]
        assert sid in ev.corrupt_shards  # demoted for the whole mount
        # every other needle still reads correctly around the demotion
        for k2, o2, s2 in live[:25]:
            got = ev.read_needle(k2, rs)
            assert got == dat[o2:o2 + get_actual_size(s2, Version.V3)]
        retries = [s for s in tracer.snapshot()
                   if s.name == "pipeline.retry"
                   and s.attrs.get("reason") == "corrupt_shard"]
        assert retries and retries[0].attrs["source"] == "read"
    finally:
        ev.close()


def test_read_unrecoverable_corruption_raises(tmp_path):
    """With > parity_shards shards rotted on disk, reads that need them
    must raise ShardCorruptError, not return wrong bytes."""
    base = _write_test_volume(tmp_path)
    rs = _encode(base)
    with open(base + ".dat", "rb") as f:
        dat = f.read()
    for sid in (0, 1, 2, 3, 4):
        _flip(base + to_ext(sid), 128)
    ev = EcVolume(base, large_block_size=LARGE, small_block_size=SMALL)
    try:
        raised = False
        for key, o, s in _live_needles(base):
            try:
                got = ev.read_needle(key, rs)
                # any read that DID succeed must be correct bytes
                assert got == dat[o:o + get_actual_size(s, Version.V3)]
            except ShardCorruptError:
                raised = True
                break
        assert raised
    finally:
        ev.close()


def test_reconstruct_interval_skips_oserror_shard(tmp_path, monkeypatch):
    """A survivor that errors at the IO layer (bad sector) is skipped
    and an alternate local shard takes its place — the read succeeds
    instead of failing outright."""
    base = _write_test_volume(tmp_path)
    rs = _encode(base)
    with open(base + ".dat", "rb") as f:
        dat = f.read()
    os.remove(base + to_ext(6))  # force reconstruction for shard-6 reads
    ev = EcVolume(base, large_block_size=LARGE, small_block_size=SMALL)
    try:
        # shard 0 is always among the first-choice survivors: make its
        # reads die like a failing disk
        real = ev.shards[0].read_at

        def dying(length, offset):
            raise OSError(5, "Input/output error")

        monkeypatch.setattr(ev.shards[0], "read_at", dying)
        for key, o, s in _live_needles(base)[:25]:
            got = ev.read_needle(key, rs)
            assert got == dat[o:o + get_actual_size(s, Version.V3)]
    finally:
        ev.close()


# --- streaming encode/rebuild --------------------------------------------

def test_streaming_rebuild_demotes_corrupt_survivor(tmp_path):
    """The StreamingEncoder rebuild (after an encode on one device, the
    staged plane, and on every device, the queue plane) detects
    survivor rot via the sidecar and regenerates both the missing and
    the rotted shard byte-identical."""
    from seaweedfs_tpu.ec.streaming import StreamingEncoder

    base = str(tmp_path / "v")
    open(base + ".dat", "wb").write(
        rng.integers(0, 256, 1_200_000, dtype=np.uint8).tobytes())
    for devices in ("1", None):
        out = str(tmp_path / f"o{devices}")
        enc = StreamingEncoder(10, 4, engine="device", devices=devices,
                               dispatch_mb=1)
        enc.encode_file(base + ".dat", out, 1_000_000, 10_000)
        assert os.path.exists(out + ".eci")
        ref = _shards(out)
        os.remove(out + to_ext(7))
        _flip(out + to_ext(2), 55_555, bit=3)
        generated = enc.rebuild_files(out)
        assert sorted(generated) == [2, 7], (devices, generated)
        assert _shards(out) == ref, devices
        assert enc.stats["verify_s"] >= 0.0


def test_sidecar_survives_checkpoint_resume(tmp_path, monkeypatch):
    """PR-3 staged retries resume mid-file from a checkpoint; the
    sidecar's crc accumulators are re-seeded from the surviving prefix,
    so the final `.eci` must equal a clean run's."""
    import seaweedfs_tpu.ec.streaming as streaming_mod
    from seaweedfs_tpu.ec.streaming import StreamingEncoder

    base = str(tmp_path / "v")
    open(base + ".dat", "wb").write(
        rng.integers(0, 256, 2_000_000, dtype=np.uint8).tobytes())
    enc = StreamingEncoder(10, 4, engine="device", devices="1",
                           dispatch_mb=1, depth=1)
    enc.dispatch_b = 65536
    real = streaming_mod.preadv_into
    calls = {"n": 0}

    def flaky(f, views, off):
        calls["n"] += 1
        if calls["n"] == 15:
            # fault once the writer has retired a dispatch: the retry
            # resumes past byte 0
            deadline = time.monotonic() + 30
            while enc._ckpt[1] == 0 and time.monotonic() < deadline:
                time.sleep(0.002)
            raise OSError("injected fill IO error")
        return real(f, views, off)

    monkeypatch.setattr(streaming_mod, "preadv_into", flaky)
    out = str(tmp_path / "o")
    enc.encode_file(base + ".dat", out, 1_000_000, 10_000)
    assert enc.stats["retries"] == 1  # the drill actually resumed
    assert enc._ckpt[1] > 0
    resumed = EciSidecar.load(out)
    assert resumed is not None
    clean = backfill_sidecar(out)  # recompute from the finished shards
    assert np.array_equal(resumed.crcs, clean.crcs)
    assert resumed.shard_size == clean.shard_size


# --- scrubber -------------------------------------------------------------

def _store_with_ec_volume(tmp_path, vid=1):
    from seaweedfs_tpu.volume_server.store import Store

    _write_test_volume(tmp_path, vid=vid, n_needles=60)
    store = Store([str(tmp_path)])
    store.ec_generate(vid)
    store.ec_mount(vid)
    return store, os.path.join(str(tmp_path), str(vid))


def test_scrubber_quarantine_and_repair_roundtrip(tmp_path, tracer):
    """End to end: rot a parity shard at rest -> one scrub pass detects
    it, quarantines `.ecNN` -> `.ecNN.bad`, rebuilds it byte-identical,
    remounts, and reports verdict + counters."""
    from seaweedfs_tpu.volume_server.scrubber import EcScrubber

    store, base = _store_with_ec_volume(tmp_path)
    try:
        orig = _shards(base)
        scrub = EcScrubber(store, rate_mb_s=0)
        st = scrub.run_pass()
        assert st["verdicts"]["1"]["status"] == "clean"
        _flip(base + to_ext(11), 2048, bit=5)
        m = ec_integrity_metrics()
        r0 = m.repairs.value("repaired")
        st = scrub.run_pass()
        verdict = st["verdicts"]["1"]
        assert verdict["status"] == "repaired"
        assert verdict["corrupt_shards"] == [11]
        assert os.path.exists(base + to_ext(11) + ".bad")
        assert open(base + to_ext(11), "rb").read() == orig[11]
        assert 11 in store.ec_volumes[1].shards  # remounted whole
        assert m.repairs.value("repaired") - r0 == 1
        spans = {s.name for s in tracer.snapshot()}
        assert {"ec.scrub.pass", "ec.scrub.volume",
                "ec.scrub.quarantine"} <= spans
    finally:
        store.close()


def test_scrubber_unrepairable_quarantines_without_garbage(tmp_path):
    """Rot in 5 shards (> parity budget): the scrubber quarantines them
    and reports unrepairable — it must NOT fabricate shards."""
    from seaweedfs_tpu.volume_server.scrubber import EcScrubber

    store, base = _store_with_ec_volume(tmp_path)
    try:
        for sid in (0, 1, 2, 3, 4):
            _flip(base + to_ext(sid), 300)
        scrub = EcScrubber(store, rate_mb_s=0)
        st = scrub.run_pass()
        verdict = st["verdicts"]["1"]
        assert verdict["status"] == "unrepairable"
        assert verdict["corrupt_shards"] == [0, 1, 2, 3, 4]
        for sid in (0, 1, 2, 3, 4):
            assert os.path.exists(base + to_ext(sid) + ".bad")
            assert not os.path.exists(base + to_ext(sid))
    finally:
        store.close()


def test_scrubber_backfills_pre_sidecar_volume(tmp_path):
    """A shard set that predates sidecars gets adopted when backfill is
    on: the pass writes the `.eci` and subsequent passes verify."""
    from seaweedfs_tpu.volume_server.scrubber import EcScrubber

    store, base = _store_with_ec_volume(tmp_path)
    try:
        os.remove(base + ".eci")
        store.ec_mount(1)  # reload without sidecar
        assert store.ec_volumes[1].sidecar is None
        scrub = EcScrubber(store, rate_mb_s=0)
        st = scrub.run_pass()
        assert st["verdicts"]["1"]["status"] == "no_sidecar"
        scrub.backfill = True
        st = scrub.run_pass()
        assert st["verdicts"]["1"]["status"] == "clean"
        assert os.path.exists(base + ".eci")
    finally:
        store.close()


def test_scrubber_cursor_resumes_mid_volume(tmp_path):
    """A stop()-preserved cursor makes the next pass resume mid-volume
    (shards below the cursor skipped), then wrap clean to (0, 0)."""
    from seaweedfs_tpu.volume_server.scrubber import EcScrubber

    store, base = _store_with_ec_volume(tmp_path)
    try:
        scrub = EcScrubber(store, rate_mb_s=0)
        full_blocks = scrub.run_pass()["verdicts"]["1"]["blocks"]
        scrub.cursor = (1, 5)  # as a stop() mid-volume would leave it
        st = scrub.run_pass()
        resumed = st["verdicts"]["1"]
        assert resumed["status"] == "clean"
        assert resumed["blocks"] < full_blocks  # shards 0-4 skipped
        assert tuple(scrub.cursor) == (0, 0)  # clean wrap
    finally:
        store.close()


def test_read_truncated_shard_demotes_not_zeros(tmp_path):
    """A truncated shard must NOT serve its lost tail as trusted zeros:
    the size mismatch demotes it and needles reconstruct byte-identical
    from the other 13 (while a sidecar stale on EVERY shard — geometry
    change — still just disables verification at mount)."""
    base = _write_test_volume(tmp_path)
    rs = _encode(base)
    with open(base + ".dat", "rb") as f:
        dat = f.read()
    live = _live_needles(base)
    ev0 = EcVolume(base, large_block_size=LARGE, small_block_size=SMALL)
    key = live[10][0]
    _, _, ivs = ev0.locate_ec_shard_needle(key)
    sid, _ = ivs[0].to_shard_id_and_offset(LARGE, SMALL, 10)
    ev0.close()
    with open(base + to_ext(sid), "r+b") as f:
        f.truncate(os.path.getsize(base + to_ext(sid)) - 600)
    ev = EcVolume(base, large_block_size=LARGE, small_block_size=SMALL)
    try:
        assert ev.sidecar is not None  # one divergent shard != stale
        for k2, o2, s2 in live[:25]:
            got = ev.read_needle(k2, rs)
            assert got == dat[o2:o2 + get_actual_size(s2, Version.V3)]
        assert sid in ev.corrupt_shards
    finally:
        ev.close()


def test_scrub_stop_mid_volume_still_quarantines(tmp_path):
    """stop() mid-scan must not drop corruption already found in the
    scanned prefix: the rot is quarantined and repaired before the pass
    returns, even though the cursor resumes mid-volume."""
    from seaweedfs_tpu.volume_server.scrubber import EcScrubber

    store, base = _store_with_ec_volume(tmp_path)
    try:
        orig = _shards(base)
        _flip(base + to_ext(0), 512)  # rot in the FIRST scanned shard
        scrub = EcScrubber(store, rate_mb_s=0)
        calls = [0]

        def stop_soon():  # busy_fn: runs before every block read
            calls[0] += 1
            if calls[0] == 30:  # well past shard 0's blocks
                scrub._stop.set()
            return False

        scrub.busy_fn = stop_soon
        scrub.run_pass()
        assert scrub.cursor[0] == 1 and scrub.cursor[1] > 0  # mid-volume
        assert os.path.exists(base + to_ext(0) + ".bad")
        assert open(base + to_ext(0), "rb").read() == orig[0]
        assert scrub.verdicts[1]["status"] == "repaired"
    finally:
        store.close()


def test_scrubber_stale_sidecar_never_quarantines(tmp_path):
    """A sidecar whose geometry disagrees with EVERY present shard is
    STALE (crash between shard rewrite and sidecar rewrite) — the
    scrubber must report it, not mass-quarantine healthy shards on its
    say-so; with backfill on it re-adopts the volume instead."""
    from seaweedfs_tpu.volume_server.scrubber import EcScrubber

    store, base = _store_with_ec_volume(tmp_path)
    try:
        sc = EciSidecar.load(base)
        # perturb shard_size without changing the block count, so the
        # doctored sidecar still passes its own load-time checks
        wrong = sc.shard_size - 1 if sc.shard_size % sc.block_size == 0 \
            else sc.shard_size + 1
        EciSidecar(sc.block_size, wrong, sc.crcs, sc.present_mask).save(base)
        store.ec_mount(1)  # reload so the stale table is the live one
        scrub = EcScrubber(store, rate_mb_s=0)
        st = scrub.run_pass()
        assert st["verdicts"]["1"]["status"] == "stale_sidecar"
        for sid in range(14):
            assert os.path.exists(base + to_ext(sid)), sid
            assert not os.path.exists(base + to_ext(sid) + ".bad"), sid
        scrub.backfill = True
        st = scrub.run_pass()
        assert st["verdicts"]["1"]["status"] == "clean"
    finally:
        store.close()


def test_scrubber_detects_truncated_shard(tmp_path):
    """Blocks past EOF of a truncated shard must scan as corrupt, not
    vacuously clean: the scrubber quarantines and regenerates the full
    shard."""
    from seaweedfs_tpu.volume_server.scrubber import EcScrubber

    store, base = _store_with_ec_volume(tmp_path)
    try:
        orig = _shards(base)
        with open(base + to_ext(12), "r+b") as f:
            f.truncate(len(orig[12]) - 700)
        st = EcScrubber(store, rate_mb_s=0).run_pass()
        verdict = st["verdicts"]["1"]
        assert verdict["status"] == "repaired"
        assert verdict["corrupt_shards"] == [12]
        assert open(base + to_ext(12), "rb").read() == orig[12]
    finally:
        store.close()


def test_store_read_path_heals_corrupt_shard(tmp_path):
    """The PRODUCTION read path (Store.read_ec_needle) verifies local
    shard reads: a bit flip demotes the shard for the mount and every
    needle still reads back its exact clean bytes via reconstruction."""
    store, base = _store_with_ec_volume(tmp_path)
    try:
        ev = store.ec_volumes[1]
        live = _live_needles(base)
        clean = {k: store.read_ec_needle(1, k)[0] for k, _, _ in live[:20]}
        key = live[7][0]
        _, _, ivs = ev.locate_ec_shard_needle(key)
        sid, soff = ivs[0].to_shard_id_and_offset(
            ev.large_block_size, ev.small_block_size, ev.data_shards)
        fi.enable("ec.shard.corrupt",
                  params={"shard": sid, "offset": soff, "bit": 2})
        try:
            for k, want in clean.items():
                assert store.read_ec_needle(1, k)[0] == want, k
        finally:
            fi.clear()
        assert sid in ev.corrupt_shards
    finally:
        store.close()


# --- server routes + shell + cluster health -------------------------------

def test_scrub_routes_and_cluster_health(tmp_path):
    """/ec/scrub/start runs a pass that repairs planted rot; the verdict
    shows on /ec/scrub/status and /status, the counters ride /metrics,
    and the master's /cluster/health folds them into its degraded
    verdict (a repaired run can't pass as clean)."""
    from seaweedfs_tpu.master.server import MasterServer
    from seaweedfs_tpu.shell import CommandEnv, run_command
    from seaweedfs_tpu.utils.httpd import http_json
    from seaweedfs_tpu.volume_server.server import VolumeServer

    from tests.conftest import free_port

    d = tmp_path / "vs0"
    d.mkdir()
    base = _write_test_volume(d)
    master = MasterServer(port=free_port(), pulse_seconds=0.4).start()
    vs = VolumeServer([str(d)], master.url, port=free_port(),
                      pulse_seconds=0.4).start()
    try:
        vs.store.ec_generate(1)
        vs.store.ec_mount(1)
        orig11 = open(base + to_ext(11), "rb").read()
        _flip(base + to_ext(11), 4096)
        r = http_json("POST", f"http://{vs.url}/ec/scrub/start",
                      {"rate_mb_s": 0})
        assert r["started"] is True
        deadline = time.time() + 10
        verdict = {}
        while time.time() < deadline:
            st = http_json("GET", f"http://{vs.url}/ec/scrub/status")
            verdict = st["verdicts"].get("1", {})
            if not st["running"] and verdict:
                break
            time.sleep(0.05)
        assert verdict.get("status") == "repaired", verdict
        assert open(base + to_ext(11), "rb").read() == orig11
        status = http_json("GET", f"http://{vs.url}/status")
        assert status["EcScrub"]["verdicts"]["1"] == "repaired"
        assert status["EcIntegrity"]["corrupt_shards"] >= 1
        # shell surface
        env = CommandEnv(master.url)
        out = run_command(env, f"ec.scrub -server {vs.url} -action status")
        assert "repairs=1" in out or "repairs=" in out
        assert "corrupt=" in out
        # master rollup: the scrub counters mark the cluster degraded
        vs.heartbeat_now()
        health = http_json("GET", f"http://{master.url}/cluster/health")
        assert health["totals"]["corrupt_shards"] >= 1
        assert health["totals"]["scrub_repairs"] >= 1
        assert health["degraded"] is True
    finally:
        vs.stop()
        master.stop()


def test_targeted_rescrub_clears_stale_unrepairable_verdict(tmp_path):
    """The coordinator's post-repair follow-up: a volume scrubbed
    UNREPAIRABLE (> r rotted shards), then healed out of band (the
    cross-server repair restoring clean shards), re-verifies via a
    TARGETED one-pass scan — start(volume_id=vid) — and the stale
    verdict flips to clean immediately instead of waiting for the
    next full pass."""
    import shutil
    import time as _time

    from seaweedfs_tpu.volume_server.scrubber import EcScrubber

    store, base = _store_with_ec_volume(tmp_path)
    try:
        clean_copies = {sid: open(base + to_ext(sid), "rb").read()
                        for sid in range(14)}
        for sid in (0, 1, 2, 3, 4):
            _flip(base + to_ext(sid), 300)
        scrub = EcScrubber(store, rate_mb_s=0)
        st = scrub.run_pass()
        assert st["verdicts"]["1"]["status"] == "unrepairable"
        # out-of-band heal (what the coordinator's cross-server repair
        # does): clean shard files land back on disk, remount
        for sid in (0, 1, 2, 3, 4):
            bad = base + to_ext(sid) + ".bad"
            if os.path.exists(bad):
                os.remove(bad)
            with open(base + to_ext(sid), "wb") as f:
                f.write(clean_copies[sid])
        store.ec_unmount(1)
        store.ec_mount(1)
        # targeted re-scrub: one pass over JUST volume 1.  Wait on the
        # PASS COUNTER, not the running flag — the scan thread sets
        # running=True asynchronously, so polling the flag right after
        # start() can observe the pre-start False and read the stale
        # verdict before the scan ever ran.
        p0 = scrub.status()["passes"]
        assert scrub.start(volume_id=1) is True
        deadline = _time.time() + 10
        while _time.time() < deadline and \
                scrub.status()["passes"] == p0:
            _time.sleep(0.05)
        st = scrub.status()
        assert st["passes"] == p0 + 1
        assert st["verdicts"]["1"]["status"] == "clean"
        # the targeted marker cleared: the next start is a full scan
        assert scrub.only_vid is None
        shutil.rmtree(str(tmp_path), ignore_errors=True)
    finally:
        store.close()


# --- the native block crc -------------------------------------------------

def test_native_block_crcs_match_google_crc32c():
    """block_crcs over a buffer's own address equals google_crc32c over
    copies of the same bytes, block by block: assorted lengths (0, under
    a word, under, at and across a block), unaligned offsets, and open
    blocks carried in from a previous call (a chained crc)."""
    google_crc32c = pytest.importorskip("google_crc32c")
    from seaweedfs_tpu.storage import crc

    if crc.load_block_crc() != "native":
        pytest.skip("native dataplane library unavailable")
    buf = np.random.default_rng(31).integers(0, 256, 1 << 20,
                                             dtype=np.uint8)

    def want(data, run, fill, bs):
        closed, pos = [], 0
        while pos < len(data):
            take = min(bs - fill, len(data) - pos)
            run = google_crc32c.extend(run, data[pos:pos + take])
            fill += take
            pos += take
            if fill == bs:
                closed.append(crc.masked_value(run))
                run, fill = 0, 0
        return closed, run

    lengths = [0, 1, 7, 8, 9, 255, 4095, 4096, 4097, 100_003, 256 * 1024,
               900_001]
    for bs in (4096, 256 * 1024):
        for n in lengths:
            for off in (0, 1, 3, 7, 13):
                for run, fill in ((0, 0), (0x1234ABCD, 5), (0xFFFFFFFF,
                                                            bs - 1)):
                    data = memoryview(buf[off:off + n])
                    got = crc.block_crcs(data, run, fill, bs)
                    assert got == want(data.tobytes(), run, fill, bs), \
                        (bs, n, off, run, fill)
    # chaining: a buffer fed in parts closes the blocks of the whole
    whole = want(buf.tobytes(), 0, 0, 4096)
    closed, run, fill = [], 0, 0
    for a, b in [(0, 5), (5, 70_001), (70_001, 1 << 20)]:
        got, run = crc.block_crcs(memoryview(buf[a:b]), run, fill, 4096)
        closed += got
        fill = (fill + b - a) % 4096
    assert (closed, run) == whole


@pytest.mark.parametrize("block", [256, 4096, 256 * 1024])
def test_sidecar_builder_native_crc_is_bit_identical(monkeypatch, block):
    """SidecarBuilder stores the same table with the native crc (one
    crc32c_blocks call an update, blocks left open across calls) as
    with the google_crc32c path it falls back to: contiguous rows,
    bytes, strided rows (which take the fallback) and u32 words, in any
    chunking."""
    rng = np.random.default_rng(block)
    data = rng.integers(0, 256, (2, 600_001), dtype=np.uint8)
    pairs = rng.integers(0, 256, (600_001, 2), dtype=np.uint8)
    words = rng.integers(0, 1 << 32, 40_000, dtype=np.uint32)
    cuts = [0, 1, 4097, 262_144, 500_000, 600_001]

    def build():
        b = SidecarBuilder(3, block)
        for lo, hi in zip(cuts, cuts[1:]):
            b.update(0, data[0, lo:hi])
            b.update(1, memoryview(data[1, lo:hi].tobytes()))
            b.update(2, pairs[lo:hi, 0])  # stride 2
        w = SidecarBuilder(1, block)
        w.update(0, words)
        return np.concatenate([b.finalize().crcs.ravel(),
                               w.finalize().crcs.ravel()])

    from seaweedfs_tpu.storage import crc

    monkeypatch.setattr(crc, "_blocks", False)
    want = build()
    monkeypatch.setattr(crc, "_blocks", None)
    got = build()
    assert crc.block_crc_path() == "native"  # the library was compared
    np.testing.assert_array_equal(got, want)
