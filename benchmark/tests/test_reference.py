"""The plain reference at small sizes on the CPU: a round trip, and
agreement with the program's CpuEngine, encoder layout and .ecx."""


import numpy as np
import pytest

from reference import rs


def test_round_trip():
    rng = np.random.default_rng(1)
    data = rng.integers(0, 256, (10, 4097), dtype=np.uint8)
    parity = rs.encode(data)
    shards = {i: data[i] for i in range(10)}
    shards.update({10 + i: parity[i] for i in range(4)})
    for lost in ([3], [0, 7, 10, 13], [9, 10, 11, 12]):
        have = {k: v for k, v in shards.items() if k not in lost}
        got = rs.reconstruct(have, lost)
        assert all(np.array_equal(got[i], shards[i]) for i in lost)


def test_matrix_is_systematic():
    for r in range(10):
        assert rs.MATRIX[r] == [int(r == c) for c in range(10)]


def test_agrees_with_program_cpu_engine():
    from seaweedfs_tpu.ec.codec import CpuEngine, ReedSolomon

    prog = ReedSolomon(10, 4, engine=CpuEngine())
    assert np.array_equal(np.array(rs.MATRIX, np.uint8), prog.matrix)
    data = np.random.default_rng(2).integers(0, 256, (10, 70001),
                                             dtype=np.uint8)
    assert np.array_equal(rs.encode(data), prog.encode(data))


@pytest.mark.parametrize("size", [5 * 4096 + 17, 3 * 10 * 65536 + 12345])
def test_layout_and_ecx_agree_with_program_encoder(tmp_path, size):
    """Small blocks (64 KiB large, 4 KiB small) so that both kinds of
    row appear, as the program's encoder and the reference lay them."""
    from seaweedfs_tpu.ec import encoder
    from seaweedfs_tpu.ec.codec import CpuEngine, ReedSolomon

    large, small = 65536, 4096
    dat = np.random.default_rng(size).integers(0, 256, size, dtype=np.uint8)
    base = str(tmp_path / "1")
    dat.tofile(base + ".dat")
    encoder.write_ec_files(base, ReedSolomon(10, 4, engine=CpuEngine()),
                           large_block_size=large, small_block_size=small,
                           sidecar=False)
    n = rs.shard_size(size, large, small)
    ref = rs.shard_columns(dat, 0, n, large, small)
    for i in range(14):
        got = np.fromfile(base + ".ec%02d" % i, dtype=np.uint8)
        assert np.array_equal(got, ref[i]), i
    # .ecx from an .idx with an overwrite and a delete
    e = np.array([(5, 10, 100), (2, 20, 50), (5, 30, 60), (9, 40, 10),
                  (9, 0, -1)],
                 dtype=[("key", ">u8"), ("off", ">u4"), ("size", ">i4")])
    e.tofile(base + ".idx")
    encoder.write_sorted_file_from_idx(base)
    with open(base + ".ecx", "rb") as f:
        assert f.read() == rs.sorted_index(e.tobytes())


def test_shard_of_matches_layout():
    large, small = 65536, 4096
    size = 12 * 10 * large + 7 * 10 * small + 1234
    for off in [0, large - 1, large, 10 * large * 12 + 5, size - 1]:
        s = int(rs.shard_of(size, off, large, small))
        row = [r for r in rs.rows(size, large, small)
               if r[0] <= off < r[0] + 10 * r[1]][0]
        assert s == (off - row[0]) // row[1]
