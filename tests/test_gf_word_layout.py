"""The word layout of the GF(2^8) dispatch: [K, B//4] u32 in, [R, B//4]
u32 out, byte q of word j being column 4j + q (ops/gf_matmul.py).

Every case is checked byte for byte against the numpy CpuEngine and the
byte-layout XLA path.  The "ramp" data gives the four bytes of every word
different values, so a kernel that swapped lanes or byte order fails."""

import numpy as np
import pytest

from seaweedfs_tpu.ec.codec import CpuEngine, ReedSolomon
from seaweedfs_tpu.ops.gf_matmul import (INTERPRET_TILE_B,
                                         expand_matrix_bitplanes,
                                         gf_matmul_pallas_packed,
                                         gf_matmul_xla, gf_matmul_xla_packed,
                                         unpack_u32_host)

K = 10
MATRIX = ReedSolomon(K, 4).matrix


def _data(pattern: str, width: int, seed: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    data = rng.integers(0, 256, (K, width), dtype=np.uint8)
    if pattern == "ramp":
        # even rows: column c holds (c + 37 k) % 251, so the four bytes of
        # every word differ; odd rows stay seeded random bytes
        cols = np.arange(width)
        for k in range(0, K, 2):
            data[k] = (cols + 37 * k) % 251
    return data


def _word_matmul(impl: str, planes, words):
    if impl == "pallas":
        return gf_matmul_pallas_packed(planes, words,
                                       tile_b=INTERPRET_TILE_B,
                                       interpret=True)
    return gf_matmul_xla_packed(planes, words)


@pytest.mark.parametrize("pattern", ["random", "ramp"])
@pytest.mark.parametrize("tiles", [1, 3])
@pytest.mark.parametrize("impl", ["pallas", "xla"])
@pytest.mark.parametrize("rows", [4, 1])
def test_word_path_is_byte_identical(rows, impl, tiles, pattern):
    m = np.ascontiguousarray(MATRIX[K:K + rows])
    width = tiles * INTERPRET_TILE_B
    data = _data(pattern, width, seed=1000 * rows + tiles)
    planes = expand_matrix_bitplanes(m)

    out = np.asarray(_word_matmul(impl, planes, data.view(np.uint32)))
    assert out.dtype == np.uint32 and out.shape == (rows, width // 4)
    got = unpack_u32_host(out, width)
    assert np.array_equal(got, CpuEngine().matmul(m, data))
    assert np.array_equal(got, np.asarray(gf_matmul_xla(planes, data)))


def test_ramp_data_tells_the_lanes_apart():
    """The ramp case would catch a lane swap: permuting the bytes inside
    each word changes every row of the parity."""
    m = np.ascontiguousarray(MATRIX[K:])
    data = _data("ramp", INTERPRET_TILE_B, seed=7)
    want = CpuEngine().matmul(m, data)
    swapped = want.reshape(4, -1, 4)[:, :, ::-1].reshape(4, -1)
    for r in range(4):
        assert not np.array_equal(want[r], swapped[r])


def test_mesh_queue_encode_takes_words():
    """`-ec.engine=mesh`'s per-device encode shares the word layout."""
    from seaweedfs_tpu.parallel.mesh import device_encode_fn

    m = np.ascontiguousarray(MATRIX[K:])
    data = _data("ramp", 4096, seed=11)
    out = device_encode_fn(on_tpu=False)(expand_matrix_bitplanes(m),
                                         data.view(np.uint32))
    assert np.array_equal(unpack_u32_host(np.asarray(out), 4096),
                          CpuEngine().matmul(m, data))
