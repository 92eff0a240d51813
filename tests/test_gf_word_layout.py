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


@pytest.mark.parametrize("tiles", [1, 2])
@pytest.mark.parametrize("groups", [1, 3])
@pytest.mark.parametrize("impl", ["pallas", "xla"])
def test_row_groups_equal_per_row_results(impl, groups, tiles):
    """[G, K, W] u32 rows, as a mapped `.dat` lays them out, give the
    per-row 2-D results side by side: [R, G*W], row g in columns
    [g*W, (g+1)*W)."""
    m = np.ascontiguousarray(MATRIX[K:])
    planes = expand_matrix_bitplanes(m)
    width = tiles * INTERPRET_TILE_B
    rows = np.stack([_data("ramp" if g % 2 else "random", width,
                           seed=50 + g) for g in range(groups)])
    out = np.asarray(_word_matmul(impl, planes, rows.view(np.uint32)))
    assert out.dtype == np.uint32 and out.shape == (4, groups * width // 4)
    per_row = [np.asarray(_word_matmul(impl, planes, rows[g].view(np.uint32)))
               for g in range(groups)]
    assert np.array_equal(out, np.concatenate(per_row, axis=1))
    got = unpack_u32_host(out, groups * width)
    want = CpuEngine().matmul(m, np.concatenate(list(rows), axis=1))
    assert np.array_equal(got, want)


@pytest.mark.parametrize("tiles", [1, 3])
@pytest.mark.parametrize("impl", ["pallas", "xla"])
def test_row_tuple_equals_stacked_words(impl, tiles):
    """A rebuild's K survivor rows, each [W] u32 and put on its own, as
    a tuple: the same words as the stacked [K, W] array gives, and the
    CPU codec's bytes."""
    m = np.ascontiguousarray(MATRIX[K:K + 1])
    planes = expand_matrix_bitplanes(m)
    width = tiles * INTERPRET_TILE_B
    words = _data("ramp", width, seed=70 + tiles).view(np.uint32)
    got = np.asarray(_word_matmul(impl, planes, tuple(words)))
    assert got.dtype == np.uint32 and got.shape == (1, width // 4)
    assert np.array_equal(got, np.asarray(_word_matmul(impl, planes, words)))
    assert np.array_equal(unpack_u32_host(got, width),
                          CpuEngine().matmul(m, words.view(np.uint8)))


# sha256 of the 2-D word programs' jaxprs before the row axis came in:
# rebuilds, the slot dispatches and the queue plane run exactly these
_PROGRAMS_2D = {
    "pallas": "d712aa620b7b7567857a469ecf7dd4a13318f7252452c17fa57cd7dbdafed267",
    "pallas-interpret":
        "378c3d6d948031e68e9d6ae59edbc2c41911114a72ecebe7f17dd62907538fb7",
    "xla": "54192b0537368cc6c61e474b72bd67f85e3fd8b95f4d16e3d5e2f6b0cf0ad3d2",
}


@pytest.mark.parametrize("impl", sorted(_PROGRAMS_2D))
def test_2d_word_program_unchanged(impl):
    import functools
    import hashlib

    import jax
    import jax.numpy as jnp

    if impl == "xla":
        fn, width = gf_matmul_xla_packed, 4096
    else:
        tile, interpret = ((65536, False) if impl == "pallas"
                           else (INTERPRET_TILE_B, True))
        fn = functools.partial(gf_matmul_pallas_packed, tile_b=tile,
                               interpret=interpret)
        width = (8 << 20) // 4
    jaxpr = str(jax.make_jaxpr(fn)(
        jax.ShapeDtypeStruct((32, 80), jnp.uint8),
        jax.ShapeDtypeStruct((K, width), jnp.uint32)))
    assert hashlib.sha256(jaxpr.encode()).hexdigest() == _PROGRAMS_2D[impl]
