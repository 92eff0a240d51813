"""Compile the device kernels for a described TPU v5e, no chip attached.

The TPU compiler refuses what interpret mode accepts (unaligned slices,
too much VMEM, programs that do not fit HBM), so these compiles guard
the chip path at no chip time.  The topology is described only inside
the module fixture: only one process may load libtpu, and the worker
that runs this file keeps it until it exits.
"""

import re

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

K, R = 10, 4
TILE = 65536


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    # a described-chip compile can be written to the persistent cache
    # but never read back here: keep the cache off around these tests
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # else libtpu logs to /tmp
        try:
            t = topologies.get_topology_desc(platform="tpu",
                                             topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield t
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, sharding, dtype=jnp.uint8):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _words(shape, sharding):
    return _spec(shape, sharding, jnp.uint32)


@pytest.mark.parametrize("rows", [4, 1])
def test_pallas_packed_at_streaming_dispatch_width(one_chip, rows):
    """The kernel every `-ec.engine=tpu` encode and rebuild dispatch
    runs, at the width StreamingEncoder picks for dispatch_mb=8, in the
    word layout: the whole module is the one Pallas kernel, its ROOT,
    with no gather (the lane packing it replaces lowered to four)."""
    from seaweedfs_tpu.ops.gf_matmul import gf_matmul_pallas_packed

    b = (8 << 20) // TILE * TILE
    text = gf_matmul_pallas_packed.lower(
        _spec((8 * rows, 8 * K), one_chip), _words((K, b // 4), one_chip),
        tile_b=TILE).compile().as_text()
    assert re.search(r"^HloModule jit_gf_matmul_pallas_packed\b", text,
                     re.M)
    calls = [ln for ln in text.splitlines() if "custom-call(" in ln]
    assert len(calls) == 1, calls
    assert re.match(r"\s*ROOT %gf_matmul_pallas(\.\d+)? = ", calls[0]), \
        calls[0]
    assert "tpu_custom_call" in calls[0]
    assert "gather" not in text


def test_pallas_packed_takes_mapped_rows(one_chip):
    """A dispatch of eight 1 MiB rows viewed in the mapped `.dat`,
    [8, K, 262144] u32, put on the chip as the encoder puts it: the
    module is named as the benchmark finds it, its ROOT the one Pallas
    kernel, with no gather; the chip's default layout for the rows is
    K-major, so the relayout to the row-major layout the kernel reads
    is a copy inside the same module (the dispatch's time, not the
    transfer's); its parity is [R, 8 MiB/4] u32 as a slot dispatch's."""
    from seaweedfs_tpu.ops.gf_matmul import gf_matmul_pallas_packed

    rows = _words((8, K, (1 << 20) // 4), one_chip)
    compiled = gf_matmul_pallas_packed.lower(
        _spec((8 * R, 8 * K), one_chip), rows, tile_b=TILE).compile()
    text = compiled.as_text()
    assert re.search(r"^HloModule jit_gf_matmul_pallas_packed\b", text,
                     re.M)
    calls = [ln for ln in text.splitlines() if "custom-call(" in ln]
    assert len(calls) == 1, calls
    assert re.match(r"\s*ROOT %gf_matmul_pallas(\.\d+)? = "
                    r"u32\[4,2097152\]", calls[0]), calls[0]
    assert "gather" not in text


def test_pallas_packed_takes_survivor_rows(one_chip):
    """A rebuild's whole chunk: K [8 MiB/4] u32 rows, each put from its
    survivor shard's map, as a tuple: the module keeps the name the
    benchmark finds it by, stacks the rows with one concatenate into
    the [K, W] words a slot gives, and its ROOT is the one Pallas
    kernel, named as ever, with no gather."""
    from seaweedfs_tpu.ops.gf_matmul import gf_matmul_pallas_packed

    b = (8 << 20) // TILE * TILE
    rows = tuple(_words((b // 4,), one_chip) for _ in range(K))
    text = gf_matmul_pallas_packed.lower(
        _spec((8, 8 * K), one_chip), rows, tile_b=TILE).compile().as_text()
    assert re.search(r"^HloModule jit_gf_matmul_pallas_packed\b", text,
                     re.M)
    cats = [ln for ln in text.splitlines() if " concatenate(" in ln]
    assert len(cats) == 1 and f"u32[{K},{b // 4}]" in cats[0], cats
    calls = [ln for ln in text.splitlines() if "custom-call(" in ln]
    assert len(calls) == 1, calls
    assert re.match(r"\s*ROOT %gf_matmul_pallas(\.\d+)? = "
                    rf"u32\[1,{b // 4}\]", calls[0]), calls[0]
    assert "gather" not in text


# sha256 of the lowered 2-D word dispatch at the streaming width, the
# Mosaic kernel's serialized body masked (it carries source paths and
# lines), as it was before the survivors' tuple came in: slot
# dispatches and the queue plane lower exactly as they did
_LOWERED_2D = {
    4: "f17882fa3b7f5323670cc9dd40d8c5b1d3a4a0df959f22a944005fa1dcb5e44b",
    1: "42fc002d98b553354c21c86a7aab049eb7f29ff328fe83b7bc3cdf0dd32ef76d",
}


@pytest.mark.parametrize("rows", sorted(_LOWERED_2D))
def test_pallas_packed_2d_lowering_unchanged(one_chip, rows):
    import hashlib

    from seaweedfs_tpu.ops.gf_matmul import gf_matmul_pallas_packed

    b = (8 << 20) // TILE * TILE
    text = gf_matmul_pallas_packed.lower(
        _spec((8 * rows, 8 * K), one_chip), _words((K, b // 4), one_chip),
        tile_b=TILE).as_text()
    masked, n = re.subn(r'\\22body\\22: \\22[A-Za-z0-9+/=]*\\22',
                        "BODY", text)
    assert n == 1
    assert hashlib.sha256(masked.encode()).hexdigest() == _LOWERED_2D[rows]


@pytest.mark.parametrize("rows", [4, 1])
def test_pallas_reconstruct_rows(one_chip, rows):
    """TpuEngine's kernel for a 4-row (parity / 4-shard rebuild) and a
    1-row (single-shard degraded read) matrix."""
    from seaweedfs_tpu.ops.gf_matmul import gf_matmul_pallas

    text = gf_matmul_pallas.lower(
        _spec((8 * rows, 8 * K), one_chip), _spec((K, 4 * TILE), one_chip),
        tile_b=TILE).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("width", [TILE, 1 << 20])
def test_pallas_reconstruct_at_width_classes(one_chip, width):
    """TpuEngine's smallest and largest width class on a TPU (one tile
    and the 1 MiB small block), for the reconstruct's 4 x 10 matrix: the
    module and its one custom call are named as benchmark/kernels.py
    finds them, and the output is the whole class (the valid columns
    are cut on the host)."""
    from seaweedfs_tpu.ops.gf_matmul import WidthClasses, gf_matmul_pallas

    classes = WidthClasses(TILE, 1 << 20)
    assert width in (classes.widths[0], classes.widths[-1])
    text = gf_matmul_pallas.lower(
        _spec((8 * R, 8 * K), one_chip), _spec((K, width), one_chip),
        tile_b=TILE).compile().as_text()
    assert re.search(r"^HloModule jit_gf_matmul_pallas\b", text, re.M)
    calls = [ln for ln in text.splitlines() if "custom-call(" in ln]
    assert len(calls) == 1, calls
    assert re.match(rf"\s*ROOT %gf_matmul_pallas(\.\d+)? = u8\[{R},{width}\]",
                    calls[0]), calls[0]
    assert "tpu_custom_call" in calls[0]


def test_device_encode_fn_on_tpu(one_chip):
    """The kernel each chip of a slice runs in the per-device queues
    (`-ec.engine=tpu|mesh` on several chips), as the benchmark finds it
    (benchmark/kernels.py): the module jit_gf_matmul_pallas_packed, its
    one custom call the ROOT gf_matmul_pallas Pallas kernel."""
    from seaweedfs_tpu.parallel.mesh import device_encode_fn

    text = device_encode_fn(on_tpu=True).lower(
        _spec((8 * R, 8 * K), one_chip),
        _words((K, 2 * TILE // 4), one_chip)).compile().as_text()
    assert re.search(r"^HloModule jit_gf_matmul_pallas_packed\b", text,
                     re.M)
    calls = [ln for ln in text.splitlines() if "custom-call(" in ln]
    assert len(calls) == 1, calls
    assert re.match(r"\s*ROOT %gf_matmul_pallas(\.\d+)? = ", calls[0]), \
        calls[0]
    assert "tpu_custom_call" in calls[0]


def test_sharded_encode_fn_on_v5e_2x2(topo):
    """MeshEngine's codec matmul on a four-chip host: dp x sp x tp =
    1 x 2 x 2, the tp contraction folded with an all-reduce."""
    from seaweedfs_tpu.parallel.mesh import (factor_mesh, make_mesh,
                                             sharded_encode_fn)

    dp, sp, tp = factor_mesh(len(topo.devices))
    assert (dp, sp, tp) == (1, 2, 2)
    mesh = make_mesh(dp, sp, tp, devices=list(topo.devices))
    b = dp * sp * TILE
    text = sharded_encode_fn(mesh).lower(
        _spec((8 * R, 8 * K), NamedSharding(mesh, P(None, "tp"))),
        _spec((K, dp, b // dp), NamedSharding(mesh, P(None, "dp", "sp"))),
    ).compile().as_text()
    assert "all-reduce" in text
