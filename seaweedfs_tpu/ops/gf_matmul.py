"""GF(2^8) matrix multiply on TPU via bit-plane decomposition.

The RS hot loop (reference: reedsolomon.Encode at ec_encoder.go:179 and
ReconstructData at store_ec.go:331, AVX2 PSHUFB assembly on CPU) is
``out[R,B] = M[R,K] . data[K,B]`` over GF(2^8).  TPUs have no byte-LUT
instruction, but GF(2^8) multiplication by a constant is linear over GF(2):
byte x maps to M_c . bits(x) for an 8x8 0/1 matrix M_c.  Expanding every
entry of the GF matrix into its bit-matrix turns the whole operation into a
single 0/1 matmul

    out_bits[8R, B] = (A[8R, 8K] @ data_bits[8K, B]) mod 2

which the MXU eats directly: 0/1 values are exact in bfloat16, accumulation
is exact in float32 (sums <= 8K << 2^24), and mod 2 of the popcount equals
the XOR fold.  Column layout of A is bit-plane-major: column j*K + k is input
bit j of data shard k, so data_bits is built by stacking the 8 shifted bit
planes of the byte matrix — no byte-granular shuffles on chip.

Two implementations, byte-identical to each other and to the numpy CPU
engine (differential-tested):
  - `gf_matmul_xla`: pure jnp, XLA fuses unpack+matmul+pack
  - `gf_matmul_pallas`: fused Pallas kernel tiled over B

Each comes in two layouts.  Bytes: data [K, B] u8 -> [R, B] u8, for
reconstructs (TpuEngine: any width, run at one of a fixed set of width
classes).  Words (`*_packed`): data [K, B//4] u32 -> [R, B//4] u32, the
streaming pipeline's dispatches; or
[G, K, W] u32 -> [R, G*W] u32, G whole rows of a `.dat` as the file lays
them out (row g's K blocks of W words back to back), whose parity comes
back in the 2-D layout of the same columns packed row after row; or a
tuple of K [B//4] u32 rows, one a rebuild survivor, stacked to [K, B//4]
inside the jitted dispatch.
Byte q of word j is column 4j + q, as a little-endian host's
``.view(np.uint32)`` / ``.view(np.uint8)`` sees it, and the matmul is
independent per column, so the word path runs the bit-plane matmul once
per byte lane ((w >> 8q) & 0xFF in, its bytes OR-ed back at << 8q): a
quarter of the elements cross the host link each way, and no lane ever
moves on the device (strided lane slices lower to gathers on a TPU).
"""

from __future__ import annotations

import bisect
import functools
from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..ec.gf256 import expand_matrix_to_bits
from ..ec.layout import SMALL_BLOCK_SIZE
from ..observability import get_tracer

# picked by a round-1 tile sweep (8K..128K columns) on a v5e; that sweep
# predates this code and has not been repeated on it
DEFAULT_TILE_B = 65536
# the Pallas interpreter (CPU tests/dryrun) grinds on 64K tiles; use the
# small tile there — correctness paths only, never a perf surface
INTERPRET_TILE_B = 1024


def expand_matrix_bitplanes(gmat: np.ndarray) -> np.ndarray:
    """[R, K] GF matrix -> [8R, 8K] 0/1 matrix in bit-plane-major layout on
    BOTH axes: column j*K + k is input bit j of shard k, row i*R + r is
    output bit i of shard r.  This layout makes on-chip unpack (stack of 8
    shifted planes) and repack (8 contiguous row-slices) free of strided or
    3D operations."""
    r, k = gmat.shape
    abits = expand_matrix_to_bits(gmat)  # [8R, 8K], (r-major,i-minor)x(k-major,j-minor)
    a = abits.reshape(r, 8, k, 8)  # [r, i, k, j]
    return np.ascontiguousarray(a.transpose(1, 0, 3, 2).reshape(8 * r, 8 * k))


def _unpack_bitplanes(data: jnp.ndarray, shift: int = 0) -> jnp.ndarray:
    """[K, B] u8/i32 -> [8K, B] 0/1 i32, rows bit-plane-major to match the A
    layout: row j*K + k is bit shift+j of data[k].  Static concat of 2D
    shifts — no 3D intermediates (Mosaic-safe)."""
    d = data.astype(jnp.int32)
    return jnp.concatenate([(d >> (shift + j)) & 1 for j in range(8)],
                           axis=0)


def _pack_bits(bits: jnp.ndarray, r: int, dtype=jnp.uint8) -> jnp.ndarray:
    """[8R, B] 0/1 i32 (rows bit-major: row i*R + r) -> [R, B] bytes.
    Contiguous static row-slices only — no 3D or strided ops (Mosaic-safe)."""
    out = bits[0:r]
    for i in range(1, 8):
        out = out | (bits[i * r : (i + 1) * r] << i)
    return out.astype(dtype)


def _gf_bytes(a_planes: jnp.ndarray, data: jnp.ndarray) -> jnp.ndarray:
    """[8R, 8K] planes . [K, B] u8 -> [R, B] u8: one MXU matmul, mod 2."""
    # v5e MXU does native int8 x int8 -> int32; unpack must go through i32
    # (Mosaic has no packed u8 shifts), the dot runs in i8
    bits = _unpack_bitplanes(data).astype(jnp.int8)
    acc = jnp.dot(a_planes.astype(jnp.int8), bits,
                  preferred_element_type=jnp.int32)
    return _pack_bits(acc & 1, a_planes.shape[0] // 8)


def _gf_words(a_planes: jnp.ndarray, words: jnp.ndarray) -> jnp.ndarray:
    """[8R, 8K] planes . [K, W] u32 -> [R, W] u32: the byte matmul once per
    byte lane q, each lane's bytes OR-ed into the output word at << 8q."""
    a = a_planes.astype(jnp.int8)
    r = a_planes.shape[0] // 8
    w = jax.lax.bitcast_convert_type(words, jnp.int32)
    out = None
    for q in range(4):
        bits = _unpack_bitplanes(w, 8 * q).astype(jnp.int8)
        acc = jnp.dot(a, bits, preferred_element_type=jnp.int32)
        lane = _pack_bits(acc & 1, r, jnp.int32) << (8 * q)
        out = lane if out is None else out | lane
    return jax.lax.bitcast_convert_type(out, jnp.uint32)


@jax.jit
def gf_matmul_xla(a_planes: jnp.ndarray, data: jnp.ndarray) -> jnp.ndarray:
    """a_planes [8R, 8K] u8 (from expand_matrix_bitplanes), data [K, B] u8
    -> [R, B] u8."""
    return _gf_bytes(a_planes, data)


@jax.jit
def gf_matmul_xla_packed(a_planes: jnp.ndarray,
                         words: jnp.ndarray) -> jnp.ndarray:
    """gf_matmul_xla in the word layout: words [K, B//4] u32 -> [R, B//4]
    u32, or [G, K, W] u32 rows -> [R, G*W] u32, or a tuple of K [B//4]
    u32 rows, stacked here -> [R, B//4] u32."""
    if isinstance(words, tuple):
        words = jnp.stack(words)
    if words.ndim == 3:
        words = words.transpose(1, 0, 2).reshape(words.shape[1], -1)
    return _gf_words(a_planes, words)


def _gf_kernel(a_ref, d_ref, o_ref):
    o_ref[:] = _gf_bytes(a_ref[:], d_ref[:])


def _gf_word_kernel(a_ref, d_ref, o_ref):
    o_ref[:] = _gf_words(a_ref[:], d_ref[:])


@functools.partial(jax.jit, static_argnames=("tile_b", "interpret", "words"))
def gf_matmul_pallas(a_planes: jnp.ndarray, data: jnp.ndarray,
                     tile_b: int = DEFAULT_TILE_B,
                     interpret: bool = False,
                     words: bool = False) -> jnp.ndarray:
    """Fused Pallas kernel: grid over B tiles; A resident in VMEM; unpack,
    MXU matmul, mod-2, repack — no 8x bit expansion ever hits HBM.
    `tile_b` counts columns (bytes) in both layouts; with `words` data is
    [K, B//4] u32 and so is the output, tile_b//4 words a block, or
    [G, K, W] u32 rows (see _gf_rows_call)."""
    r8, k8 = a_planes.shape
    tile = tile_b // 4 if words else tile_b
    if data.ndim == 3:
        assert words
        return _gf_rows_call(a_planes, data, tile, interpret)
    k, n = data.shape
    assert k8 == 8 * k and n % tile == 0
    dtype = jnp.uint32 if words else jnp.uint8
    return pl.pallas_call(
        _gf_word_kernel if words else _gf_kernel,
        out_shape=jax.ShapeDtypeStruct((r8 // 8, n), dtype),
        grid=(n // tile,),
        in_specs=[
            pl.BlockSpec((r8, k8), lambda i: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((k, tile), lambda i: (0, i), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((r8 // 8, tile), lambda i: (0, i),
                               memory_space=pltpu.VMEM),
        interpret=interpret,
    )(a_planes, data)


def _gf_rows_call(a_planes: jnp.ndarray, rows: jnp.ndarray, tile: int,
                  interpret: bool) -> jnp.ndarray:
    """[G, K, W] u32 rows -> [R, G*W] u32: the word kernel's body over a
    (G, W//tile) grid, block (K, tile) of row g in, block g*(W//tile) + i
    of the 2-D output out, so row g's parity lands in columns
    [g*W, (g+1)*W) as if its words had been packed shard-major."""
    r8, k8 = a_planes.shape
    g, k, w = rows.shape
    assert k8 == 8 * k and w % tile == 0
    nt = w // tile
    return pl.pallas_call(
        _gf_word_kernel,
        out_shape=jax.ShapeDtypeStruct((r8 // 8, g * w), jnp.uint32),
        grid=(g, nt),
        in_specs=[
            pl.BlockSpec((r8, k8), lambda gi, i: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((None, k, tile), lambda gi, i: (gi, 0, i),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((r8 // 8, tile),
                               lambda gi, i: (0, gi * nt + i),
                               memory_space=pltpu.VMEM),
        interpret=interpret,
    )(a_planes, rows)


@functools.partial(jax.jit, static_argnames=("tile_b", "interpret"))
def gf_matmul_pallas_packed(a_planes: jnp.ndarray, words: jnp.ndarray,
                            tile_b: int = DEFAULT_TILE_B,
                            interpret: bool = False) -> jnp.ndarray:
    """gf_matmul_pallas in the word layout: words [K, B//4] u32 ->
    [R, B//4] u32, or [G, K, W] u32 rows -> [R, G*W] u32, the whole
    dispatch one kernel; or a tuple of K [B//4] u32 rows (a rebuild's
    survivors, each put from its own map), stacked on the device into
    the [K, B//4] words the kernel reads."""
    if isinstance(words, tuple):
        words = jnp.stack(words)
    return gf_matmul_pallas(a_planes, words, tile_b=tile_b,
                            interpret=interpret, words=True)


def unpack_u32_host(words: np.ndarray, width: int) -> np.ndarray:
    """The word layout's output back to bytes: [R, width//4] u32 -> [R,
    width] u8 (no copy beyond the fetch buffer when already little-endian)."""
    arr = np.ascontiguousarray(words)
    if arr.dtype.byteorder == ">":  # pragma: no cover - TPU hosts are LE
        arr = arr.astype("<u4")
    return arr.view(np.uint8).reshape(arr.shape[0], width)


class WidthClasses:
    """The fixed widths a device engine runs its codec matmul at.

    A call of width b runs at the least class >= b and its valid
    columns are cut on the host, so no device program depends on b: a
    fixed set of compiles per matrix shape, whatever widths the reads
    ask for.  Up to `block` (the 1 MiB small block: every reconstructed
    interval of a volume under 10 GiB) the classes are the multiples of
    `step`, or with `doubling` its doublings (the CPU backend's few
    compiles for the tests); a wider call (a large-block row) runs at a
    multiple of `block`."""

    def __init__(self, step: int, block: int, doubling: bool = False):
        if block % step:
            raise ValueError(f"block {block} is not a multiple of {step}")
        self.block = block
        widths, w = [], step
        while w <= block:
            widths.append(w)
            w = 2 * w if doubling else w + step
        self.widths = tuple(widths)
        # the widths compiled so far, and the matrix shapes they serve
        self.compiled: set[int] = set()
        self._shapes: set[tuple[int, int]] = set()

    def of(self, b: int) -> int:
        if b > self.block:
            return -(-b // self.block) * self.block
        return self.widths[bisect.bisect_left(self.widths, b)]

    def compile(self, rows: int, k: int, at) -> None:
        """Compile every class for an [rows, k] matrix, once: `at(w)`
        compiles the engine's program for class w and returns the width
        it runs at.  One thread a class: a compile, or a load from the
        persistent cache, runs outside the interpreter lock."""
        if (rows, k) in self._shapes:
            return
        with get_tracer().span("ec.compile_classes", rows=rows, k=k,
                               classes=len(self.widths)), \
                ThreadPoolExecutor(len(self.widths)) as ex:
            self.compiled.update(ex.map(at, self.widths))
        self._shapes.add((rows, k))


class TpuEngine:
    """GfMatmulEngine backed by the bit-plane kernels.

    Plugs into seaweedfs_tpu.ec.codec.ReedSolomon; byte-identical to
    CpuEngine.  `mode` is "xla" | "pallas" | "auto" (pallas on a TPU,
    xla on a CPU-pinned process — pallas-on-CPU uses the interpreter,
    which is only for tests).  Without a TPU the process must be pinned
    to the CPU (ops/device.py), or construction raises NoTpuError.

    Every call runs at its width class (WidthClasses over the tile: 16
    on a TPU); compile_classes compiles them for a matrix shape ahead
    of the calls."""

    def __init__(self, mode: str = "auto", tile_b: int = 0):
        from .device import device_backend, ensure_compile_cache

        self.on_tpu = device_backend() == "tpu"
        ensure_compile_cache()
        # matmuls run: proves a degraded read reached the device engine
        self.calls = 0
        self.tile_b = tile_b or (DEFAULT_TILE_B if self.on_tpu
                                 else INTERPRET_TILE_B)
        if mode == "auto":
            mode = "pallas" if self.on_tpu else "xla"
        self.mode = mode
        self.name = f"tpu-{mode}"
        self.classes = WidthClasses(self.tile_b, SMALL_BLOCK_SIZE,
                                    doubling=not self.on_tpu)
        self._plane_cache: dict[bytes, jnp.ndarray] = {}

    def _planes(self, m: np.ndarray) -> jnp.ndarray:
        key = m.tobytes() + bytes([m.shape[0]])
        p = self._plane_cache.get(key)
        if p is None:
            p = jnp.asarray(expand_matrix_bitplanes(m))
            self._plane_cache[key] = p
        return p

    def _kernel(self) -> tuple:
        """The jitted matmul of this mode, and its static arguments."""
        if self.mode == "pallas":
            return gf_matmul_pallas, {"tile_b": self.tile_b,
                                      "interpret": not self.on_tpu}
        return gf_matmul_xla, {}

    def width_class(self, b: int) -> int:
        return self.classes.of(b)

    def compile_classes(self, rows: int, k: int) -> None:
        """Compile the kernel for an [rows, k] matrix at every class up
        to the small block, so that no call of that shape compiles."""
        fn, static = self._kernel()
        a = jax.ShapeDtypeStruct((8 * rows, 8 * k), jnp.uint8)

        def at(w: int) -> int:
            # an ahead-of-time compile fills jit's own cache
            fn.lower(a, jax.ShapeDtypeStruct((k, w), jnp.uint8),
                     **static).compile()
            return w

        self.classes.compile(rows, k, at)

    def matmul(self, m: np.ndarray, shards: np.ndarray) -> np.ndarray:
        a = self._planes(np.asarray(m, dtype=np.uint8))
        b = shards.shape[1]
        w = self.classes.of(b)
        self.calls += 1
        fn, static = self._kernel()
        padded = np.pad(shards, ((0, 0), (0, w - b))) if w > b else shards
        out = np.asarray(jax.device_get(fn(a, jnp.asarray(padded),
                                           **static)))
        # the valid columns, cut on the host as a view: the class's
        # padding is fetched too, and no device op depends on b
        return out[:, :b] if w > b else out
