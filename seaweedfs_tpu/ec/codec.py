"""Reed-Solomon codec over GF(2^8) — CPU (numpy) engine + engine protocol.

Semantics-compatible with the reference's klauspost/reedsolomon usage:
``Encode`` fills parity shards (ec_encoder.go:179), ``Reconstruct`` fills any
missing shards from >= data_shards survivors (ec_encoder.go:270,
store_ec.go:331), ``ReconstructData`` only restores data shards
(store_ec.go:367).  The heavy operation in all three is one GF matmul
``out[R,B] = M[R,K] . shards[K,B]``; engines provide that matmul:

  - CpuEngine: numpy 256x256-LUT gather + XOR reduction
  - TpuEngine (seaweedfs_tpu.ops.gf_matmul): bit-plane XLA/Pallas matmul
  - MeshEngine: the same matmul sharded across a jax device mesh
    (parallel/mesh.py) — block dimension split over dp x sp, contraction
    folded over tp

Both produce byte-identical output; tests enforce it.
"""

from __future__ import annotations

from typing import Optional, Protocol, Sequence

import numpy as np

from ..observability import get_tracer
from .gf256 import (MUL_TABLE, build_cauchy_matrix, build_encoding_matrix,
                    mat_invert, mat_mul)
from .layout import SMALL_BLOCK_SIZE


class GfMatmulEngine(Protocol):
    name: str

    def matmul(self, m: np.ndarray, shards: np.ndarray) -> np.ndarray:
        """out[R, B] = m[R, K] . shards[K, B] over GF(2^8); all uint8."""
        ...


class CpuEngine:
    """Vectorized numpy GF matmul: R*K gathers through the 64KB mul table."""

    name = "cpu"

    def matmul(self, m: np.ndarray, shards: np.ndarray) -> np.ndarray:
        out = np.zeros((m.shape[0], shards.shape[1]), dtype=np.uint8)
        return self.matmul_into(m, shards, out)

    def matmul_into(self, m: np.ndarray, shards: np.ndarray,
                    out: np.ndarray) -> np.ndarray:
        """Parity-only in-place variant: out[R, B] is caller-owned (a
        recycled scratch) — no fresh R*B allocation per call."""
        r, k = m.shape
        out[:] = 0
        for j in range(k):
            # MUL_TABLE[m[:, j]] is [R, 256]; fancy-index by the data column
            out ^= MUL_TABLE[m[:, j][:, None], shards[j][None, :]]
        return out


class NativeEngine:
    """C++ AVX2 PSHUFB engine (seaweedfs_tpu/native) — the equivalent of the
    reference's klauspost/reedsolomon assembly path and the default CPU
    engine when the toolchain is available."""

    name = "cpu-simd"

    def __init__(self):
        from .. import native

        if native.load() is None:
            raise RuntimeError("native gf256 library unavailable")
        self._matmul = native.gf_matmul

    def matmul(self, m: np.ndarray, shards: np.ndarray) -> np.ndarray:
        return self._matmul(m, np.ascontiguousarray(shards))

    def matmul_into(self, m: np.ndarray, shards: np.ndarray,
                    out: np.ndarray) -> np.ndarray:
        """Parity-only in-place variant through the row-pointer kernel:
        the product lands straight in the caller's recycled scratch
        (each out row must be contiguous; rows may be strided apart)."""
        from .. import native

        m = np.ascontiguousarray(m, dtype=np.uint8)
        shards = np.ascontiguousarray(shards)
        r, k = m.shape
        n = shards.shape[1]
        if out.shape != (r, n) or out.dtype != np.uint8 \
                or out.strides[1] != 1:
            # the C kernel writes n bytes at every out-row pointer; a
            # mis-shaped target would be an out-of-bounds write
            raise ValueError("out must be uint8 [R, B] with contiguous rows")
        row = shards.strides[0]
        native.gf_matmul_ptrs(
            m, [shards.ctypes.data + i * row for i in range(k)],
            [out[i].ctypes.data for i in range(r)], n)
        return out

    def matmul_rows(self, m: np.ndarray,
                    rows: list[np.ndarray]) -> np.ndarray:
        """Same product, but over separately-allocated input rows via the
        row-pointer kernel — no [k, B] stack copy of the inputs."""
        from .. import native

        m = np.ascontiguousarray(m, dtype=np.uint8)
        rows = [np.ascontiguousarray(r, dtype=np.uint8) for r in rows]
        n = len(rows[0])
        if any(len(r) != n for r in rows):
            # the C kernel reads n bytes from EVERY row pointer; a short
            # row would be an out-of-bounds read, not a clean error
            raise ValueError("inconsistent shard sizes")
        out = np.empty((m.shape[0], n), dtype=np.uint8)
        native.gf_matmul_ptrs(
            m, [r.ctypes.data for r in rows],
            [out[i].ctypes.data for i in range(m.shape[0])], n)
        return out


class MeshEngine:
    """Multi-device GfMatmulEngine: ONE logical matmul with the block
    dimension sharded across a jax device mesh (parallel/mesh.py's
    dp x sp x tp shard_map) — every chip computes its slice of the byte
    stream, the tp axis folds partial popcounts with a psum.

    This is the codec-level face of `-ec.engine=mesh`: ReedSolomon
    encode/verify/reconstruct route through it unchanged, and output is
    byte-identical to CpuEngine (differential-test contract).  Encodes
    and rebuilds of whole volumes do not come here: under `tpu` and
    `mesh` alike the streaming pipeline (ec/streaming.py) runs whole
    dispatches on each device of the slice through
    parallel.mesh.device_encode_fn."""

    name = "mesh"

    def __init__(self, devices=None, mesh=None):
        import jax

        from ..ops.device import device_backend, ensure_compile_cache
        from ..ops.gf_matmul import (DEFAULT_TILE_B, INTERPRET_TILE_B,
                                     WidthClasses, expand_matrix_bitplanes)
        from ..parallel.mesh import (factor_mesh, make_mesh,
                                     parse_device_spec, sharded_encode_fn)
        on_tpu = device_backend() == "tpu"
        ensure_compile_cache()
        self._jax = jax
        if mesh is None:
            devs = (list(devices) if isinstance(devices, (list, tuple))
                    else parse_device_spec(devices))
            dp, sp, tp = factor_mesh(len(devs))
            mesh = make_mesh(dp, sp, tp, devices=devs)
        self.mesh = mesh
        self.dims = tuple(int(mesh.devices.shape[i]) for i in range(3))
        self.devices = list(mesh.devices.reshape(-1))
        self._encode = sharded_encode_fn(mesh)
        self._expand = expand_matrix_bitplanes
        self._plane_cache: dict[bytes, object] = {}
        # TpuEngine's width classes, each a multiple of the dp x sp grid
        self.classes = WidthClasses(
            DEFAULT_TILE_B if on_tpu else INTERPRET_TILE_B,
            SMALL_BLOCK_SIZE, doubling=not on_tpu)

    def width_class(self, b: int) -> int:
        q = self.dims[0] * self.dims[1]
        return -(-self.classes.of(b) // q) * q

    def compile_classes(self, rows: int, k: int) -> None:
        """As TpuEngine.compile_classes, for the sharded matmul: the
        planes and the [K, dp, B/dp] data placed as matmul places them."""
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P
        spec = self._jax.ShapeDtypeStruct
        dp = self.dims[0]
        planes = spec((8 * rows, 8 * k), np.uint8,
                      sharding=NamedSharding(self.mesh, P(None, "tp")))
        grid = NamedSharding(self.mesh, P(None, "dp", "sp"))

        def at(w: int) -> int:
            w = self.width_class(w)
            self._encode.lower(planes, spec((k, dp, w // dp), np.uint8,
                                            sharding=grid)).compile()
            return w

        self.classes.compile(rows, k, at)

    def _planes(self, m: np.ndarray):
        """Bit-plane matrix, device_put replicated-over-(dp,sp) and
        sharded over tp's contraction columns; cached per matrix so
        repeated encodes skip the H2D."""
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P
        key = m.tobytes() + bytes([m.shape[0]])
        planes = self._plane_cache.get(key)
        if planes is None:
            planes = self._jax.device_put(
                self._expand(m), NamedSharding(self.mesh, P(None, "tp")))
            if len(self._plane_cache) >= 8:
                self._plane_cache.pop(next(iter(self._plane_cache)))
            self._plane_cache[key] = planes
        return planes

    def matmul(self, m: np.ndarray, shards: np.ndarray) -> np.ndarray:
        dp, _, tp = self.dims
        m = np.ascontiguousarray(m, dtype=np.uint8)
        if (8 * m.shape[1]) % tp != 0:  # contraction must split over tp
            raise ValueError(f"8*K={8 * m.shape[1]} not divisible by "
                             f"tp={tp}")
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P
        k, width = shards.shape
        pad = self.width_class(width) - width
        if pad:
            data = np.zeros((k, width + pad), dtype=np.uint8)
            data[:, :width] = shards
        else:
            data = np.ascontiguousarray(shards, dtype=np.uint8)
        # [K, B'] -> [K, dp, B'/dp]: dp contiguous row-chunks of the byte
        # stream; sp splits within each chunk.  The inverse reshape on
        # the way out restores the exact byte order.
        grid = data.reshape(k, dp, data.shape[1] // dp)
        dev = self._jax.device_put(
            grid, NamedSharding(self.mesh, P(None, "dp", "sp")))
        out = self._encode(self._planes(m), dev)  # [R, dp, B'/dp] u8
        host = np.asarray(out).reshape(out.shape[0], -1)
        return host[:, :width] if pad else host


def best_cpu_engine() -> GfMatmulEngine:
    """Native SIMD if buildable, else numpy — mirroring the reference's
    'assembly when available' behavior."""
    try:
        return NativeEngine()
    except Exception:
        return CpuEngine()


_FALLBACK_ENGINE: Optional[GfMatmulEngine] = None


def _fallback_matmul(m: np.ndarray, shards: np.ndarray,
                     failed: GfMatmulEngine, err: BaseException) -> np.ndarray:
    """Per-call engine fallback: when a non-CPU engine (device kernel,
    native plane) raises mid-matmul, recompute on the numpy/SIMD CPU
    path instead of failing the whole encode — output is byte-identical
    by the differential-test contract.  Counted and traced so degraded
    results never masquerade as clean ones."""
    global _FALLBACK_ENGINE
    if _FALLBACK_ENGINE is None:
        _FALLBACK_ENGINE = best_cpu_engine()
    if type(_FALLBACK_ENGINE) is type(failed):
        # the CPU engine itself failed: nothing softer to fall to
        raise err
    from ..stats import ec_pipeline_metrics

    ec_pipeline_metrics().engine_fallbacks.inc("codec")
    get_tracer().event("pipeline.fallback", reason="codec",
                       engine=getattr(failed, "name", "?"),
                       error=type(err).__name__)
    from ..observability import events as _events

    _events.emit("engine_fallback", reason="codec",
                 engine=getattr(failed, "name", "?"),
                 error=type(err).__name__)
    return _FALLBACK_ENGINE.matmul(m, shards)


class ReedSolomon:
    """One (data, parity) geometry with its cached encoding matrix."""

    def __init__(self, data_shards: int, parity_shards: int,
                 matrix_kind: str = "vandermonde",
                 engine: Optional[GfMatmulEngine] = None):
        if data_shards <= 0 or parity_shards <= 0:
            raise ValueError("shard counts must be positive")
        if data_shards + parity_shards > 256:
            raise ValueError("too many shards for GF(2^8)")
        self.data_shards = data_shards
        self.parity_shards = parity_shards
        self.total_shards = data_shards + parity_shards
        self.matrix_kind = matrix_kind
        if matrix_kind == "cauchy":
            self.matrix = build_cauchy_matrix(data_shards, self.total_shards)
        else:
            self.matrix = build_encoding_matrix(data_shards, self.total_shards)
        self.parity_matrix = self.matrix[data_shards:]
        self.engine: GfMatmulEngine = engine or CpuEngine()

    # --- core ---------------------------------------------------------
    def encode(self, data: np.ndarray) -> np.ndarray:
        """data[data_shards, B] -> parity[parity_shards, B]."""
        if data.shape[0] != self.data_shards:
            raise ValueError(f"expected {self.data_shards} data shards")
        # every engine materializes the result to host before returning
        # (TpuEngine device_gets), so the span bounds real device time —
        # the block_until_ready discipline without an explicit call
        with get_tracer().span("ec.encode", k=self.data_shards,
                               r=self.parity_shards, bytes=int(data.nbytes),
                               backend=self.engine.name):
            data = np.ascontiguousarray(data)
            try:
                return self.engine.matmul(self.parity_matrix, data)
            except ValueError:
                raise  # shape/size validation, not an engine fault
            except Exception as e:
                # engine choice is a per-call decision: a failing device
                # or native engine degrades to the CPU codec instead of
                # failing the encode (byte-identical output)
                return _fallback_matmul(self.parity_matrix, data,
                                        self.engine, e)

    def encode_into(self, data: np.ndarray, out: np.ndarray) -> np.ndarray:
        """Parity-only output variant of encode(): the product lands in
        the caller-provided out[parity_shards, B] scratch — the chunked
        encoders recycle ONE buffer across all chunks instead of
        allocating r*B per call, and nothing but parity is ever
        materialized.  Engines without an in-place kernel fall back to
        matmul + copy; byte-identical either way (same fallback
        discipline as encode())."""
        if data.shape[0] != self.data_shards:
            raise ValueError(f"expected {self.data_shards} data shards")
        if out.shape != (self.parity_shards, data.shape[1]) \
                or out.dtype != np.uint8 or out.strides[1] != 1:
            raise ValueError("out must be uint8 [parity_shards, B] "
                             "with contiguous rows")
        with get_tracer().span("ec.encode", k=self.data_shards,
                               r=self.parity_shards, bytes=int(data.nbytes),
                               backend=self.engine.name):
            data = np.ascontiguousarray(data)
            try:
                if hasattr(self.engine, "matmul_into"):
                    return self.engine.matmul_into(self.parity_matrix,
                                                   data, out)
                out[:] = self.engine.matmul(self.parity_matrix, data)
                return out
            except ValueError:
                raise  # shape/size validation, not an engine fault
            except Exception as e:
                out[:] = _fallback_matmul(self.parity_matrix, data,
                                          self.engine, e)
                return out

    def encode_shards(self, shards: list[np.ndarray]) -> None:
        """klauspost Encode: shards[0:data] in, shards[data:total] overwritten."""
        data = np.stack(shards[: self.data_shards])
        parity = self.encode(data)
        for i in range(self.parity_shards):
            shards[self.data_shards + i][:] = parity[i]

    def verify(self, shards: Sequence[np.ndarray]) -> bool:
        data = np.stack(shards[: self.data_shards])
        parity = self.encode(data)
        return all(
            np.array_equal(parity[i], shards[self.data_shards + i])
            for i in range(self.parity_shards)
        )

    def reconstruct(self, shards: list[Optional[np.ndarray]],
                    data_only: bool = False) -> None:
        """Fill None entries in-place from >= data_shards survivors.

        Mirrors klauspost Reconstruct/ReconstructData semantics, fused
        into ONE kernel pass: every shard obeys shard_i = matrix[i] @
        data (identity top makes the matrix systematic), and data =
        inv(matrix[sub]) @ survivors, so ALL missing shards — data and
        parity alike — are (matrix[missing] @ inv(matrix[sub])) @
        survivors.  One survivor stack, one matmul: the old two-pass
        shape (decode data, re-stack, recompute parity) cost a second
        160MB stack + matmul and ran ~6x below the encode kernel.
        """
        if len(shards) != self.total_shards:
            raise ValueError(f"expected {self.total_shards} shards")
        present = [i for i, s in enumerate(shards) if s is not None]
        if len(present) == self.total_shards:
            return
        if len(present) < self.data_shards:
            raise ValueError("too few shards to reconstruct")
        size = next(len(shards[i]) for i in present)

        sub_rows = present[: self.data_shards]
        upto = self.data_shards if data_only else self.total_shards
        missing = [i for i in range(upto) if shards[i] is None]
        if missing:
            # `columns` is the useful width, `padded` the width the
            # engine runs it at (a device engine's width class)
            pad_to = getattr(self.engine, "width_class", None)
            with get_tracer().span(
                    "ec.reconstruct", k=self.data_shards,
                    r=self.parity_shards, missing=len(missing),
                    bytes=size * self.data_shards, columns=size,
                    padded=pad_to(size) if pad_to else size,
                    backend=self.engine.name):
                sub = [list(int(v) for v in self.matrix[i])
                       for i in sub_rows]
                decode = mat_invert(sub)
                want = [list(int(v) for v in self.matrix[m])
                        for m in missing]
                rows = np.array(mat_mul(want, decode), dtype=np.uint8)
                try:
                    if hasattr(self.engine, "matmul_rows"):
                        # row-pointer kernel: skips the [k, B] survivor
                        # stack copy
                        restored = self.engine.matmul_rows(
                            rows, [shards[i] for i in sub_rows])
                    else:
                        survivors = np.stack([shards[i] for i in sub_rows])
                        restored = self.engine.matmul(rows, survivors)
                except ValueError:
                    raise  # shape/size validation, not an engine fault
                except Exception as e:
                    restored = _fallback_matmul(
                        rows, np.stack([shards[i] for i in sub_rows]),
                        self.engine, e)
                for out_i, shard_i in enumerate(missing):
                    shards[shard_i] = restored[out_i]
        # keep sizes consistent
        for i in range(self.total_shards):
            if shards[i] is not None and len(shards[i]) != size:
                raise ValueError("inconsistent shard sizes")

    def reconstruct_data(self, shards: list[Optional[np.ndarray]]) -> None:
        self.reconstruct(shards, data_only=True)
