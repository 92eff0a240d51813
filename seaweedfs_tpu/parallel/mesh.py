"""Multi-chip sharded GF(2^8) encode over a jax.sharding.Mesh.

The distribution story for the EC pipeline (SURVEY.md §2.11): batch many
stripes per launch and shard them across chips.  Three mesh axes, all real:

  - "dp"  — stripe-batch data parallel: independent volumes/rows
  - "sp"  — byte-stream parallel: the B axis within a stripe (the
            sequence-parallel analog for a storage workload)
  - "tp"  — tensor parallel over the CONTRACTION: the 8K bit-plane rows are
            split across chips, each computes a partial popcount, and a
            psum over "tp" folds them before the mod-2.  This works because
            XOR == mod-2 addition: counts add across devices, parity is the
            sum's low bit.

Collectives ride the mesh exactly like a sharded matmul's — psum over tp —
so XLA lays them on ICI.  dp/sp need no communication (parity is pointwise
in the byte-stream).

The shard_map serves ec/codec.py's MeshEngine.  The streaming encode and
rebuild do not use it: on a slice of several devices each device runs
whole dispatches through device_encode_fn, and nothing crosses between
them (ec/streaming.py).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from ..ops.gf_matmul import _pack_bits, _unpack_bitplanes


def parse_device_spec(spec=None, devices: list | None = None) -> list:
    """The `-ec.mesh.devices` flag vocabulary -> a concrete device list:

      ''/None/'all'  every visible device
      'N'            the first N devices (a bare integer is a COUNT)
      'i,j,k'        exactly those jax.devices() indices ('3,' selects
                     index 3 — the trailing comma forces index form)

    ValueError on empty selections, non-integers, out-of-range or
    duplicate indices — the flag should fail loudly at server start,
    not at first encode."""
    devices = devices if devices is not None else jax.devices()
    if spec is None:
        return list(devices)
    if isinstance(spec, int):
        spec = str(spec)
    s = str(spec).strip()
    if s in ("", "all"):
        return list(devices)
    if "," not in s:
        try:
            n = int(s)
        except ValueError:
            raise ValueError(
                f"bad -ec.mesh.devices {spec!r}: expected '', 'all', a "
                f"device count, or comma-separated indices") from None
        if not 1 <= n <= len(devices):
            raise ValueError(
                f"-ec.mesh.devices={n} out of range: have "
                f"{len(devices)} device(s)")
        return list(devices[:n])
    try:
        idxs = [int(t) for t in s.split(",") if t.strip() != ""]
    except ValueError:
        raise ValueError(
            f"bad -ec.mesh.devices {spec!r}: indices must be "
            f"integers") from None
    if not idxs:
        raise ValueError(f"bad -ec.mesh.devices {spec!r}: empty selection")
    if len(set(idxs)) != len(idxs):
        raise ValueError(
            f"bad -ec.mesh.devices {spec!r}: duplicate indices")
    bad = [i for i in idxs if not 0 <= i < len(devices)]
    if bad:
        raise ValueError(
            f"-ec.mesh.devices indices {bad} out of range: have "
            f"{len(devices)} device(s)")
    return [devices[i] for i in idxs]


def device_encode_fn(on_tpu: bool = False):
    """The jitted one-device encode that every streaming dispatch runs,
    in the word layout (ops/gf_matmul.py): (planes [8R, 8K], data
    [K, B//4] u32) -> [R, B//4] u32 parity.  On a TPU the Pallas kernel
    (gf_matmul_pallas_packed, tile DEFAULT_TILE_B), on the CPU its XLA
    twin.  One callable serves every device in the slice: jit
    specializes per input placement, so committed device_put inputs
    pin the compute to their device."""
    from ..ops.gf_matmul import gf_matmul_pallas_packed, gf_matmul_xla_packed

    return gf_matmul_pallas_packed if on_tpu else gf_matmul_xla_packed


def factor_mesh(n_devices: int) -> tuple[int, int, int]:
    """Factor n into (dp, sp, tp), preferring all three axes real."""
    tp = 2 if n_devices % 2 == 0 else 1
    rem = n_devices // tp
    sp = 2 if rem % 2 == 0 else 1
    dp = rem // sp
    return dp, sp, tp


def make_mesh(dp: int = 1, sp: int = 1, tp: int = 1,
              devices: list | None = None) -> Mesh:
    devices = devices if devices is not None else jax.devices()
    n = dp * sp * tp
    if len(devices) < n:
        raise ValueError(f"need {n} devices, have {len(devices)}")
    dev = np.array(devices[:n]).reshape(dp, sp, tp)
    return Mesh(dev, axis_names=("dp", "sp", "tp"))


def _local_gf_matmul(a_loc: jnp.ndarray, data_loc: jnp.ndarray) -> jnp.ndarray:
    """Per-device shard of the bit-plane matmul.

    a_loc   [8R, 8K/tp] — this device's slice of contraction columns
    data_loc[K, S/dp, B/sp] — this device's stripe/byte block (full K)
    returns [R, S/dp, B/sp] parity block (replicated over tp)
    """
    k, s, b = data_loc.shape
    flat = data_loc.reshape(k, s * b)
    bits = _unpack_bitplanes(flat)  # [8K, s*b] bit-plane-major rows
    # slice this device's contraction rows to match a_loc's columns
    tp_idx = jax.lax.axis_index("tp")
    rows = a_loc.shape[1]
    my_bits = jax.lax.dynamic_slice_in_dim(bits, tp_idx * rows, rows, axis=0)
    acc = jnp.dot(a_loc.astype(jnp.int8), my_bits.astype(jnp.int8),
                  preferred_element_type=jnp.int32)
    acc = jax.lax.psum(acc, "tp")  # fold partial popcounts across tp
    out = _pack_bits(acc & 1, a_loc.shape[0] // 8)
    return out.reshape(-1, s, b)


def sharded_encode_fn(mesh: Mesh):
    """Build a jitted sharded encode: (a_planes [8R, 8K], data [K, S, B])
    -> parity [R, S, B], with S sharded over dp, B over sp, and the
    contraction over tp."""

    shmap = jax.shard_map(
        _local_gf_matmul,
        mesh=mesh,
        in_specs=(P(None, "tp"), P(None, "dp", "sp")),
        out_specs=P(None, "dp", "sp"),
    )
    return jax.jit(shmap)


def training_step_fn(mesh: Mesh):
    """The 'full step' the driver dry-runs: sharded encode + sharded
    self-check (re-derive one data shard from parity + the rest, the
    degraded-read path) + a psum'd mismatch metric.  Exercises every mesh
    axis and the tp collective in one jitted program."""

    encode = sharded_encode_fn(mesh)

    def step(a_planes, decode_planes, data):
        parity = encode(a_planes, data)
        # degraded-read check: reconstruct data shard 0 from shards 1..K-1
        # plus parity row 0, using the precomputed decode matrix planes
        recon_in = jnp.concatenate([data[1:], parity[:1]], axis=0)
        recovered = encode(decode_planes, recon_in)
        mismatches = jnp.sum((recovered[0] != data[0]).astype(jnp.int32))
        return parity, mismatches

    return jax.jit(step)


def shard_data(mesh: Mesh, data: np.ndarray) -> jax.Array:
    """Place [K, S, B] host data onto the mesh with the encode sharding."""
    return jax.device_put(data, NamedSharding(mesh, P(None, "dp", "sp")))


# --- ring-collective rebuild -------------------------------------------------
# The ring-parallel pattern (the storage analog of ring attention /
# ring all-reduce): survivor shards are sharded ACROSS devices — each chip
# holds K/ring whole shards — and reconstruction circulates partial GF
# accumulators around the ring with lax.ppermute, adding the local
# contribution each hop.  D-1 neighbor hops over ICI instead of one
# all-to-all psum: bandwidth-optimal when shard blocks are large, and no
# chip ever materializes more than its own survivors plus one accumulator.


def _ring_rebuild_local(planes_loc: jnp.ndarray,
                        shards_loc: jnp.ndarray) -> jnp.ndarray:
    """Per-device shard of the ring rebuild.

    planes_loc [8M, 8K/ring] — reconstruction-matrix columns for the
                               survivors THIS device holds
    shards_loc [K/ring, B]   — this device's survivor shards
    returns    [M, B]        — rebuilt shards (replicated over the ring)
    """
    ring = jax.lax.axis_size("ring")
    bits = _unpack_bitplanes(shards_loc)  # [8*K/ring, B]
    partial = jnp.dot(planes_loc.astype(jnp.int8), bits.astype(jnp.int8),
                      preferred_element_type=jnp.int32)  # [8M, B] counts

    perm = [(j, (j + 1) % ring) for j in range(ring)]

    def hop(_, acc):
        return jax.lax.ppermute(acc, "ring", perm) + partial

    # after ring-1 hops every device's accumulator has folded every
    # device's partial exactly once (its own came in at initialization)
    acc = jax.lax.fori_loop(0, ring - 1, hop, partial)
    return _pack_bits(acc & 1, planes_loc.shape[0] // 8)


def ring_plane_layout(planes: np.ndarray, k: int, ring: int) -> np.ndarray:
    """Permute [8M, 8K] plane columns from the global bit-plane-major
    layout (column j*K + k) into ring-device-major order, so a contiguous
    split over "ring" hands each device exactly the columns matching the
    bit rows its LOCAL K/ring shards unpack into (j-major over local
    shards)."""
    kl = k // ring
    cols = [j * k + d * kl + kk
            for d in range(ring) for j in range(8) for kk in range(kl)]
    return np.ascontiguousarray(planes[:, cols])


def ring_rebuild_fn(mesh: Mesh):
    """Build a jitted ring rebuild over the mesh's LAST axis (renamed
    "ring"): (planes [8M, 8K] pre-permuted with ring_plane_layout,
    survivor shards [K, B]) -> [M, B].

    Shard k lives on ring position k // (K/ring)."""
    ring_axis = mesh.axis_names[-1]
    flat = Mesh(mesh.devices.reshape(-1), axis_names=("ring",)) \
        if ring_axis != "ring" else mesh
    shmap = jax.shard_map(
        _ring_rebuild_local,
        mesh=flat,
        in_specs=(P(None, "ring"), P("ring", None)),
        out_specs=P(None, None),
        # after ring-1 hops every device holds the same fold (addition
        # commutes), but the varying-axis checker cannot prove it — the
        # replication is by construction, not by collective type
        check_vma=False,
    )
    return jax.jit(shmap)
