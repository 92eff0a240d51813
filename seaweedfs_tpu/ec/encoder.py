"""File-level erasure coding: `.dat` -> `.ec00`..`.ec13` (+ `.ecx`), rebuild,
and decode back.

Semantics ported from weed/storage/erasure_coding/ec_encoder.go +
ec_decoder.go, engine-parameterized: the same striping/padding rules feed
either the CPU numpy codec or the TPU bit-plane matmul engine, and both
produce byte-identical shard files.  Unlike the reference's fixed 256KB
batches (ec_encoder.go:58), the IO chunk here is a free parameter — output
bytes are invariant to it, so the TPU engine uses multi-MB chunks to amortize
device transfer and launch overhead.

Striping (encodeDatFile, ec_encoder.go:194-231):
  while remaining >  data_shards*large: encode one large-block row
  while remaining >  0:                 encode one small-block row
Rows are strict `>` comparisons — a file of exactly N*(10*large) bytes puts
its last 10*large bytes into small-block rows; tails are zero-padded
(encodeDataOneBatch, ec_encoder.go:172-176).
"""

from __future__ import annotations

import os
from typing import Optional

import numpy as np

from ..observability import get_tracer
from ..storage.needle_map import MemDb
from ..storage.types import NEEDLE_ID_SIZE
from ..utils import faultinject
from ..utils.ioutil import pread_padded as _pread_padded
from .codec import ReedSolomon
from .integrity import (
    CorruptSurvivor,
    EciSidecar,
    ShardCorruptError,
    SidecarBuilder,
    note_corruption,
    sidecar_path,
)
from .layout import (
    DATA_SHARDS_COUNT,
    LARGE_BLOCK_SIZE,
    PARITY_SHARDS_COUNT,
    SMALL_BLOCK_SIZE,
    to_ext,
)

DEFAULT_CHUNK = 4 * 1024 * 1024  # IO chunk; output is invariant to this


def write_sorted_file_from_idx(base_file_name: str, ext: str = ".ecx") -> int:
    """`.idx` -> ascending-key `.ecx` (ec_encoder.go:27-54).  Returns
    the number of entries written."""
    db = MemDb.from_idx_file(base_file_name + ".idx")
    db.write_sorted_file(base_file_name + ext)
    return len(db)



def _encode_row(dat_file, rs: ReedSolomon, start_offset: int, block_size: int,
                outputs, chunk: int, builder: Optional[SidecarBuilder] = None
                ) -> None:
    """Encode one row of data_shards blocks of block_size each
    (encodeData/encodeDataOneBatch, ec_encoder.go:120-192)."""
    scratch = np.empty((rs.parity_shards, min(chunk, block_size)),
                       dtype=np.uint8)
    for chunk_off in range(0, block_size, chunk):
        n = min(chunk, block_size - chunk_off)
        data = np.empty((rs.data_shards, n), dtype=np.uint8)
        for i in range(rs.data_shards):
            data[i] = _pread_padded(dat_file, n, start_offset + i * block_size + chunk_off)
        # parity-only in-place encode: one scratch recycled across all
        # chunks instead of an r*n allocation per chunk
        parity = rs.encode_into(data, scratch[:, :n])
        for i in range(rs.data_shards):
            outputs[i].write(data[i].tobytes())
            if builder is not None:
                builder.update(i, data[i])
        for i in range(rs.parity_shards):
            outputs[rs.data_shards + i].write(parity[i].tobytes())
            if builder is not None:
                builder.update(rs.data_shards + i, parity[i])


def write_ec_files(base_file_name: str, rs: Optional[ReedSolomon] = None,
                   large_block_size: int = LARGE_BLOCK_SIZE,
                   small_block_size: int = SMALL_BLOCK_SIZE,
                   chunk: int = DEFAULT_CHUNK, sidecar: bool = True,
                   sidecar_block_size: Optional[int] = None) -> None:
    """WriteEcFiles (ec_encoder.go:57): stripe `.dat` into `.ec00`..`.ecNN`.
    Also writes the `.eci` block-crc sidecar (ec/integrity.py), built
    incrementally as shard bytes stream out — all 14 shards including
    parity get crc coverage at encode time, no second read pass."""
    rs = rs or ReedSolomon(DATA_SHARDS_COUNT, PARITY_SHARDS_COUNT)
    dat_path = base_file_name + ".dat"
    remaining = os.path.getsize(dat_path)
    processed = 0
    builder = SidecarBuilder(rs.total_shards, sidecar_block_size) \
        if sidecar else None
    with get_tracer().span("ec.write_ec_files", path=dat_path,
                           bytes=remaining, k=rs.data_shards,
                           r=rs.parity_shards,
                           backend=rs.engine.name), \
            open(dat_path, "rb") as dat:
        outputs = []
        ok = False
        try:
            # opened INSIDE the cleanup scope: a mid-loop open failure
            # (EMFILE, ENOSPC) must not leak handles or leave the
            # already-created 0-byte shards behind
            for i in range(rs.total_shards):
                outputs.append(open(base_file_name + to_ext(i), "wb"))
            while remaining > large_block_size * rs.data_shards:
                _encode_row(dat, rs, processed, large_block_size, outputs,
                            chunk, builder)
                remaining -= large_block_size * rs.data_shards
                processed += large_block_size * rs.data_shards
            while remaining > 0:
                _encode_row(dat, rs, processed, small_block_size, outputs,
                            chunk, builder)
                remaining -= small_block_size * rs.data_shards
                processed += small_block_size * rs.data_shards
            if builder is not None:
                builder.finalize().save(base_file_name)
            else:
                # sidecar=False over a previously-sidecar'd volume: the
                # old table describes the OLD bytes and would mass-demote
                # every freshly written shard
                try:
                    os.remove(sidecar_path(base_file_name))
                except OSError:
                    pass
            ok = True
        finally:
            for f in outputs:
                f.close()
            if not ok:
                # same discipline as rebuild_ec_files: a truncated .ecNN
                # surviving a failed encode would satisfy existence checks
                # and mask the missing bytes on the next mount/rebuild
                # (and a stale sidecar would mass-demote the next encode's
                # shards, so it goes too)
                for p in [base_file_name + to_ext(i)
                          for i in range(rs.total_shards)] + \
                         [sidecar_path(base_file_name)]:
                    try:
                        os.remove(p)
                    except OSError:
                        pass


def rebuild_ec_files(base_file_name: str, rs: Optional[ReedSolomon] = None,
                     chunk: int = SMALL_BLOCK_SIZE) -> list[int]:
    """RebuildEcFiles (ec_encoder.go:61, :89-118, :233-287): regenerate every
    missing `.ecNN` from the >= data_shards present ones.  Returns generated
    shard ids.

    Survivors are verified block-by-block against the `.eci` sidecar as
    they stream in: a crc-mismatching survivor is DEMOTED to an erasure
    and the rebuild restarts with an alternate survivor set, which also
    regenerates the demoted shard (bit rot becomes a correctable
    erasure); when demotions leave fewer than data_shards clean shards
    the rebuild fails with ShardCorruptError instead of emitting silent
    garbage.  Without a sidecar, survivors are trusted as before."""
    rs = rs or ReedSolomon(DATA_SHARDS_COUNT, PARITY_SHARDS_COUNT)
    sidecar = EciSidecar.load(base_file_name)
    demoted: set[int] = set()
    while True:
        try:
            return _rebuild_ec_attempt(base_file_name, rs, chunk, sidecar,
                                       demoted)
        except CorruptSurvivor as e:
            # corruption is an erasure: retry with the shard excluded —
            # it lands in the missing set and is regenerated clean
            demoted.add(e.shard_id)
            note_corruption("rebuild", e.shard_id, base_file_name,
                            block=e.block)


def _rebuild_ec_attempt(base_file_name: str, rs: ReedSolomon, chunk: int,
                        sidecar: Optional[EciSidecar],
                        demoted: set[int]) -> list[int]:
    has_data = [os.path.exists(base_file_name + to_ext(i))
                and i not in demoted for i in range(rs.total_shards)]
    if sum(has_data) < rs.data_shards:
        if demoted:
            raise ShardCorruptError(
                f"unrepairable: only {sum(has_data)} clean shards after "
                f"demoting corrupt {sorted(demoted)}", tuple(sorted(demoted)))
        raise ValueError(
            f"unrepairable: only {sum(has_data)} of {rs.total_shards} shards present")
    generated = [i for i in range(rs.total_shards) if not has_data[i]]
    if not generated:
        return []
    if sidecar is not None:
        # chunk reads must land on sidecar block boundaries so every
        # block crc can be checked against exactly its covered bytes
        bs = sidecar.block_size
        chunk = max(bs, chunk - chunk % bs)

    inputs = {i: open(base_file_name + to_ext(i), "rb")
              for i in range(rs.total_shards) if has_data[i]}
    # validate survivors BEFORE creating outputs: an empty .ecNN left by a
    # failed rebuild would count as "present" next time and mask the gap
    try:
        shard_size = os.fstat(next(iter(inputs.values())).fileno()).st_size
        for f in inputs.values():
            if os.fstat(f.fileno()).st_size != shard_size:
                raise ValueError("ec shard size mismatch")
    except BaseException:
        for f in inputs.values():
            f.close()
        raise
    if sidecar is not None and sidecar.shard_size != shard_size:
        # stale sidecar (written for a different geometry): its crcs
        # describe other bytes — unverifiable, not evidence of rot
        sidecar = None
    outputs = {i: open(base_file_name + to_ext(i), "wb") for i in generated}
    ok = False
    try:
        with get_tracer().span("ec.rebuild_ec_files", path=base_file_name,
                               missing=len(generated), k=rs.data_shards,
                               r=rs.parity_shards, backend=rs.engine.name,
                               demoted=len(demoted)):
            offset = 0
            while offset < shard_size:
                n = min(chunk, shard_size - offset)
                shards: list[Optional[np.ndarray]] = [None] * rs.total_shards
                for i, f in inputs.items():
                    raw = os.pread(f.fileno(), n, offset)
                    if len(raw) != n:
                        raise IOError(
                            f"short read on shard {i}: {len(raw)} < {n}")
                    if faultinject._points:
                        raw = faultinject.corrupt_block(
                            "ec.shard.corrupt", i, raw, offset)
                    if sidecar is not None:
                        bad = sidecar.verify_range(i, offset, raw)
                        if bad is not None:
                            raise CorruptSurvivor(i, bad)
                    shards[i] = np.frombuffer(raw, dtype=np.uint8)
                rs.reconstruct(shards)
                for i in generated:
                    outputs[i].write(shards[i].tobytes())
                offset += n
        ok = True
    finally:
        for f in inputs.values():
            f.close()
        for f in outputs.values():
            f.close()
        if not ok:
            for i in generated:  # no partial shards under the final names
                try:
                    os.remove(base_file_name + to_ext(i))
                except OSError:
                    pass
    return generated


# --- decode back to a normal volume (ec_decoder.go) -------------------------

def write_dat_file(base_file_name: str, dat_file_size: int,
                   large_block_size: int = LARGE_BLOCK_SIZE,
                   small_block_size: int = SMALL_BLOCK_SIZE,
                   data_shards: int = DATA_SHARDS_COUNT) -> None:
    """WriteDatFile (ec_decoder.go:154-195): concatenate data-shard blocks.
    No GF math — data shards hold the original bytes."""
    inputs = [open(base_file_name + to_ext(i), "rb") for i in range(data_shards)]
    positions = [0] * data_shards
    try:
        with open(base_file_name + ".dat", "wb") as dat:
            remaining = dat_file_size
            # NOTE: `>=` here vs strict `>` in write_ec_files — reference
            # parity (ec_decoder.go:173 vs ec_encoder.go:214).  A .dat of
            # exactly N*data_shards*large bytes is striped as small rows by
            # the encoder but reassembled via the large path here; the
            # reference shares this latent mismatch and real volumes never
            # hit the exact multiple.
            while remaining >= data_shards * large_block_size:
                for i in range(data_shards):
                    buf = os.pread(inputs[i].fileno(), large_block_size,
                                   positions[i])
                    if len(buf) != large_block_size:
                        # same guard as the small-block loop below: a
                        # truncated shard must not silently yield a short
                        # .dat that parses as a smaller volume
                        raise IOError(f"short read on shard {i}")
                    dat.write(buf)
                    positions[i] += large_block_size
                    remaining -= large_block_size
            while remaining > 0:
                for i in range(data_shards):
                    to_read = min(remaining, small_block_size)
                    buf = os.pread(inputs[i].fileno(), to_read, positions[i])
                    if len(buf) != to_read:
                        raise IOError(f"short read on shard {i}")
                    dat.write(buf)
                    positions[i] += to_read
                    remaining -= to_read
                    if remaining <= 0:
                        break
    finally:
        for f in inputs:
            f.close()


def write_idx_file_from_ec_index(base_file_name: str) -> None:
    """WriteIdxFileFromEcIndex (ec_decoder.go:18-43): `.idx` = `.ecx` copied
    verbatim + one tombstone entry per `.ecj` key."""
    from ..storage import idx as idx_mod

    with open(base_file_name + ".ecx", "rb") as ecx, \
         open(base_file_name + ".idx", "wb") as out:
        out.write(ecx.read())
        for key in iterate_ecj_file(base_file_name):
            out.write(idx_mod.pack_entry(key, 0, -1))


def iterate_ecj_file(base_file_name: str):
    path = base_file_name + ".ecj"
    if not os.path.exists(path):
        return
    with open(path, "rb") as f:
        while True:
            buf = f.read(NEEDLE_ID_SIZE)
            if len(buf) != NEEDLE_ID_SIZE:
                return
            yield int.from_bytes(buf, "big")


def find_dat_file_size(data_base_file_name: str, index_base_file_name: str) -> int:
    """FindDatFileSize (ec_decoder.go:48-70): max live-entry end offset."""
    from ..storage import idx as idx_mod
    from ..storage.needle import get_actual_size
    from ..storage.super_block import SuperBlock
    from ..storage.types import size_is_deleted

    with open(data_base_file_name + to_ext(0), "rb") as f:
        version = SuperBlock.from_bytes(f.read(8 + 0xFFFF)).version

    dat_size = 0
    with open(index_base_file_name + ".ecx", "rb") as f:
        entries = idx_mod.parse_entries(f.read())
    for i in range(len(entries)):
        size = int(entries["size"][i])
        if size_is_deleted(size):
            continue
        stop = int(entries["offset"][i]) * 8 + get_actual_size(size, version)
        dat_size = max(dat_size, stop)
    return dat_size
