"""The kernels the benchmark times, and the work each must do.

A kernel is found in the device trace by its op name, a program (the
jitted dispatch around a kernel) by its module name (trace_reduce.py).
The RS work is counted as the bytes any implementation has to move
through HBM: for out[R, B] = M[R, K] . data[K, B] over GF(2^8), it reads
K rows and writes R rows of B useful bytes.  Operation counts belong to
one implementation (the bit-plane kernel does 64 int8 MACs per byte
pair), so only the bytes bound is used, and a share of it cannot pass
100% whatever implements it.  Padding that an engine adds (TpuEngine
pads every call to a 65,536-column tile) is not useful work: it shows
as a lower share.
"""

from __future__ import annotations

import re

KERNELS = {
    # the Pallas GF(2^8) matmul (ops/gf_matmul.py): its pallas_call is
    # given no name=, so on the device plane's "XLA Ops" line the
    # custom call carries the name of the jitted function around it,
    # "gf_matmul_pallas.1" (tf_op ".../pallas_call"), in the encode and
    # rebuild dispatches (inside jit(gf_matmul_pallas_packed)) and in
    # TpuEngine's reconstructs alike
    "gf_matmul": re.compile(r"^gf_matmul_pallas(\.\d+)?$"),
}


PROGRAMS = {
    # the whole jitted GF dispatch on one chip, as the device plane's
    # "XLA Modules" line names each run of it: the encode and rebuild
    # dispatch (ops/gf_matmul.py gf_matmul_pallas_packed: the u32 lane
    # packing around the kernel) and TpuEngine's reconstruct
    # (gf_matmul_pallas)
    "gf_dispatch": re.compile(r"^jit_gf_matmul_pallas(_packed)?\("),
}


def rs_bytes(k: int, r: int, columns: int) -> int:
    """HBM bytes of one GF matmul: K rows read, R rows written."""
    return (k + r) * columns
