"""The GF(2^8) kernel's share of its HBM roofline over the window's
rebuilds: (10 + parity or lost shards) x shard bytes per op, at the
chip's HBM bandwidth, over the kernel's summed device time, in %."""

from layers import kernel_share, ops_bytes

LAYER = "kernels"
MOVES = "rebuild_s"


def read(run):
    return kernel_share(run, ops_bytes(run))
