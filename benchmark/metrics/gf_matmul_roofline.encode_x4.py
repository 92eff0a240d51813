"""The GF(2^8) kernel's share of its HBM roofline over the window's
encodes on four chips: (10 + 4 parity shards) x shard bytes per op, at
one chip's HBM bandwidth, over the kernel's device time summed over the
four chips, in %.  Each chip computes a quarter of the dispatches, so
100% means every chip at its roof.  None where no op of the Pallas
kernel ran (kernels.KERNELS)."""

from layers import kernel_share, ops_bytes

LAYER = "kernels"
MOVES = "encode_mbps"


def read(run):
    return kernel_share(run, ops_bytes(run))
