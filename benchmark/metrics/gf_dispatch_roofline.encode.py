"""The whole GF(2^8) dispatch's share of its HBM roofline over the
window's encodes: the same bytes as gf_matmul_roofline.encode, over the
summed device time of the jitted dispatch programs (kernels.PROGRAMS:
the kernel with the lane packing around it), in %."""

from layers import ops_bytes, program_share

LAYER = "kernels"
MOVES = "encode_mbps"


def read(run):
    return program_share(run, ops_bytes(run))
