"""The whole GF(2^8) dispatch's share of its HBM roofline over the
window's encodes on four chips: the same bytes as
gf_matmul_roofline.encode_x4, over the device time of the jitted
dispatch programs (kernels.PROGRAMS) summed over the four chips, in %.
None where no such program ran."""

from layers import ops_bytes, program_share

LAYER = "kernels"
MOVES = "encode_mbps"


def read(run):
    return program_share(run, ops_bytes(run))
