"""The GF(2^8) kernel's share of its HBM roofline over the window's
degraded reads of mixed 1 KiB-4 MiB needles: (10 + missing) x useful
columns of each ec.reconstruct span, at the chip's HBM bandwidth, over
the kernel's summed device time, in %.  The padding up to each call's
width class is not useful."""

from layers import kernel_share, reconstruct_bytes

LAYER = "kernels"
MOVES = "read_p99_ms"


def read(run):
    return kernel_share(run, reconstruct_bytes(run))
