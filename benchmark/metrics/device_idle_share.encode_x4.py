"""1 - (union of each chip's op intervals) / traced window, mean over
the four chips, in %, over the window's encodes."""

from layers import idle_share

LAYER = "device"
MOVES = "encode_mbps"


def read(run):
    return idle_share(run)
