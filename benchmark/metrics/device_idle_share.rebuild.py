"""1 - (union of the device's op intervals) / traced window, mean over
the chips, in %, over the window's rebuilds."""

from layers import idle_share

LAYER = "device"
MOVES = "rebuild_s"


def read(run):
    return idle_share(run)
