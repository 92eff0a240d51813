"""1 - (union of the device's op intervals) / traced window, mean over
the chips, in %, over the window's degraded reads of mixed 1 KiB-4 MiB
needles."""

from layers import idle_share

LAYER = "device"
MOVES = "read_p99_ms"


def read(run):
    return idle_share(run)
