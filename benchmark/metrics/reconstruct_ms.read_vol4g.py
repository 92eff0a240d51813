"""Mean duration of the codec's ec.reconstruct spans (ec/codec.py) over
the window's degraded reads of mixed 1 KiB-4 MiB needles, in ms:
survivor stack, matrix inversion, the device matmul at the call's width
class and its fetch."""

from layers import mean_ms

LAYER = "codec"
MOVES = "read_p99_ms"


def read(run):
    return mean_ms(run.spans, "ec.reconstruct")
