"""Mean duration of the codec's ec.reconstruct spans (ec/codec.py), in
ms: survivor stack, matrix inversion, the device matmul and its fetch."""

from layers import mean_ms

LAYER = "codec"
MOVES = "read_p99_ms"


def read(run):
    return mean_ms(run.spans, "ec.reconstruct")
