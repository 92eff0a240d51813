"""The share of the columns the device engine runs that is padding:
sum(padded - columns) / sum(padded) over the window's ec.reconstruct
spans, in %.  `columns` is a reconstruct's useful width, `padded` the
width class it ran at (ec/codec.py).  None where the spans carry no
such args (a program before the width classes)."""

from layers import named

LAYER = "codec"
MOVES = "read_p99_ms"


def read(run):
    args = [s.get("args", {}) for s in named(run.spans, "ec.reconstruct")]
    if not args or any("padded" not in a or "columns" not in a
                       for a in args):
        return None
    padded = sum(int(a["padded"]) for a in args)
    if not padded:
        return None
    return 100.0 * sum(int(a["padded"]) - int(a["columns"])
                       for a in args) / padded
