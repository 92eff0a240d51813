"""Mean self time of the store's EC needle read (store.read_ec_needle,
volume_server/store.py) less the codec's ec.reconstruct spans under it,
in ms, over mixed 1 KiB-4 MiB needles: locating the needle, the local
shard reads of large needles, and the survivor reads of a reconstruct.
None where the program records no store.read_ec_needle span."""

from layers import self_ms

LAYER = "store"
MOVES = "read_ops_s"


def read(run):
    return self_ms([s for s in run.spans if s["name"] in
                    ("store.read_ec_needle", "ec.reconstruct")],
                   "store.read_ec_needle")
