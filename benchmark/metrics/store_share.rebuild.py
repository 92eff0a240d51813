"""Share of the traced window the store layer (volume_server/store.py)
spends on its own over the window's rebuilds: the summed self time of
the store.* spans (store.ec_rebuild, store.ec_delete), each less the
part its child spans cover, over the window, in %.  None where the
program records no store spans."""

from layers import named, self_ms

LAYER = "store"
MOVES = "rebuild_s"


def read(run):
    names = {s["name"] for s in run.spans if s["name"].startswith("store.")}
    if not names or run.window_s <= 0:
        return None
    self_s = sum(self_ms(run.spans, n) * len(named(run.spans, n))
                 for n in names) / 1e3
    return 100.0 * self_s / run.window_s
