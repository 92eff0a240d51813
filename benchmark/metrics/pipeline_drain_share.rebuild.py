"""Share of the rebuilds' time (pipeline.rebuild_files spans,
ec/streaming.py) spent in pipeline.drain, the fetch of the rebuilt rows
from the device, which waits for the device, in %.  The rebuild path
has no pipeline.drain_wait span."""

from layers import span_share

LAYER = "streaming pipeline"
MOVES = "rebuild_s"


def read(run):
    return span_share(run.spans, "pipeline.drain", "pipeline.rebuild_files")
