"""Share of the rebuilds' time (pipeline.rebuild_files spans, ec/streaming.py)
spent in pipeline.dispatch, the host buffer's transfer to the device, in %."""

from layers import span_share

LAYER = "streaming pipeline"
MOVES = "rebuild_s"


def read(run):
    return span_share(run.spans, "pipeline.dispatch", "pipeline.rebuild_files")
