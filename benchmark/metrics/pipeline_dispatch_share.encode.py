"""Share of the encodes' time (pipeline.encode_file spans, ec/streaming.py)
spent in pipeline.dispatch, the host buffer's transfer to the device, in %."""

from layers import span_share

LAYER = "streaming pipeline"
MOVES = "encode_mbps"


def read(run):
    return span_share(run.spans, "pipeline.dispatch", "pipeline.encode_file")
