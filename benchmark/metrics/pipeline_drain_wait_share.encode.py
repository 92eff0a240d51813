"""Share of the encodes' time (pipeline.encode_file spans, ec/streaming.py)
spent in pipeline.drain_wait, waiting for the device's parity, in %."""

from layers import span_share

LAYER = "streaming pipeline"
MOVES = "encode_mbps"


def read(run):
    return span_share(run.spans, "pipeline.drain_wait", "pipeline.encode_file")
