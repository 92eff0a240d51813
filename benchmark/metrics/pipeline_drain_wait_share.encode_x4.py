"""Share of the encodes' time (pipeline.encode_file spans, ec/streaming.py)
spent in pipeline.drain_wait, waiting for a chip's slot to come back, in
%.  Read only while the per-device queue plane runs: None unless every
pipeline.encode_file span of the window says it ran on 4 devices."""

from layers import named, span_share

LAYER = "streaming pipeline"
MOVES = "encode_mbps"


def read(run):
    roots = named(run.spans, "pipeline.encode_file")
    if not roots or any(str(s.get("args", {}).get("devices")) != "4"
                        for s in roots):
        return None
    return span_share(run.spans, "pipeline.drain_wait", "pipeline.encode_file")
