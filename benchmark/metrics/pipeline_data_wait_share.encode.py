"""Share of the encodes' time (pipeline.encode_file spans, ec/streaming.py)
that their producer spent blocked in pipeline.data_wait, waiting for the
data-write lanes, in %.  Only the waits on an encode's own thread count:
the fetch threads' and the parity writer's waits run beside it.  A
program without the lanes has no such span, and reads None."""

from layers import named

LAYER = "streaming pipeline"
MOVES = "encode_mbps"


def read(run):
    waits = named(run.spans, "pipeline.data_wait")
    roots = named(run.spans, "pipeline.encode_file")
    total = sum(s["dur"] for s in roots)
    if not waits or not total:
        return None
    producers = {(s["pid"], s["tid"]) for s in roots}
    return 100.0 * sum(s["dur"] for s in waits
                       if (s["pid"], s["tid"]) in producers) / total
