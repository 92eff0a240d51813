"""Mean wait of a needle GET in the reactor's queue, from the loop's
handoff to a worker's pickup (http.queue_wait spans under the
http.<server>.read_object spans, utils/httpd.py), in ms.  None where
the program records no http.queue_wait span."""

from layers import named

LAYER = "HTTP fronts"
MOVES = "read_p99_ms"


def read(run):
    reads = {s["args"]["span_id"] for s in run.spans
             if s["name"].endswith(".read_object")}
    d = [s["dur"] for s in named(run.spans, "http.queue_wait")
         if s.get("args", {}).get("parent_id") in reads]
    return sum(d) / len(d) / 1e3 if d else None
