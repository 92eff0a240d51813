"""Mean self time of the needle-GET HTTP span (http.<server>.read_object,
utils/httpd.py), less the time its child spans cover, in ms."""

from layers import self_ms

LAYER = "HTTP fronts"
MOVES = "read_ops_s"


def read(run):
    return self_ms(run.spans, ".read_object")
