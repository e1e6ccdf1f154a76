"""Host time dispatching the flush's kernel launches (``sim.flush.launch``
self time), per window op, in microseconds, from the program's spans in
the trace."""
from bench import span_reduce


def read(run):
    return span_reduce.us_per_op(run, "sim.flush.launch", "self")
