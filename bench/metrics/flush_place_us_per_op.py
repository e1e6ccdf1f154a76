"""Host time in the flush's placement phase (``sim.flush.place`` self
time: queue drain, failover, reliability opens, per-command placement),
per window op, in microseconds, from the program's spans in the trace."""
from bench import span_reduce


def read(run):
    return span_reduce.us_per_op(run, "sim.flush.place", "self")
