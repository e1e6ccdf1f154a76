"""Host time staging page planes into the device arena
(``PlaneStore._stage``, ``sim.stage`` total time, wherever it is called),
per window op, in microseconds, from the program's spans in the trace."""
from bench import span_reduce


def read(run):
    return span_reduce.us_per_op(run, "sim.stage", "total")
