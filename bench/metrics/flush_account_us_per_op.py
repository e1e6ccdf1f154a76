"""Host time in the flush's accounting phase (``sim.flush.account`` self
time: ``ChipBurst`` records, parity snapshots, ``observe_flush``, counter
updates), per window op, in microseconds, from the program's spans in the
trace."""
from bench import span_reduce


def read(run):
    return span_reduce.us_per_op(run, "sim.flush.account", "self")
