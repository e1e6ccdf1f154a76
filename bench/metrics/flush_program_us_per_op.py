"""Host time in the flush's write phase (``sim.flush.program`` self time:
the grouped simulated NAND programs and the timeline's program-group
report; the arena restage inside it is ``sim.stage``), per window op, in
microseconds, from the program's spans in the trace."""
from bench import span_reduce


def read(run):
    return span_reduce.us_per_op(run, "sim.flush.program", "self")
