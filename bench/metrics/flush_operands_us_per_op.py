"""Host time in the flush's operand phase (``sim.flush.operands`` self
time: ``rows_for``, the ``take``/``take2d`` gathers, the operand arrays),
per window op, in microseconds, from the program's spans in the trace.
Arena staging inside it (``sim.stage``) is a child, not counted here."""
from bench import span_reduce


def read(run):
    return span_reduce.us_per_op(run, "sim.flush.operands", "self")
