"""Host time in the backend's result tails (``LazyResultBatch.run``,
``sim.tail`` total time: the device-to-host copy and the response
decode), per window op, in microseconds, from the program's spans in the
trace.  ``replay_self_us_per_op`` counts this time as the frontend's."""
from bench import span_reduce


def read(run):
    return span_reduce.us_per_op(run, "sim.tail", "total")
