"""Share of the window in which no program ran on the device, in percent:
1 - (union of the device's program intervals) / window, from the trace."""


def read(run):
    if run.trace is None:
        return None
    return 100.0 * run.trace.idle_share
