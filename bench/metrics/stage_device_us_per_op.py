"""Device time of the plane store's arena scatters (the ``jit_scatter``
programs of ``PlaneStore._stage``) in the window, per window op, in
microseconds, from the profiler trace."""


def read(run):
    if run.trace is None or not run.n_ops:
        return None
    seconds = run.trace.module_seconds("jit_scatter")
    return seconds / run.n_ops * 1e6 if seconds else None
