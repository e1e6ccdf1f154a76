"""Ops completed in the window over the window's wall time (host clock)."""


def read(run):
    return run.n_ops / run.window_s if run.n_ops else None
