"""Host time inside the calls into ``ReplayCore`` (its drains and the
ticket tails they run included) less the time inside the backend's
``flush``, per window op, in microseconds (host clock)."""


def read(run):
    if not run.n_ops:
        return None
    return (run.host_s["replay"] - run.host_s["flush"]) / run.n_ops * 1e6
