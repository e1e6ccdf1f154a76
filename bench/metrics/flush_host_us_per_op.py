"""Host time inside ``ShardedSsdBackend.flush`` (placement, operand
gathers, dispatch, grouped programs and restaging), per window op, in
microseconds (host clock)."""


def read(run):
    if not run.n_ops:
        return None
    return run.host_s["flush"] / run.n_ops * 1e6
