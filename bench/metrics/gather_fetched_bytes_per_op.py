"""Bytes the gather tails copied device to host in the window, padding
included (``BackendStats.gather_fetched_bytes``), per window op.  Nothing
to read where the program has no such counter or the window gathered
nothing."""


def read(run):
    fetched = run.counters.get("gather_fetched_bytes")
    return fetched / run.n_ops if fetched and run.n_ops else None
