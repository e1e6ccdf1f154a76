"""Page-plane bytes the plane store shipped host to device in the window
(``BackendStats.staged_bytes``), per window op.  Nothing to read where the
window staged nothing."""


def read(run):
    staged = run.counters["staged_bytes"]
    return staged / run.n_ops if staged and run.n_ops else None
