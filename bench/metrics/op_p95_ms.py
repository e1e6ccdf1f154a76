"""95th percentile of every window op's latency, hand-off to answer, in
milliseconds (host clock)."""
import numpy as np


def read(run):
    if not run.n_ops:
        return None
    return float(np.percentile(run.latency_s, 95)) * 1e3
