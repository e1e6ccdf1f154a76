"""95th percentile, over the window's lookup flushes, of the time from the
end of ``sim.flush`` k to the start of the lookup ``sim.tail`` launched by
flush k, in milliseconds: how long a read burst's answers wait on the
host after their launch (the depth-1 drain), from the program's spans."""
import numpy as np


def read(run):
    spans = getattr(run, "spans", None)
    if spans is None or not spans.drain_lag_ns:
        return None
    return float(np.percentile(spans.drain_lag_ns, 95)) / 1e6
