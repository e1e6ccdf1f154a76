"""The fused lookup kernel's share of its HBM roofline: the bytes the
window's lookup launches require (``bench/roofline.py``) over the chip's
peak bandwidth, divided by the device time of the kernel's custom-call
ops inside ``jit_sim_lookup_kernel`` programs in the trace."""
from bench import roofline


def read(run):
    if run.trace is None or not run.lookup_launches:
        return None
    return roofline.share_percent(
        run.lookup_bytes, run.trace.kernel_seconds("jit_sim_lookup_kernel"),
        run.peaks["hbm_bytes_per_s"])
