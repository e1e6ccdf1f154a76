"""The range-plan kernel's share of its HBM roofline: the bytes the
window's plan launches require (``bench/roofline.py``) over the chip's
peak bandwidth, divided by the device time of the kernel's custom-call
ops inside ``jit__stacked_plan`` programs in the trace."""
from bench import roofline


def read(run):
    if run.trace is None or not run.plan_launches:
        return None
    return roofline.share_percent(
        run.plan_bytes, run.trace.kernel_seconds("jit__stacked_plan"),
        run.peaks["hbm_bytes_per_s"])
