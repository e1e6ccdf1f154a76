"""The fused lookup kernel's share of its HBM roofline: the bytes the
window's lookup launches require (``bench/roofline.py``, the family's
``kernel_bytes``) over the chip's peak bandwidth, divided by the device
time of the kernel's custom-call ops inside ``jit_sim_lookup_kernel``
programs in the trace."""
from bench import roofline

PROGRAM = "jit_sim_lookup_kernel"


def read(run):
    launches, required = run.kernel_bytes.get(PROGRAM, (0, 0))
    if run.trace is None or not launches:
        return None
    return roofline.share_percent(
        required, run.trace.kernel_seconds(PROGRAM),
        run.peaks["hbm_bytes_per_s"])
