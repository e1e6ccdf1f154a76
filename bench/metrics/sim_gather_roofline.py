"""The gather kernel's share of its HBM roofline: the bytes the window's
gather launches require (the family's ``kernel_bytes``: each gathered page
once at 4 KiB, its 8 B chunk bitmap in, 64 B per selected chunk out) over
the chip's peak bandwidth, divided by the device time of the kernel's
custom-call ops inside ``jit_sim_gather_kernel`` programs in the trace."""
from bench import roofline

PROGRAM = "jit_sim_gather_kernel"


def read(run):
    launches, required = run.kernel_bytes.get(PROGRAM, (0, 0))
    if run.trace is None or not launches:
        return None
    return roofline.share_percent(
        required, run.trace.kernel_seconds(PROGRAM),
        run.peaks["hbm_bytes_per_s"])
