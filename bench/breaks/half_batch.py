"""Fault: half of each batch is left out.  A lookup burst is submitted
without its second half, and a scan's plan keeps only the first half of
its passes."""
import contextlib
import importlib

from bench.control import patched

KIND = "fault"


def applies(cell) -> bool:
    if cell.config["family"] != "ycsb":     # whose traffic keys it reads
        return False
    t = cell.traffic
    return t["read_proportion"] + t["scan_proportion"] > 0


@contextlib.contextmanager
def apply():
    from repro.core.range_query import RangePlan
    replay = importlib.import_module("repro.frontend.replay")
    exact = replay.exact_range

    def halved(lo, hi, **kw):
        plan = exact(lo, hi, **kw)
        keep = plan.include[:max(1, len(plan.include) // 2)]
        return RangePlan(include=keep, exclude=plan.exclude, exact=True)

    def prepare(core, backend):
        inner = core.resolve_burst

        def resolve_burst():
            del core.pending[(len(core.pending) + 1) // 2:]
            inner()
        core.resolve_burst = resolve_burst
    with patched(replay, "exact_range", halved):
        yield prepare
