"""Control: answers that are approximate where the configuration promises
exact ones.  Point lookups match with the key's lowest bit masked off, and
scans run the paper's one-pass approximate range plan (the program's own
``approximate_range``) in place of the exact one."""
import contextlib
import importlib

from bench.control import patched

KIND = "control"


@contextlib.contextmanager
def apply():
    from repro.core.range_query import approximate_range
    replay = importlib.import_module("repro.frontend.replay")
    with patched(replay, "FULL_MASK", replay.FULL_MASK & ~1), \
            patched(replay, "exact_range", approximate_range):
        yield None
