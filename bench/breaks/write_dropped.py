"""Fault: a drain acknowledges the buffered pages and programs none of
them, so the step returns the stored state unchanged.  It needs a write
buffer and updates to drop."""
import contextlib

KIND = "fault"


def applies(cell) -> bool:
    return (cell.config["family"] == "ycsb"
            and cell.config["run_config"]["preset"] == "buffered"
            and cell.traffic["update_proportion"] > 0)


@contextlib.contextmanager
def apply():
    def prepare(core, backend):
        wb = core.wb

        def flush(backend):
            n = wb.n_dirty
            wb._dirty.clear()
            return n
        wb.flush = flush
    yield prepare
