"""Control: reads skip the write buffer's overlay and go to flash while
their page is dirty, so a buffered write is acknowledged but not seen."""
import contextlib

KIND = "control"


@contextlib.contextmanager
def apply():
    def prepare(core, backend):
        core.wb.get = lambda page_addr: None
    yield prepare
