"""The device allocator's peak bytes in use after the window, on the
fullest chip, in MiB: what bounds the index one chip can hold."""


def read(run):
    if run.memory_peak_bytes is None:
        return None
    return run.memory_peak_bytes / 2**20
