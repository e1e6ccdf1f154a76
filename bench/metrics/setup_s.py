"""Process start to window start: generation, bulk load, staging and
warm-up, compiles included (host clock)."""


def read(run):
    return run.setup_s
