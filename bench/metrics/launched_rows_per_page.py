"""Page-plane rows handed to the window's search, plan and lookup launches
(``BackendStats.launched_rows``, padding and duplicates included) over the
distinct pages those launches referenced (``staged_pages``): 1 is a launch
with no padding and no duplicate row."""


def read(run):
    rows = run.counters.get("launched_rows")
    pages = run.counters.get("staged_pages")
    return rows / pages if rows and pages else None
