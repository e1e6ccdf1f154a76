"""TPC-H's ``lineitem`` columns that query 6 reads, and query 6, in numpy.

The benchmark's own reference for the ``tpch`` family: it imports nothing
of the program.  ``generate`` follows dbgen's rules (TPC-H Standard
Specification §4.2.3) for the four columns Q6 reads:

* each order has 1 to 7 lineitems, uniformly, and the rows lie in order
  key order, as dbgen writes them; the last order is cut at ``records``;
* O_ORDERDATE is uniform over STARTDATE (1992-01-01) to ENDDATE
  (1998-12-31) less 151 days, and L_SHIPDATE is O_ORDERDATE plus 1 to 121
  days;
* L_QUANTITY is uniform over 1 to 50, L_DISCOUNT over 0.00 to 0.10 in
  steps of 0.01;
* L_EXTENDEDPRICE is L_QUANTITY times P_RETAILPRICE of a part key uniform
  over 1 to 200,000 x SF, where P_RETAILPRICE = (90000 + (key / 10) mod
  20001 + 100 x (key mod 1000)) / 100.

Dates are days since STARTDATE, discounts hundredths, prices cents, so
every column is a small integer.  ``q6`` is §2.4.6's query over them: the
rows with L_SHIPDATE in [DATE, DATE + 1 year), L_DISCOUNT in DISCOUNT ±
0.01 and L_QUANTITY below QUANTITY, and the exact revenue
sum(L_EXTENDEDPRICE x L_DISCOUNT) in cents x hundredths.
"""
from __future__ import annotations

import numpy as np

SF1_ROWS = 6_001_215                 # lineitem rows at scale factor 1
ROWS_PER_PAGE = 504                  # 8-byte slots of a 4 KiB page
CHUNK_SLOTS = 8                      # slots of one 64 B chunk
HEADER_SLOTS = 8                     # chunk 0 of a page is its header
DAY0 = np.datetime64("1992-01-01", "D")
ORDERDATE_MAX = int((np.datetime64("1998-12-31", "D") - DAY0)
                    .astype(np.int64)) - 151
SHIP_LAG_DAYS = (1, 121)
QUANTITY = (1, 50)
DISCOUNT_HUNDREDTHS = (0, 10)
PARTS_PER_SF = 200_000


def days(date: str) -> int:
    """Days from STARTDATE to ``date`` (ISO)."""
    return int((np.datetime64(date, "D") - DAY0).astype(np.int64))


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """The generator of one stream of a run, from the run's seed (any
    non-negative integer, above 2**32 too)."""
    return np.random.default_rng([seed % (1 << 64), stream])


def retail_price_cents(partkey: np.ndarray) -> np.ndarray:
    """P_RETAILPRICE in cents (§4.2.3)."""
    pk = np.asarray(partkey, np.int64)
    return 90000 + (pk // 10) % 20001 + 100 * (pk % 1000)


def generate(records: int, *, seed: int, scale_factor: int = 1) -> dict:
    """The four Q6 columns of ``records`` lineitem rows, as int64 arrays
    ``shipdate``, ``discount``, ``quantity``, ``extendedprice``."""
    rng = rng_for(seed, 0)
    lines = rng.integers(1, 8, records // 4 + 64)
    while lines.sum() < records:
        lines = np.r_[lines, rng.integers(1, 8, records // 4 + 64)]
    n_orders = int(np.searchsorted(np.cumsum(lines), records)) + 1
    orderdate = rng.integers(0, ORDERDATE_MAX + 1, n_orders)
    shipdate = np.repeat(orderdate, lines[:n_orders])[:records] \
        + rng.integers(SHIP_LAG_DAYS[0], SHIP_LAG_DAYS[1] + 1, records)
    quantity = rng.integers(QUANTITY[0], QUANTITY[1] + 1, records)
    discount = rng.integers(DISCOUNT_HUNDREDTHS[0],
                            DISCOUNT_HUNDREDTHS[1] + 1, records)
    partkey = rng.integers(1, PARTS_PER_SF * scale_factor + 1, records)
    return {"shipdate": shipdate.astype(np.int64),
            "discount": discount.astype(np.int64),
            "quantity": quantity.astype(np.int64),
            "extendedprice": quantity * retail_price_cents(partkey)}


# ----------------------------------------------------------------- query 6
def predicates(year: int, discount: int, quantity: int) -> dict:
    """Q6's ranges ``column: (lo, hi)``, each ``lo <= column < hi``, for
    DATE = 1 January of ``year``, DISCOUNT ``discount`` hundredths and
    QUANTITY ``quantity`` (BETWEEN is inclusive)."""
    return {"shipdate": (days(f"{year}-01-01"), days(f"{year + 1}-01-01")),
            "discount": (discount - 1, discount + 2),
            "quantity": (0, quantity)}


def q6_mask(cols: dict, year: int, discount: int,
            quantity: int) -> np.ndarray:
    """The rows Q6 selects."""
    keep = np.ones(len(cols["shipdate"]), bool)
    for name, (lo, hi) in predicates(year, discount, quantity).items():
        keep &= (cols[name] >= lo) & (cols[name] < hi)
    return keep


def q6(cols: dict, year: int, discount: int, quantity: int
       ) -> tuple[int, int]:
    """(revenue in cents x hundredths, rows selected) of one Q6."""
    keep = q6_mask(cols, year, discount, quantity)
    revenue = int((cols["extendedprice"][keep]
                   * cols["discount"][keep]).sum())
    return revenue, int(keep.sum())


def hit_pages_and_chunks(keep: np.ndarray) -> tuple[int, int]:
    """Pages, and 64 B chunks, that hold a selected row, rows laid out
    ``ROWS_PER_PAGE`` per page after the page's header chunk."""
    rows = np.nonzero(keep)[0]
    pages = rows // ROWS_PER_PAGE
    chunks = pages * 64 + (HEADER_SLOTS + rows % ROWS_PER_PAGE) // CHUNK_SLOTS
    return len(np.unique(pages)), len(np.unique(chunks))


# ------------------------------------------------------------- parameters
def parameter_sets(traffic: dict) -> list[tuple[int, int, int]]:
    """Every (year, discount, quantity) the traffic can draw."""
    return [(y, d, q)
            for y in range(traffic["date_year"][0],
                           traffic["date_year"][1] + 1)
            for d in range(traffic["discount_hundredths"][0],
                           traffic["discount_hundredths"][1] + 1)
            for q in range(traffic["quantity"][0], traffic["quantity"][1] + 1)]


def draw_parameters(rng: np.random.Generator,
                    traffic: dict) -> tuple[int, int, int]:
    """One query's (year, discount, quantity), each uniform over its
    inclusive range and independent of the others."""
    return tuple(int(rng.integers(lo, hi + 1)) for lo, hi in (
        traffic["date_year"], traffic["discount_hundredths"],
        traffic["quantity"]))
