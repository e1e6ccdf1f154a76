"""Conjunctive selections (``RowCodec.where``, ``SimSecondaryIndex.select_where``)
against plain numpy.

The planner's exact plan is held to a numpy conjunction on seeded random
packed keys, edges included; ``select_where`` runs on the scalar reference
backend and on ``ShardedSsdBackend`` with its kernels in interpret mode,
and must return exactly the rows a numpy filter returns; TPC-H Q6's revenue
summed from those rows must equal the benchmark's own reference
(``bench/tpch.py``).
"""
from __future__ import annotations

import dataclasses
import glob
import os
import sys

import jax
import numpy as np
import pytest

from repro import trace
from repro.backend.planestore import padded_rows
from repro.backend.sharded import ShardedSsdBackend
from repro.core.bitweaving import Column, RowCodec
from repro.core.engine import SimChipArray
from repro.core.range_query import conjunctive_range
from repro.index.secondary import (ROWS_PER_PAGE, IncompleteGatherError,
                                   SimSecondaryIndex)
from repro.kernels.sim_plan.sim_plan import MAX_PASSES

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
from bench import tpch  # noqa: E402

Q6_COLUMNS = (("shipdate", 12), ("discount", 4), ("quantity", 6),
              ("extendedprice", 24))
N_PAGES = 16
SEED = 2**31 + 1616


def q6_codec() -> RowCodec:
    return RowCodec([Column(n, w) for n, w in Q6_COLUMNS])


def numpy_where(values: dict, predicates: dict) -> np.ndarray:
    keep = np.ones(len(next(iter(values.values()))), bool)
    for name, (lo, hi) in predicates.items():
        keep &= (values[name] >= lo) & (values[name] < hi)
    return keep


# ------------------------------------------------------------- the planner
PLANS = {
    "q6": {"shipdate": (366, 731), "discount": (4, 7), "quantity": (0, 24)},
    "first_from_zero": {"shipdate": (0, 100), "quantity": (10, 11)},
    "first_to_top": {"shipdate": (4000, 4096), "discount": (0, 16)},
    "first_whole": {"shipdate": (0, 4096), "quantity": (0, 64)},
    "one_value_each": {"shipdate": (2001, 2002), "discount": (5, 6),
                       "quantity": (63, 64), "extendedprice": (0, 1)},
    "complement_low_empty": {"discount": (0, 9), "quantity": (17, 40)},
    "complement_high_empty": {"quantity": (5, 64),
                              "extendedprice": (1 << 23, 1 << 24)},
    "later_column_first": {"extendedprice": (12345, 9_000_000),
                           "discount": (3, 4)},
}


@pytest.mark.parametrize("name", sorted(PLANS))
def test_where_plan_matches_numpy_conjunction(name):
    codec = q6_codec()
    rng = np.random.default_rng([SEED, len(name)])
    values = {n: rng.integers(0, 1 << w, 20_000) for n, w in Q6_COLUMNS}
    # Put each range's edges in the data too.
    for i, (col, (lo, hi)) in enumerate(PLANS[name].items()):
        values[col][i * 8:i * 8 + 4] = [lo, hi - 1, max(lo - 1, 0),
                                         min(hi, (1 << codec.widths[col]) - 1)]
    keys = codec.encode_rows(values)
    plan = codec.where(PLANS[name])
    assert plan.exact and plan.include
    assert (plan.evaluate(keys) == numpy_where(values, PLANS[name])).all()


def test_where_puts_the_most_significant_column_first():
    codec = q6_codec()
    plan = codec.where({"quantity": (0, 24), "shipdate": (366, 731)})
    shipdate = codec.range("shipdate", 366, 731)
    assert plan.include == shipdate.include
    with pytest.raises(KeyError, match="l_tax"):
        codec.where({"l_tax": (0, 1)})


def test_q6_plans_need_11_to_18_passes():
    codec = q6_codec()
    traffic = {"date_year": [1993, 1997], "discount_hundredths": [2, 9],
               "quantity": [24, 25]}
    passes = {codec.where(tpch.predicates(*p)).n_passes
              for p in tpch.parameter_sets(traffic)}
    assert min(passes) == 11 and max(passes) == 18


def test_planner_refuses_a_plan_over_the_pass_limit():
    whole = (1, (1 << 64) - 1, 0, 64)       # 126 passes on its own
    assert conjunctive_range([whole]).n_passes == 126
    with pytest.raises(ValueError, match=f"at most {MAX_PASSES}"):
        conjunctive_range([whole, (0x7FFF, 0x8001, 0, 64)])
    with pytest.raises(ValueError):
        conjunctive_range([(0, 10, 0, 8), (5, 5, 8, 8)])   # empty range


# --------------------------------------------------------- select_where
@pytest.fixture(scope="module")
def table():
    """Q6's four columns, dbgen's rules, N_PAGES pages less a partial
    last page."""
    return tpch.generate(N_PAGES * ROWS_PER_PAGE - 100, seed=SEED)


def make_index(kind: str, cols: dict) -> SimSecondaryIndex:
    if kind == "scalar":
        backend = SimChipArray(n_chips=1, pages_per_chip=N_PAGES)
    else:
        backend = ShardedSsdBackend.from_geometry(
            channels=8, dies_per_channel=2, pages_per_chip=N_PAGES // 16,
            timeline=True, use_kernel=True)
    index = SimSecondaryIndex(backend, q6_codec())
    index.load_rows(cols)
    return index


@pytest.fixture(scope="module", params=["scalar", "sharded"])
def index(request, table):
    return make_index(request.param, table)


SELECTIONS = [
    tpch.predicates(1994, 6, 24),
    {"shipdate": (0, 4096), "discount": (10, 11)},
    {"discount": (0, 1), "quantity": (50, 64)},
    {"shipdate": (1000, 1001), "quantity": (0, 64)},
    # Holds the all-ones vacant slots of the partial last page.
    {"shipdate": (2048, 4096)},
]


@pytest.mark.parametrize("i", range(len(SELECTIONS)))
def test_select_where_returns_the_numpy_rows(index, table, i):
    preds = SELECTIONS[i]
    want = index.codec.encode_rows(table)[numpy_where(table, preds)]
    assert np.array_equal(index.select_where(preds), want)


@pytest.mark.parametrize("params", [(1993, 2, 24), (1995, 6, 25),
                                    (1997, 9, 24)])
def test_q6_revenue_matches_the_reference(index, table, params):
    rows = index.select_where(tpch.predicates(*params))
    codec = index.codec
    revenue = int((codec.decode_rows(rows, "extendedprice").astype(np.int64)
                   * codec.decode_rows(rows, "discount").astype(np.int64)
                   ).sum())
    assert (revenue, len(rows)) == tpch.q6(table, *params)
    assert len(rows) > 0


def test_one_plan_flush_and_one_gather_flush(table):
    index = make_index("sharded", table)
    stats = index.backend.stats
    params = (1996, 4, 25)
    keep = tpch.q6_mask(table, *params)
    pages, chunks = tpch.hit_pages_and_chunks(keep)
    before = (stats.flushes, stats.plans, stats.gathers)
    index.select_where(tpch.predicates(*params))
    assert (stats.flushes, stats.plans, stats.gathers) == (
        before[0] + 2, before[1] + N_PAGES, before[2] + pages)
    assert stats.gathered_chunks == chunks
    assert index.io_chunk_bytes == 64 * stats.gathered_chunks
    assert index.io_bitmap_bytes == 64 * N_PAGES
    # The tail copies the whole padded (rows, 64, 16) uint32 output.
    assert stats.gather_fetched_bytes == padded_rows(pages, 8) * 64 * 64


def test_a_gather_missing_its_last_chunk_raises(index, table, monkeypatch):
    """Every gather response loses its last chunk on the way back: the
    selection raises ``IncompleteGatherError`` naming the first page with
    a match and that page's last selected chunk."""
    backend = index.backend
    submit_gather, flush = backend.submit_gather, backend.flush
    gathers = []

    def submit(cmd):
        gathers.append(submit_gather(cmd))
        return gathers[-1]

    def flush_and_drop():
        flush()
        for ticket in gathers:
            g = ticket.result()
            ticket._resolve(dataclasses.replace(
                g, chunks=g.chunks[:-1], chunk_ids=g.chunk_ids[:-1],
                parity_ok=g.parity_ok[:-1]))
        gathers.clear()

    monkeypatch.setattr(backend, "submit_gather", submit)
    monkeypatch.setattr(backend, "flush", flush_and_drop)
    preds = tpch.predicates(1995, 5, 24)
    row = np.flatnonzero(numpy_where(table, preds))
    page = row[0] // ROWS_PER_PAGE
    # Rows start at slot 8, after the header chunk; 8 slots per chunk.
    last = (8 + row[row // ROWS_PER_PAGE == page] % ROWS_PER_PAGE).max() // 8
    with pytest.raises(IncompleteGatherError, match=rf"^page {page}: "
                       rf"chunks \[{last}\] not gathered$"):
        index.select_where(preds)


def test_select_where_writes_its_spans(table, tmp_path):
    index = make_index("scalar", table)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        index.select_where(tpch.predicates(1993, 5, 24))
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                        recursive=True)
    spans = {e.name: (e.start_ns, e.end_ns, dict(e.stats))
             for plane in jax.profiler.ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events
             if e.name.startswith("sim.select")}
    assert set(spans) == {trace.SELECT, trace.SELECT_COLLECT,
                          trace.SELECT_DECODE}
    s, e, meta = spans[trace.SELECT]
    assert int(meta["pages"]) == N_PAGES and int(meta["passes"]) > 0
    for name in (trace.SELECT_COLLECT, trace.SELECT_DECODE):
        assert s <= spans[name][0] <= spans[name][1] <= e
