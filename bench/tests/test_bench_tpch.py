"""The TPC-H family at a tiny size on the CPU, kernels in interpret mode:
a run of the cell through the unedited harness is correct, the generator's
columns stay in dbgen's domains, and the warm-up leaves no launch shape
for the window to compile."""
from __future__ import annotations

import copy
import dataclasses
import os

import numpy as np

from bench import harness, tpch, trace_reduce

CELL = "tpch_q6-sf1"
FIXTURE = os.path.join(harness.BENCH_DIR, "testdata", "tiny.xplane.pb")


def tiny(pages: int = 24) -> harness.Cell:
    cell = harness.load_cell(CELL)
    config = copy.deepcopy(cell.config)
    config["records"] = pages * tpch.ROWS_PER_PAGE - 37
    return dataclasses.replace(cell, config=config)


def test_cell_is_sf1_on_one_chip():
    cell = harness.load_cell(CELL)
    assert cell.chips == 1
    assert cell.config["records"] == tpch.SF1_ROWS
    assert [m["name"] for m in cell.end_to_end] == [
        "setup_s", "ops_per_s", "peak_hbm_mib"]
    assert {"sim_gather_roofline", "gather_fetched_bytes_per_op",
            "replay_self_us_per_op", "flush_host_us_per_op",
            "device_idle_share"} == {m["name"] for m in cell.per_layer}


def test_tiny_run_is_correct():
    line, info = harness.run_cell(tiny(), seed=2**32 + 17, seconds=0.3,
                                  trace=False, interpret=True, t_start=0.0)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert line["checks"] == {name: {"value": 0, "limit": 0} for name in
                              ("wrong_revenue", "wrong_rows", "host_served")}
    assert set(line["metrics"]) == {"setup_s", "ops_per_s"}
    assert info["window_compiles"][0] == 0
    assert info["setup"]["warm_up_ops"] >= 2     # pass paddings 16 and 32
    assert info["latency_ms"]["q6"][0] == line["attempted"]


def test_traced_run_reads_the_gather_counter(monkeypatch):
    """The trace of a CPU run has no TPU plane, so the reduction is the
    recorded chip trace's, which has no gather kernel: the roofline reads
    nothing, the counter reads the padded output per query."""
    monkeypatch.setattr(harness, "reduce_trace",
                        lambda path: trace_reduce.reduce_file(FIXTURE))
    monkeypatch.setattr(harness.roofline, "peaks",
                        lambda kind: {"hbm_bytes_per_s": 819e9})
    line, _ = harness.run_cell(tiny(), seed=5, seconds=0.2, trace=True,
                               interpret=True, t_start=0.0)
    assert line["correct"] is True
    assert set(line["metrics"]) == {
        "replay_self_us_per_op", "flush_host_us_per_op",
        "device_idle_share", "gather_fetched_bytes_per_op"}
    # 24 pages, nearly all with a hit: 24 rows padded to 32, 4 KiB each.
    assert line["metrics"]["gather_fetched_bytes_per_op"]["value"] == \
        32 * 4096


def test_generator_columns_stay_in_their_domains():
    cols = tpch.generate(50_000, seed=2**33 + 5)
    assert all(len(v) == 50_000 for v in cols.values())
    assert 1 <= cols["shipdate"].min() and cols["shipdate"].max() <= 2526
    assert cols["discount"].min() == 0 and cols["discount"].max() == 10
    assert cols["quantity"].min() == 1 and cols["quantity"].max() == 50
    price = cols["extendedprice"] // cols["quantity"]
    assert (cols["extendedprice"] % cols["quantity"] == 0).all()
    assert 90_000 <= price.min() and price.max() <= 209_900
    again = tpch.generate(50_000, seed=2**33 + 5)
    other = tpch.generate(50_000, seed=2**33 + 6)
    assert all((cols[k] == again[k]).all() for k in cols)
    assert not (cols["shipdate"] == other["shipdate"]).all()


def test_q6_selects_about_two_percent():
    cols = tpch.generate(200_000, seed=7)
    traffic = harness.load_cell(CELL).traffic
    share = [tpch.q6(cols, *p)[1] / 200_000
             for p in tpch.parameter_sets(traffic)]
    assert 0.012 < np.mean(share) < 0.025


def test_warm_up_leaves_no_gather_shape_to_compile():
    """After the warm-up, a gather flush of any page count up to the
    table's compiles nothing, whichever pages the queries hit."""
    from repro.core.commands import Command
    import jax
    cell = tiny(pages=20)
    jax.clear_caches()          # so that the warm-up has to compile
    deploy = cell.family.Deployment(cell, seed=2**31 + 99, seconds=0.1,
                                    interpret=True, trace=False, marks={})
    with harness.CompileLog() as log:
        assert deploy.warm_up(log) == 2          # pass paddings 16 and 32
        warm_compiles = log.compiles
        for n in (1, 8, 9, 16, 17, deploy.index.n_pages):
            tickets = [deploy.backend.submit_gather(Command.gather(p, 0b10))
                       for p in range(n)]
            deploy.backend.flush()
            assert all(t.result().chunk_ids.tolist() == [1] for t in tickets)
    assert warm_compiles > 0 and log.compiles == warm_compiles
