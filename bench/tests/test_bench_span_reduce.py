"""bench/span_reduce.py, the span metric readers and bench/span_report.py:
on hand-built traces, on a traced tiny run on the CPU, and on traces
recorded on a TPU v5e (bench/testdata)."""
from __future__ import annotations

import copy
import dataclasses
import glob
import importlib.util
import os
from types import SimpleNamespace as NS

import numpy as np
import pytest

from bench import harness, span_reduce, trace_reduce, ycsb

TESTDATA = os.path.join(harness.BENCH_DIR, "testdata")
SPAN_READERS = ("flush_place_us_per_op", "flush_operands_us_per_op",
                "flush_launch_us_per_op", "flush_account_us_per_op",
                "flush_program_us_per_op", "stage_host_us_per_op",
                "tail_host_us_per_op", "read_drain_lag_ms")


def load_span_report():
    spec = importlib.util.spec_from_file_location(
        "bench_span_report", os.path.join(harness.BENCH_DIR,
                                          "span_report.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def ev(name, start, end, **meta):
    return NS(name=name, start_ns=float(start), end_ns=float(end),
              duration_ns=float(end - start), stats=list(meta.items()))


def profile(device_modules, host_spans, other_line=()):
    device = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[ev(n, s, e)
                                       for n, s, e in device_modules])])
    host = NS(name="/host:CPU", lines=[
        NS(name="python", events=host_spans),
        NS(name="other", events=list(other_line))])
    return NS(planes=[device, host])


HOST = [
    ev("sim.flush", -10, 5, flush=1),              # clipped at the start
    ev("bench.window", 0, 100),
    ev("bench.flush", 10, 50),
    ev("sim.flush", 10, 50, flush=2),
    ev("sim.flush.place", 10, 20),
    ev("sim.flush.operands", 20, 35),
    ev("sim.stage", 25, 30, rows=3),
    ev("sim.flush.launch", 35, 40, kind="lookup", rows=16),
    ev("sim.flush.account", 40, 48),
    ev("bench.replay", 60, 95),
    ev("sim.replay.drain", 70, 90, flush=2),
    ev("sim.tail", 70, 88, flush=2, kind="lookup"),
    ev("sim.tail.fetch", 70, 80),
    ev("sim.tail", 90, 94, flush=7, kind="lookup"),  # its flush is absent
    ev("sim.tail", 94, 95, flush=2, kind="plan"),    # not a lookup
    ev("sim.stage", 98, 110, rows=1),              # clipped at the end
]


def test_self_time_clipping_and_counts():
    s = span_reduce.reduce_profile(profile([("jit_a(1)", 36, 39)], HOST))
    assert s.window_ns == (0.0, 100.0)
    assert s.count["sim.flush"] == 2 and s.count["sim.stage"] == 2
    assert s.total_ns["sim.flush"] == 5 + 40
    assert s.total_ns["sim.stage"] == 5 + 2
    # sim.flush 2: 40 ns, its phases cover 10 + 15 + 5 + 8; 2 ns uncovered.
    assert s.self_ns["sim.flush"] == 5 + 2
    assert s.self_ns["sim.flush.operands"] == 10      # 15 less sim.stage
    assert s.self_ns["sim.tail"] == 8 + 4 + 1
    assert s.self_ns["sim.replay.drain"] == 2
    assert sum(s.self_ns.values()) == 5 + 40 + 25 + 2   # union of sim.*


def test_lookup_tails_pair_with_their_flush():
    s = span_reduce.reduce_profile(profile([("jit_a(1)", 36, 39)], HOST))
    assert s.drain_lag_ns == [70 - 50]
    assert s.unpaired_tails == 1
    run = NS(spans=s, n_ops=4)
    assert harness.metric_reader("read_drain_lag_ms")(run) == 20e-6


def test_idle_goes_to_the_innermost_bench_or_sim_span():
    s = span_reduce.reduce_profile(profile(
        [("jit_a(1)", 36, 39), ("jit_b(2)", 71, 79)], HOST))
    old = trace_reduce.reduce_profile(profile(
        [("jit_a(1)", 36, 39), ("jit_b(2)", 71, 79)], HOST))
    assert sum(s.idle_by_span.values()) == sum(old.idle_by_span.values())
    assert s.idle_by_span["sim.flush.place"] == 10
    assert s.idle_by_span["sim.stage"] == 5 + 2
    assert s.idle_by_span["sim.flush.launch"] == 1 + 1
    assert s.idle_by_span["sim.tail.fetch"] == 1 + 1
    assert s.idle_by_span["bench.window"] == 10 + 10 + 3
    assert s.breakdown(top=1) == {
        "idle_gaps_by_program_span": [["bench.window", 23e-9]]}


def test_needs_one_window():
    with pytest.raises(ValueError, match="bench.window"):
        span_reduce.reduce_profile(profile([], HOST[2:]))


def test_span_readers_per_op():
    s = span_reduce.reduce_profile(profile([("jit_a(1)", 36, 39)], HOST))
    run = NS(spans=s, n_ops=2)
    got = {name: harness.metric_reader(name)(run) for name in SPAN_READERS}
    assert got == {"flush_place_us_per_op": 10 / 2 / 1e3,
                   "flush_operands_us_per_op": 10 / 2 / 1e3,
                   "flush_launch_us_per_op": 5 / 2 / 1e3,
                   "flush_account_us_per_op": 8 / 2 / 1e3,
                   "flush_program_us_per_op": None,
                   "stage_host_us_per_op": 7 / 2 / 1e3,
                   "tail_host_us_per_op": 23 / 2 / 1e3,
                   "read_drain_lag_ms": 20e-6}


@pytest.mark.parametrize("name", SPAN_READERS + ("launched_rows_per_page",))
def test_readers_find_nothing_in_a_run_without_them(name):
    """A harness ``Run`` has no ``spans``, and a parent's counters have no
    ``launched_rows``: each reader returns None and does not raise."""
    run = NS(n_ops=10, counters={"staged_pages": 5}, trace=None)
    assert harness.metric_reader(name)(run) is None


def test_launched_rows_per_page():
    read = harness.metric_reader("launched_rows_per_page")
    assert read(NS(counters={"launched_rows": 96, "staged_pages": 40})) \
        == 2.4
    assert read(NS(counters={"launched_rows": 0, "staged_pages": 0})) is None


def test_recorded_trace_without_program_spans():
    """The chip trace recorded before the program had spans: no span
    tables, and the idle attribution is trace_reduce's."""
    path = os.path.join(TESTDATA, "tiny.xplane.pb")
    s = span_reduce.reduce_file(path)
    r = trace_reduce.reduce_file(path)
    assert s.count == {} and s.drain_lag_ns == [] and s.unpaired_tails == 0
    assert s.idle_by_span.keys() == r.idle_by_span.keys()
    for k, v in r.idle_by_span.items():
        assert s.idle_by_span[k] == pytest.approx(v)
    run = NS(spans=s, n_ops=100)
    for name in SPAN_READERS:
        assert harness.metric_reader(name)(run) is None


def tiny(name: str) -> harness.Cell:
    """The cell at 32 key pages, bursts of 8 and a short warm-up."""
    cell = harness.load_cell(name)
    config = copy.deepcopy(cell.config)
    config["records"] = 32 * ycsb.KEYS_PER_PAGE
    config["run_config"]["burst"] = 8
    traffic = dict(cell.traffic, stream_ops_per_s=200_000,
                   max_scan_length=min(cell.traffic["max_scan_length"], 12),
                   warmup={"chunk_ops": 16, "min_ops": 64, "quiet_ops": 32,
                           "max_ops": 512})
    return dataclasses.replace(cell, config=config, traffic=traffic)


@pytest.mark.parametrize("name", ["ycsb_c-10m", "ycsb_a-10m-wb"])
def test_traced_cpu_run_reduces(name, tmp_path, monkeypatch):
    """A traced tiny run on the CPU holds the program's spans; the CPU
    trace has no TPU plane, so the harness's own reduction is stubbed
    with the recorded chip trace's, and no device number is read here."""
    monkeypatch.setattr(harness, "reduce_trace", lambda path:
                        trace_reduce.reduce_file(os.path.join(
                            TESTDATA, "tiny.xplane.pb")))
    monkeypatch.setattr(harness.roofline, "peaks",
                        lambda kind: {"hbm_bytes_per_s": 819e9})
    cell = tiny(name)
    cell = dataclasses.replace(cell, per_layer=cell.per_layer + [
        load_span_report().COUNTER_METRIC])
    line, _ = harness.run_cell(cell, seed=3, seconds=0.3, trace=True,
                               interpret=True, t_start=0.0,
                               keep_trace=str(tmp_path))
    assert line["correct"] is True
    assert line["metrics"]["launched_rows_per_page"]["value"] >= 1
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                        recursive=True)
    s = span_reduce.reduce_file(path)
    want = {"sim.flush", "sim.flush.place", "sim.flush.operands",
            "sim.flush.launch", "sim.flush.account", "sim.tail",
            "sim.tail.fetch", "sim.replay.burst", "sim.replay.drain"}
    if name == "ycsb_a-10m-wb":
        want |= {"sim.flush.program", "sim.stage", "sim.replay.wb_drain"}
    assert want <= set(s.count)
    assert s.unpaired_tails == 0 and s.drain_lag_ns
    assert s.count["sim.replay.drain"] == len(s.drain_lag_ns)
    out = load_span_report().report(s, line)
    assert set(out["span_metrics"]) >= {"flush_place_us_per_op",
                                        "tail_host_us_per_op",
                                        "read_drain_lag_ms"}
    assert out["checks"]["unpaired_lookup_tails"] == 0
    assert 0 <= out["checks"]["flush_self_share"] < 1
    assert all(c == [s.count[k], s.total_ns[k] / 1e9,
                     s.self_ns.get(k, 0.0) / 1e9]
               for k, c in out["spans"].items())
    assert np.isfinite(out["checks"]["flush_inside_over_outside"])


def test_recorded_trace_with_program_spans():
    """``tiny_spans.xplane.pb``: ``record_tiny_trace.py`` run on a TPU v5e
    over the program with spans (a mixed tiny cell: reads, buffered
    updates, scans).  Every span metric reads from it, the phases cover
    the flush, every lookup tail pairs with its flush, and the kernels
    carry their names."""
    import jax
    path = os.path.join(TESTDATA, "tiny_spans.xplane.pb")
    s = span_reduce.reduce_file(path)
    r = trace_reduce.reduce_file(path)
    assert {"sim.flush", "sim.flush.program", "sim.flush.place",
            "sim.flush.operands", "sim.flush.launch", "sim.flush.account",
            "sim.stage", "sim.tail", "sim.tail.fetch", "sim.replay.burst",
            "sim.replay.drain", "sim.replay.scan",
            "sim.replay.wb_drain"} <= set(s.count)
    assert s.self_ns["sim.flush"] < 0.1 * s.total_ns["sim.flush"]
    assert s.unpaired_tails == 0
    assert len(s.drain_lag_ns) == s.count["sim.replay.drain"]
    run = NS(spans=s, n_ops=100)
    for name in SPAN_READERS:
        assert harness.metric_reader(name)(run) > 0, name
    assert sum(s.idle_by_span.values()) == pytest.approx(
        sum(r.idle_by_span.values()))
    assert {"jit_sim_lookup_kernel", "jit__stacked_plan"} <= set(r.kernel_ns)
    assert r.module_seconds("jit_scatter") > 0
    calls = {e.name.split(" ", 1)[0].rsplit(".", 1)[0]
             for plane in jax.profiler.ProfileData.from_file(path).planes
             if plane.name.startswith("/device:TPU:")
             for line in plane.lines if line.name == "XLA Ops"
             for e in line.events if " custom-call(" in e.name}
    assert {"%sim_lookup", "%sim_plan"} <= calls
