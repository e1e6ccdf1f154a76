"""The benchmark harness at a tiny size on the CPU, kernels in interpret
mode.  These call the harness's functions; ``bench/run.py``'s ``main``
needs a TPU and must refuse to run here."""
from __future__ import annotations

import copy
import dataclasses
import io
import json
import os

import numpy as np
import pytest

from bench import harness, oracle, trace_reduce, ycsb

SPEC = json.load(open(os.path.join(harness.ROOT, "BENCHMARK.json")))
CELLS = [w["name"] for w in SPEC["workloads"]]
FIXTURE = os.path.join(harness.BENCH_DIR, "testdata", "tiny.xplane.pb")


def tiny(cell: harness.Cell, *, key_pages: int = 32) -> harness.Cell:
    """The cell at 32 key pages (more value pages than the write buffer's
    high water), bursts of 8 and a short warm-up."""
    config = copy.deepcopy(cell.config)
    config["records"] = key_pages * ycsb.KEYS_PER_PAGE
    config["run_config"]["burst"] = 8
    traffic = copy.deepcopy(cell.traffic)
    traffic["max_scan_length"] = min(traffic["max_scan_length"], 12)
    traffic["stream_ops_per_s"] = 200_000
    traffic["warmup"] = {"chunk_ops": 16, "min_ops": 64, "quiet_ops": 32,
                         "max_ops": 512}
    return dataclasses.replace(cell, config=config, traffic=traffic)


def run(cell, **kw):
    kw = {"seed": 3, "seconds": 0.3, "trace": False, **kw}
    return harness.run_cell(cell, interpret=True, t_start=0.0, **kw)


@pytest.mark.parametrize("name", CELLS)
def test_cell_files_found_by_name(name):
    cell = harness.load_cell(name)
    assert cell.chips == 1
    assert cell.config["records"] == 10_000_000
    assert {"read_proportion", "update_proportion", "scan_proportion",
            "warmup"} <= set(cell.traffic)
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s", "ops_per_s"}
    for m in cell.end_to_end + cell.per_layer:
        assert callable(harness.metric_reader(m["name"]))


def test_every_metric_and_config_has_its_file():
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert os.path.isfile(os.path.join(harness.BENCH_DIR, "metrics",
                                           m["name"] + ".py"))
    for c in SPEC["configs"]:
        assert json.load(open(os.path.join(harness.ROOT, c["file"])))[
            "name"] == c["name"]
    with pytest.raises(KeyError, match="no workload"):
        harness.load_cell("no-such-cell")


def test_stream_is_fixed_by_the_seed():
    traffic = harness.load_cell("ycsb_e-10m").traffic
    big = 2**31 + 12345
    a = ycsb.generate(2000, traffic, records=5000, seed=big)
    b = ycsb.generate(2000, traffic, records=5000, seed=big)
    c = ycsb.generate(2000, traffic, records=5000, seed=big + 1)
    assert (a.keys == b.keys).all() and (a.ops == b.ops).all()
    assert not (a.keys == c.keys).all()
    assert 0.9 < (a.ops == ycsb.OP_SCAN).mean() < 0.99
    assert a.scan_lens.min() >= 1 and a.scan_lens.max() <= 100


def test_oracle_flags_a_corrupted_read_and_scan():
    ops = np.array([0, 1, 0, 2, 0], np.uint8)
    keys = np.array([3, 3, 3, 10, 4], np.int64)
    lens = np.array([1, 1, 1, 5, 1], np.int32)
    pos = np.arange(5)
    exp = oracle.expected_reads(ops, keys, pos)
    assert exp[0] == oracle.initial_values([3])[0]
    assert exp[2] == 1 * 2 + 1                     # the update at position 1
    counts = oracle.expected_scan_counts(keys, lens, n_keys=100)
    hits = np.ones(5, bool)
    good = oracle.compare(ops, keys, lens, pos, exp, hits, counts,
                          n_keys=100)
    assert not good["wrong_read"].any() and not good["wrong_scan"].any()
    bad_values, bad_counts = exp.copy(), counts.copy()
    bad_values[2] ^= np.uint64(1)
    bad_counts[3] += 1
    bad = oracle.compare(ops, keys, lens, pos, bad_values, hits, bad_counts,
                         n_keys=100)
    assert bad["wrong_read"].tolist() == [False, False, True, False, False]
    assert bad["wrong_scan"].tolist() == [False, False, False, True, False]
    missed = oracle.compare(ops, keys, lens, pos, exp, ~hits, counts,
                            n_keys=100)
    assert missed["wrong_read"].sum() == 3


def test_warmup_scans_cover_every_plan_shape():
    cell = harness.load_cell("ycsb_e-10m")
    traffic = cell.traffic
    n_keys = 19842 * ycsb.KEYS_PER_PAGE
    scans = cell.family.warmup_scans(traffic, n_keys, seed=9)
    sigs = set()
    for k, n in scans:
        lo, hi = k + 1, k + 1 + n
        pages = (hi - 2) // 504 - (lo - 1) // 504 + 1
        sigs.add((pages, harness.roofline.exact_range_passes(lo, hi)))
    assert len(sigs) == len(scans)
    assert {p for p, _ in sigs} == {1, 2}
    assert (2, 1) in sigs and (1, 1) in sigs


@pytest.mark.parametrize("name", CELLS)
def test_untraced_run_is_correct_and_shaped(name):
    line, info = run(tiny(harness.load_cell(name)))
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "checks"]
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    cell = harness.load_cell(name)
    want = {m["name"] for m in cell.end_to_end} - {"peak_hbm_mib"}
    assert want <= set(line["metrics"])
    assert all(c["value"] == 0 == c["limit"]
               for c in line["checks"].values())
    assert line["device"]["platform"] == "cpu"
    assert info["window_compiles"][0] == 0
    assert info["setup"]["warm_up_ops"] > 0


def test_traced_run_adds_breakdown(monkeypatch):
    """The trace of a CPU run has no TPU plane, so the reduction is the
    recorded chip trace's; the line's shape is what is under test."""
    monkeypatch.setattr(harness, "reduce_trace",
                        lambda path: trace_reduce.reduce_file(FIXTURE))
    monkeypatch.setattr(harness.roofline, "peaks",
                        lambda kind: {"hbm_bytes_per_s": 819e9})
    line, _ = run(tiny(harness.load_cell("ycsb_a-10m-wb")), trace=True)
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "breakdown", "checks"]
    assert line["correct"] is True
    assert set(line["metrics"]) == {
        "replay_self_us_per_op", "flush_host_us_per_op",
        "staged_bytes_per_op", "stage_device_us_per_op",
        "sim_lookup_roofline", "device_idle_share"}
    assert 0 < line["device"]["busy_s"] < line["device"]["window_s"]
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}


def test_emit_puts_checks_last_on_stderr_and_json_last_on_stdout():
    line = {"correct": True, "attempted": 1, "failed": 0, "metrics": {},
            "device": {}, "checks": {"wrong_reads": {"value": 0,
                                                     "limit": 0}}}
    out, err = io.StringIO(), io.StringIO()
    harness.emit(line, {"setup": {"bulk_load": 1.5},
                        "window_compiles": (2, 0.5)}, out=out, err=err)
    assert out.getvalue().splitlines()[:2] == [
        "setup: bulk_load 1.5", "window compiles: 2 (0.5 s)"]
    assert json.loads(out.getvalue().splitlines()[-1]) == line
    assert err.getvalue().splitlines()[-1] == "check wrong_reads: 0 " \
        "(limit 0)"


def test_run_py_refuses_without_tpu(capsys):
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "bench_run_main", os.path.join(harness.BENCH_DIR, "run.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    assert module.main(["--workload", "ycsb_c-10m", "--seed", "1",
                        "--seconds", "1", "--trace", "0"]) == 1
    assert capsys.readouterr().out == ""
