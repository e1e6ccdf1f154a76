"""Each cell's control and faults, at a tiny size on the CPU: with the
timed path broken underneath, the rest of a run goes as usual and the
check must read ``correct`` false.  The cases come from ``BENCHMARK.json``:
each cell's control as its configuration names it, and each fault in
``bench/breaks/`` that the cell can have.  The one-chip cells have no
exchange between chips to leave out."""
from __future__ import annotations

import copy
import dataclasses
import json
import os

import pytest

from bench import control, harness, ycsb

CELLS = [w["name"] for w in json.load(open(os.path.join(
    harness.ROOT, "BENCHMARK.json")))["workloads"]]
CASES = [(name, brk) for name in CELLS
         for brk in ["control"] + control.faults_of(harness.load_cell(name))]


def tiny(name: str) -> harness.Cell:
    cell = harness.load_cell(name)
    config = copy.deepcopy(cell.config)
    config["records"] = 32 * ycsb.KEYS_PER_PAGE
    config["run_config"]["burst"] = 8
    traffic = copy.deepcopy(cell.traffic)
    traffic["max_scan_length"] = 12
    traffic["stream_ops_per_s"] = 200_000
    traffic["warmup"] = {"chunk_ops": 16, "min_ops": 64, "quiet_ops": 32,
                         "max_ops": 512}
    return dataclasses.replace(cell, config=config, traffic=traffic)


def test_every_cell_has_a_control_and_a_fault():
    for name in CELLS:
        cell = harness.load_cell(name)
        assert control.control_of(cell).KIND == "control"
        assert control.faults_of(cell)


@pytest.mark.parametrize("name,brk", CASES)
def test_break_reads_not_correct(name, brk):
    cell = tiny(name)
    fn = (control.control_of(cell) if brk == "control"
          else control.load_break(brk))
    line, _ = control.run_broken(cell, fn, seed=11, seconds=0.3,
                                 interpret=True, t_start=0.0)
    assert line["correct"] is False
    assert line["failed"] > 0 or line["checks"]["lost_writes"]["value"] > 0


@pytest.mark.parametrize("name", ["ycsb_c-10m", "ycsb_b-10m-wb"])
def test_patches_are_undone(name):
    """A break leaves nothing behind: the next run is correct."""
    cell = tiny(name)
    with control.control_of(cell).apply():
        pass
    with control.load_break("answer_altered").apply(), \
            control.load_break("half_batch").apply():
        pass
    line, _ = harness.run_cell(cell, seed=12, seconds=0.3, trace=False,
                               interpret=True, t_start=0.0)
    assert line["correct"] is True
