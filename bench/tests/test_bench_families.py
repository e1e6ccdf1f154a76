"""Deployment families found by name (``bench/families/<family>.py``).

The YCSB family's streams, warm-up shapes and read-back samples are pinned
by digests taken before the family was moved out of the harness; the two
roofline readers read the same numbers from the recorded trace as they did
from the harness's old per-kernel fields.  A second family that exists only
as files under a temporary root runs through the unedited harness on the
CPU, kernels in interpret mode.
"""
from __future__ import annotations

import hashlib
import io
import json
import os
import shutil

import numpy as np
import pytest

from bench import control, harness, trace_reduce, ycsb

FIXTURE = os.path.join(harness.BENCH_DIR, "testdata", "tiny.xplane.pb")
MASKED_FAMILY = os.path.join(harness.BENCH_DIR, "testdata",
                             "masked_family.py")

# name: (window stream length at 20 s, sha256 of ops, keys and scan_lens,
#        sha256 of the warm-up bursts and scans, read-back sample size,
#        sha256 of the read-back sample at half the window stream), seed
#        2**31 + 4321.
PINNED = {
    "ycsb_c-10m": (
        416768,
        "1bcad4f8e810c09c72d342f1d5aa9bf66d4831fae7c31a97d5ee0088b4cc5ffc",
        "2bb060183b4de97f21dac16cca849d18c6264f14b75dd53ca3f7e049e246c79b",
        0, "e3b0c44298fc1c149afbf4c8996fb92427ae41e4649b934ca495991b7852b855"),
    "ycsb_e-10m": (
        43222,
        "668503ecba24f2a666fe177d8e198c68b98f7aa428d2cb1b0c842884869733b8",
        "4c122d95b397bd292f05455aeb3edbce48961965331d36fb94493cbaece53118",
        0, "e3b0c44298fc1c149afbf4c8996fb92427ae41e4649b934ca495991b7852b855"),
    "ycsb_a-10m-wb": (
        218784,
        "7222a66c20679563ffa8a48fc891c59c9ca87abf84ecdbf74c2f5291612d417e",
        "ff305b689dd09588b8fdccac986193827d24b0439f35fd7fb2411ce1272d2dfb",
        4096,
        "035ecf127a2d1fdf73972d290ac940056cca2afd42db6405e40611490b2ddb3e"),
    "ycsb_b-10m-wb": (
        418784,
        "12f2a08c8688ea88431b45371b5fccfa60927295a33bca04e45b9c2cd436d1c2",
        "ff305b689dd09588b8fdccac986193827d24b0439f35fd7fb2411ce1272d2dfb",
        4096,
        "5f1ecbf4b3b8d6e6ba2b028487c948bdc1c1f2cb6edbdafea05dbf34eeb5b107"),
}


def digest(*arrays) -> str:
    h = hashlib.sha256()
    for a in arrays:
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()


def test_ycsb_e_stream_is_pinned():
    traffic = harness.load_cell("ycsb_e-10m").traffic
    s = ycsb.generate(3000, traffic, records=50_000, seed=2**31 + 12345)
    assert digest(s.ops, s.keys, s.scan_lens) == \
        "ce8fdabeeb3353b433e5aeedf42a6c55cda02f31f8cda279149f411ff92be65f"


@pytest.mark.parametrize("name", sorted(PINNED))
def test_run_stream_warm_up_and_read_back_are_pinned(name):
    """What a whole run of the cell generates, warms up and reads back,
    at its real size, is what it was before the family moved."""
    cell = harness.load_cell(name)
    family, seed = cell.family, 2**31 + 4321
    stream, n_main, scans = family.make_stream(cell, 20, seed)
    warm = json.dumps({"bursts": family.burst_sizes(cell), "scans": scans})
    written = family.readback_keys(stream, n_main // 2, seed)
    assert (n_main, digest(stream.ops, stream.keys, stream.scan_lens),
            hashlib.sha256(warm.encode()).hexdigest(), len(written),
            digest(written)) == PINNED[name]


def test_roofline_readers_read_as_before():
    """The values the readers gave from the harness's old fields
    (``lookup_launches=7, lookup_bytes=123456, plan_launches=3,
    plan_bytes=9876``) on the recorded trace."""
    run = harness.Run(
        setup_s=1.0, window_s=2.0, kinds=np.zeros(5), latency_s=np.zeros(5),
        host_s={}, counters={},
        kernel_bytes={"jit_sim_lookup_kernel": (7, 123_456),
                      "jit__stacked_plan": (3, 9_876)},
        peaks={"hbm_bytes_per_s": 819e9}, memory_peak_bytes=None,
        trace=trace_reduce.reduce_file(FIXTURE))
    assert harness.metric_reader("sim_lookup_roofline")(run) == \
        1.2478470756616453
    assert harness.metric_reader("sim_plan_roofline")(run) == \
        0.2912003877954132
    run.kernel_bytes = {"jit_sim_lookup_kernel": (0, 0)}
    assert harness.metric_reader("sim_lookup_roofline")(run) is None
    assert harness.metric_reader("sim_plan_roofline")(run) is None


# ------------------------------------------------- a family of new files
READER = '''"""Required bytes of the window's search launches per op."""

PROGRAM = "jit__stacked_search"


def read(run):
    launches, required = run.kernel_bytes.get(PROGRAM, (0, 0))
    return required / run.n_ops if launches else None
'''


def masked_root(root, family: str | None = "masked"):
    """A checkout of its own under ``root``: BENCHMARK.json, one config,
    one traffic mix, the masked family and three metric readers."""
    bench = root / "bench"
    for d in ("configs", "traffic", "families", "metrics"):
        (bench / d).mkdir(parents=True)
    (root / "BENCHMARK.json").write_text(json.dumps({
        "configs": [{"name": "masked-8p",
                     "file": "bench/configs/masked-8p.json"}],
        "workloads": [{"name": "masked_select-8p", "config": "masked-8p",
                       "traffic": "masked_select", "chips": 1}],
        "end_to_end": [{"name": "setup_s", "unit": "s"},
                       {"name": "ops_per_s", "unit": "ops/s"}],
        "per_layer": [{"name": "search_bytes_per_op", "unit": "B/op"}]}))
    config = {"name": "masked-8p", "pages": 8,
              "geometry": {"channels": 2, "dies_per_channel": 1}}
    if family is not None:
        config["family"] = family
    (bench / "configs" / "masked-8p.json").write_text(json.dumps(config))
    (bench / "traffic" / "masked_select.json").write_text(json.dumps(
        {"name": "masked_select", "mask_bits": 8, "warmup_ops": 2,
         "stream_ops_per_s": 5000}))
    shutil.copy(MASKED_FAMILY, bench / "families" / "masked.py")
    for name in ("setup_s", "ops_per_s"):
        shutil.copy(os.path.join(harness.BENCH_DIR, "metrics", name + ".py"),
                    bench / "metrics")
    (bench / "metrics" / "search_bytes_per_op.py").write_text(READER)
    return str(root)


def test_second_family_runs_under_the_unedited_harness(tmp_path):
    cell = harness.load_cell("masked_select-8p", root=masked_root(tmp_path))
    assert cell.family.__file__.startswith(str(tmp_path))
    line, info = harness.run_cell(cell, seed=2**31 + 99, seconds=0.3,
                                  trace=False, interpret=True, t_start=0.0)
    out, err = io.StringIO(), io.StringIO()
    harness.emit(line, info, out=out, err=err)
    printed = json.loads(out.getvalue().splitlines()[-1])
    assert printed["correct"] is True and printed["failed"] == 0
    assert printed["attempted"] > 0
    assert set(printed["metrics"]) == {"setup_s", "ops_per_s"}
    assert printed["checks"] == {"wrong_counts": {"value": 0, "limit": 0}}
    assert err.getvalue().splitlines()[-1] == "check wrong_counts: 0 (limit 0)"
    assert info["latency_ms"]["search"][0] == printed["attempted"]
    assert info["window_compiles"][0] == 0


def test_second_family_reader_finds_its_kernel_bytes(tmp_path, monkeypatch):
    """The trace of a CPU run has no TPU plane, so the reduction is the
    recorded chip trace's; the reader's entry is under test."""
    monkeypatch.setattr(harness, "reduce_trace",
                        lambda path: trace_reduce.reduce_file(FIXTURE))
    monkeypatch.setattr(harness.roofline, "peaks",
                        lambda kind: {"hbm_bytes_per_s": 819e9})
    cell = harness.load_cell("masked_select-8p", root=masked_root(tmp_path))
    line, _ = harness.run_cell(cell, seed=5, seconds=0.3, trace=True,
                               interpret=True, t_start=0.0)
    assert line["correct"] is True
    assert line["metrics"] == {"search_bytes_per_op": {
        "value": 8 * (4096 + 16 + 64), "unit": "B/op"}}


def test_second_family_check_catches_half_the_table_left_out(tmp_path):
    def prepare(deploy, backend):
        deploy.n_pages //= 2
    cell = harness.load_cell("masked_select-8p", root=masked_root(tmp_path))
    line, _ = harness.run_cell(cell, seed=6, seconds=0.2, trace=False,
                               interpret=True, t_start=0.0, prepare=prepare)
    assert line["correct"] is False and line["failed"] > 0


@pytest.mark.parametrize("family", [None, "no_such_family"])
def test_config_must_name_a_known_family(tmp_path, family):
    root = masked_root(tmp_path, family=family)
    with pytest.raises(ValueError, match=r"known families: \['masked'\]"):
        harness.load_cell("masked_select-8p", root=root)


def test_breaks_apply_only_to_their_families(tmp_path):
    """The YCSB faults read YCSB traffic keys, which a masked cell lacks:
    they do not apply there, and raise nothing."""
    cell = harness.load_cell("masked_select-8p", root=masked_root(tmp_path))
    assert control.faults_of(cell) == []
    ycsb_cell = harness.load_cell("ycsb_a-10m-wb")
    assert control.faults_of(ycsb_cell) == [
        "answer_altered", "half_batch", "write_dropped"]
