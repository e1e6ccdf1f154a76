"""chip_smoke.py at a small size on the CPU, with interpret-mode kernels.

The script's phases run here over a 64-key-page index with a few hundred
ops, so a change that breaks the replay path, the oracle or the checks
fails here before it costs a chip run.  Off a TPU the script itself must
refuse to run.
"""
from __future__ import annotations

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
import chip_smoke  # noqa: E402
from repro.workload.ycsb import KEYS_PER_PAGE  # noqa: E402

RECORDS = 64 * KEYS_PER_PAGE


@pytest.mark.parametrize("phase", ["ycsb_c", "ycsb_a", "ycsb_e", "split"])
def test_phase_matches_oracle(phase):
    gen_kw, config = chip_smoke.phases()[phase]
    gen_kw = dict(gen_kw, n_queries=96 if phase == "ycsb_e" else 256)
    out = chip_smoke.run_phase(phase, gen_kw, config, records=RECORDS,
                               interpret=True)
    assert out["mismatches"] == 0 and out["degraded_ops"] == 0
    assert out["resident_rows"] == 2 * 64
    assert out["kernel_launches"] == out["flushes"] > 0


def test_oracles_follow_stream_order():
    """A read returns the loaded value until a write to its key, then the
    write's tag; a scan counts the stored keys 1..n_keys in its range."""
    import numpy as np
    from repro.workload.ycsb import Workload
    keys = np.array([3, 3, 5, 3, 3, 9998], np.int64)
    wl = Workload(ops=np.array([0, 1, 0, 1, 0, 2], np.uint8),
                  key_pages=keys // KEYS_PER_PAGE, value_pages=keys * 0,
                  alpha=0.99, read_ratio=0.5, n_index_pages=2, keys=keys,
                  scan_lens=np.array([1, 1, 1, 1, 1, 10], np.int32))
    loaded = [(k + 1) * chip_smoke.PHI64 % 2**64 | 1 for k in (3, 5)]
    exp = chip_smoke.expected_reads(wl)
    assert [int(exp[i]) for i in (0, 2, 4)] == [loaded[0], loaded[1],
                                                3 * 2 + 1]
    assert chip_smoke.expected_scan_counts(wl, 10_000)[5] == 2


def test_refuses_without_tpu(capsys):
    assert chip_smoke.main() == 1
    assert capsys.readouterr().out == ""
