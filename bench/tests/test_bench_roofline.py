"""bench/roofline.py and bench/peaks.json: the bytes a launch requires,
and the table of peaks keyed by device kind."""
from __future__ import annotations

import numpy as np
import pytest

from bench import roofline


def test_peaks_of_v5e_and_unknown_kind_raises():
    p = roofline.peaks("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes"] == 16e9
    with pytest.raises(KeyError, match="no peaks"):
        roofline.peaks("TPU v9 imaginary")


def test_lookup_counts_each_distinct_page_once():
    # Four lookups, two of them on the same key and value page.
    key_pages = np.array([5, 5, 7, 9])
    value_pages = np.array([105, 105, 107, 109])
    expected = 6 * 4096 + 4 * (16 + 64)
    assert roofline.lookup_bytes(key_pages, value_pages) == expected
    # The same pages repeated cost nothing more than the operands.
    assert roofline.lookup_bytes(np.r_[key_pages, key_pages],
                                 np.r_[value_pages, value_pages]) \
        == expected + 4 * (16 + 64)


def test_plan_bytes():
    assert roofline.plan_bytes(2, 7) == 2 * (4096 + 64) + 7 * 16


@pytest.mark.parametrize("seed", [0, 1])
def test_exact_range_passes_match_the_program_plan(seed):
    from repro.core.range_query import exact_range
    rng = np.random.default_rng(seed)
    for lo in rng.integers(1, 1 << 40, 200).tolist():
        hi = lo + int(rng.integers(1, 300))
        assert roofline.exact_range_passes(lo, hi) == \
            exact_range(lo, hi).n_passes


def test_share_percent():
    assert roofline.share_percent(819e9, 2.0, 819e9) == 50.0
    assert roofline.share_percent(1.0, 0.0, 819e9) is None
