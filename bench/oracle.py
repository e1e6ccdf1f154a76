"""Plain reference answers for a replayed YCSB stream.

The data model is the benchmark's own statement of what was loaded: record
``k`` has stored key ``k + 1`` and initial value ``((k + 1) * PHI64) | 1``
(odd, so never the vacant-slot sentinel); an update at stream position
``q`` sets its record's value to the tag ``2 * q + 1``.  A read returns the
value of the last update to its record before it in stream order, or the
initial value; a scan of length ``L`` from record ``k`` counts the stored
keys in ``[k + 1, k + 1 + L)``.  Nothing here imports the program.
"""
from __future__ import annotations

import numpy as np

from bench.ycsb import KEYS_PER_PAGE, OP_READ, OP_SCAN, OP_UPDATE

PHI64 = 0x9E3779B97F4A7C15


def initial_values(keys) -> np.ndarray:
    k = np.asarray(keys).astype(np.uint64)
    return ((k + np.uint64(1)) * np.uint64(PHI64)) | np.uint64(1)


def expected_reads(ops, keys, positions) -> np.ndarray:
    """The value each op would read, in executed order.

    ``ops``, ``keys`` and ``positions`` (each op's stream position, which
    tags its update) list the executed ops in the order they ran.  Returns
    uint64 values, meaningful where ``ops == OP_READ``.
    """
    ops = np.asarray(ops)
    keys = np.asarray(keys, np.int64)
    positions = np.asarray(positions, np.int64)
    m = len(ops)
    exp = initial_values(keys)
    if not m:
        return exp
    order = np.lexsort((np.arange(m), keys))        # by key, then time
    k_sorted = keys[order]
    idx = np.arange(m)
    last_write = np.maximum.accumulate(
        np.where(ops[order] == OP_UPDATE, idx, -1))
    group_start = np.maximum.accumulate(
        np.where(np.r_[True, k_sorted[1:] != k_sorted[:-1]], idx, 0))
    seen = last_write >= group_start
    tags = positions[order[last_write[seen]]] * 2 + 1
    exp[order[seen]] = tags.astype(np.uint64)
    return exp


def expected_scan_counts(keys, scan_lens, n_keys: int) -> np.ndarray:
    """Stored keys (1..n_keys) inside each scan's [k + 1, k + 1 + len)."""
    lo = np.asarray(keys, np.int64) + 1
    hi = np.minimum(lo + np.asarray(scan_lens, np.int64), n_keys + 1)
    return np.maximum(hi - lo, 0)


def compare(ops, keys, scan_lens, positions, got_values, got_hits,
            got_counts, *, n_keys: int) -> dict:
    """Per-op verdicts over the executed ops: a read is wrong when its
    value or hit differs from the reference, a scan when its count does.
    Returns boolean arrays ``wrong_read`` and ``wrong_scan``."""
    ops = np.asarray(ops)
    is_read, is_scan = ops == OP_READ, ops == OP_SCAN
    exp = expected_reads(ops, keys, positions)
    wrong_read = is_read & ((np.asarray(got_values, np.uint64) != exp)
                            | ~np.asarray(got_hits, bool))
    counts = expected_scan_counts(keys, scan_lens, n_keys)
    wrong_scan = is_scan & (np.asarray(got_counts, np.int64) != counts)
    return {"wrong_read": wrong_read, "wrong_scan": wrong_scan}


def n_keys_of(n_key_pages: int) -> int:
    return n_key_pages * KEYS_PER_PAGE
