"""Bytes a kernel launch needs, and its share of the chip's roofline.

A SiM match kernel does a handful of integer operations per stored word,
so it is bound by memory bandwidth: its least time is the bytes it must
move over the chip's HBM bandwidth.  The bytes counted are the work the
commands require, not what the launch happened to move:

* every distinct page the launch reads, once, at 4 KiB;
* each command's operands in (a 64-bit query and a 64-bit mask);
* each command's 64 B result bitmap out.

Padding rows, duplicate rows and operand copies are waste, not work, so
removing them raises the share and can never push it past 100%.
"""
from __future__ import annotations

import json
import os

import numpy as np

PAGE_BYTES = 4096
OPERAND_BYTES = 16          # query + mask, 8 B each
RESULT_BYTES = 64           # one 512-bit slot bitmap

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")


def peaks(device_kind: str, path: str = PEAKS_FILE) -> dict:
    """The published peaks of ``device_kind``; a device not in the table
    is an error, never a default."""
    with open(path) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def lookup_bytes(key_pages, value_pages) -> int:
    """One fused lookup launch: each lookup searches its key page and
    reads its value page."""
    n = len(key_pages)
    pages = len(np.union1d(np.unique(key_pages), np.unique(value_pages)))
    return pages * PAGE_BYTES + n * (OPERAND_BYTES + RESULT_BYTES)


def plan_bytes(n_pages: int, n_passes: int) -> int:
    """One range-plan launch over ``n_pages`` distinct pages with one plan
    of ``n_passes`` masked-equality passes: the passes go in once, one
    combined bitmap per page comes out."""
    return n_pages * (PAGE_BYTES + RESULT_BYTES) + n_passes * OPERAND_BYTES


def exact_range_passes(lo, hi):
    """Passes of the exact prefix decomposition of ``[lo, hi)`` (paper
    §V-C): at each step the largest power-of-two block that is aligned at
    the cursor and fits.  ``lo >= 1``; scalars or arrays."""
    cur = np.array(lo, np.int64, ndmin=1)
    hi = np.broadcast_to(np.asarray(hi, np.int64), cur.shape)
    n = np.zeros(cur.shape, np.int64)
    while True:
        rem = hi - cur
        active = rem > 0
        if not active.any():
            break
        fit = np.where(active, 2 ** np.floor(np.log2(np.maximum(rem, 1))),
                       0).astype(np.int64)
        cur = cur + np.minimum(cur & -cur, fit)
        n += active
    return int(n[0]) if np.ndim(lo) == 0 else n


def share_percent(required_bytes: float, seconds: float,
                  bytes_per_s: float) -> float | None:
    """Least time over measured time, in percent; None without a time."""
    if seconds <= 0:
        return None
    return 100.0 * required_bytes / bytes_per_s / seconds
