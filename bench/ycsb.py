"""The benchmark's YCSB stream generator, driven only by a traffic file.

A copy of the program's ``repro.workload.ycsb.generate`` arithmetic, kept
with the benchmark so that no change to the program can change the traffic
it is measured on.  Keys are drawn from a zipfian over key ranks, the ranks
scrambled across the keyspace by a seeded permutation; each op is a read,
an update or a scan by one uniform draw against the traffic's proportions
(scans carved from the top, as YCSB-E's ``scanproportion``), and scan
lengths are uniform in ``[1, max_scan_length]``.

Record ``k`` lives on key page ``k // 504`` with stored key ``k + 1``; its
value sits at the same entry of the paired value page (paper §V-A).
"""
from __future__ import annotations

import dataclasses

import numpy as np

KEYS_PER_PAGE = 504
OP_READ, OP_UPDATE, OP_SCAN = 0, 1, 2


def value_page_of(key_page, n_key_pages: int):
    """§V-A leaf placement: the value page of key page ``i`` sits in the
    second half of the address space, rotated by one."""
    return n_key_pages + (key_page + 1) % n_key_pages


def zipf_probs(n: int, constant: float) -> np.ndarray:
    if constant <= 0.0:
        return np.full(n, 1.0 / n)
    w = np.arange(1, n + 1, dtype=np.float64) ** (-constant)
    return w / w.sum()


@dataclasses.dataclass
class Stream:
    """One run's op stream, in the field layout the replay core reads."""
    ops: np.ndarray          # (N,) uint8: OP_READ, OP_UPDATE or OP_SCAN
    keys: np.ndarray         # (N,) int64 record ids
    key_pages: np.ndarray    # (N,) int32
    value_pages: np.ndarray  # (N,) int32
    scan_lens: np.ndarray    # (N,) int32, used where ops == OP_SCAN
    n_index_pages: int

    def set_op(self, i, op: int, key, scan_len=1) -> None:
        """Overwrite position(s) ``i`` with ``op`` on ``key``."""
        n_key_pages = self.n_index_pages // 2
        key = np.asarray(key, np.int64)
        self.ops[i] = op
        self.keys[i] = key
        self.key_pages[i] = key // KEYS_PER_PAGE
        self.value_pages[i] = value_page_of(key // KEYS_PER_PAGE, n_key_pages)
        self.scan_lens[i] = scan_len


def n_key_pages(records: int) -> int:
    return -(-records // KEYS_PER_PAGE)


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    """A generator for ``seed`` (any whole number) and a sub-stream id."""
    return np.random.default_rng([seed % (1 << 64), *stream])


def generate(n_ops: int, traffic: dict, *, records: int,
             seed: int) -> Stream:
    """``n_ops`` ops of the traffic mix over ``records`` records."""
    read = float(traffic["read_proportion"])
    update = float(traffic["update_proportion"])
    scan = float(traffic["scan_proportion"])
    if abs(read + update + scan - 1.0) > 1e-9:
        raise ValueError(f"proportions sum to {read + update + scan}, not 1")
    if traffic["request_distribution"] != "zipfian":
        raise ValueError("only the zipfian request distribution is built")
    pages = n_key_pages(records)
    n_keys = pages * KEYS_PER_PAGE
    rng = rng_for(seed, 0)
    ranks = rng.choice(n_keys, size=n_ops,
                       p=zipf_probs(n_keys, traffic["zipfian_constant"]))
    keys = rng.permutation(n_keys)[ranks] if traffic["scrambled"] else ranks
    r = rng.random(n_ops)
    ops = np.where(r < read, OP_READ, OP_UPDATE).astype(np.uint8)
    ops[r >= 1.0 - scan] = OP_SCAN
    scan_lens = rng.integers(1, traffic["max_scan_length"] + 1, n_ops,
                             dtype=np.int32)
    key_pages = (keys // KEYS_PER_PAGE).astype(np.int32)
    return Stream(ops=ops, keys=keys.astype(np.int64), key_pages=key_pages,
                  value_pages=value_page_of(key_pages, pages).astype(np.int32),
                  scan_lens=scan_lens, n_index_pages=2 * pages)
