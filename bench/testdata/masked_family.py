"""A minimal second family for the harness's tests: exact masked searches.

Each op is one (query, mask) matched against every page of a small table
of random 64-bit entries through ``ShardedSsdBackend``; its answer is the
number of entries that match, read from the result bitmaps and held to a
numpy count.  The tests copy this file to
``<root>/bench/families/masked.py`` under a temporary root, beside a
configuration and a traffic file of their own, and run it through the
unedited harness.
"""
from __future__ import annotations

import time

import numpy as np

from bench import roofline

KIND_LABELS = ((0, "search"),)
PROGRAM = "jit__stacked_search"
SLOTS = 504                       # user slots of a 4 KiB page
HEADER_SLOTS = 8                  # slots of chunk 0, the page header


class Deployment:
    """One run: the table and the ops from the seed, the table programmed
    and staged, then one search flush per op."""

    def __init__(self, cell, *, seed: int, seconds: float, interpret: bool,
                 trace: bool, marks: dict, prepare=None):
        from repro.backend.sharded import ShardedSsdBackend
        cfg, t = cell.config, cell.traffic
        rng = np.random.default_rng([seed % (1 << 64)])
        self.n_pages = n_pages = cfg["pages"]
        self.warmup_ops = t["warmup_ops"]
        n_ops = self.warmup_ops + int(t["stream_ops_per_s"] * seconds) + 1
        # Even entries: none is the all-ones vacant slot.
        self.entries = rng.integers(0, 1 << 63, (n_pages, SLOTS),
                                    dtype=np.uint64) << np.uint64(1)
        # Each query is a stored entry, so every op matches at least once.
        self.queries = self.entries[rng.integers(0, n_pages, n_ops),
                                    rng.integers(0, SLOTS, n_ops)]
        bits = np.argsort(rng.random((n_ops, 64)), axis=1)[:, :t["mask_bits"]]
        self.masks = np.bitwise_or.reduce(
            np.uint64(1) << bits.astype(np.uint64), axis=1)
        marks["generated"] = time.perf_counter()
        geo = cfg["geometry"]
        n_chips = geo["channels"] * geo["dies_per_channel"]
        self.backend = ShardedSsdBackend.from_geometry(
            channels=geo["channels"], dies_per_channel=geo["dies_per_channel"],
            pages_per_chip=-(-n_pages // n_chips), use_kernel=True,
            interpret=interpret)
        for p in range(n_pages):
            self.backend.program_entries(p, self.entries[p])
        marks["loaded"] = time.perf_counter()
        self.backend.store.stage_group(range(n_pages))
        marks["staged"] = time.perf_counter()
        if prepare is not None:
            prepare(self, self.backend)
        self.host = {"search": 0.0}
        self.t_in = np.full(n_ops, np.nan)
        self.t_out = np.full(n_ops, np.nan)
        self.counts = np.full(n_ops, -1, np.int64)

    def _search(self, i: int) -> None:
        from repro.core.commands import Command
        q, m = int(self.queries[i]), int(self.masks[i])
        tickets = [self.backend.submit_search(Command.search(p, q, m))
                   for p in range(self.n_pages)]
        self.backend.flush()
        # The chip matches every slot; the header chunk's slots 0..7 are
        # not entries, so their bits are left out.
        bits = np.unpackbits(np.stack([t.result().bitmap_words
                                       for t in tickets]).view(np.uint8),
                             axis=1, bitorder="little")
        self.counts[i] = int(bits[:, HEADER_SLOTS:].sum())

    def warm_up(self, log) -> int:
        for i in range(self.warmup_ops):
            self._search(i)
        self.w0 = self.warmup_ops
        return self.w0

    def window(self, deadline: float) -> float:
        clock, i = time.perf_counter, self.w0
        while True:
            if i >= len(self.queries):
                raise RuntimeError("the window ran past the generated ops")
            t = self.t_in[i] = clock()
            self._search(i)
            now = self.t_out[i] = clock()
            self.host["search"] += now - t
            i += 1
            if now >= deadline:
                self.end = i
                return now

    def check(self) -> tuple[dict, int]:
        want = np.array([
            int(((self.entries & m) == (q & m)).sum())
            for q, m in zip(self.queries[:self.end], self.masks[:self.end])])
        wrong = self.counts[:self.end] != want
        return ({"wrong_counts": (int(wrong.sum()), 0)},
                int(wrong[self.w0:].sum()))

    def window_ops(self) -> tuple[np.ndarray, np.ndarray]:
        window = slice(self.w0, self.end)
        return (np.zeros(self.end - self.w0, np.uint8),
                self.t_out[window] - self.t_in[window])

    def kernel_bytes(self) -> dict:
        """One search launch per op: every page read once, each command's
        operands in and bitmap out."""
        n = self.end - self.w0
        per_launch = self.n_pages * (roofline.PAGE_BYTES
                                     + roofline.OPERAND_BYTES
                                     + roofline.RESULT_BYTES)
        return {PROGRAM: (n, n * per_launch)}
