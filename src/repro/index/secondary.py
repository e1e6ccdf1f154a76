"""Secondary index with BitWeaving-encoded rows on SiM pages (§V-B/C).

Rows are packed into 8-byte keys by a RowCodec (column -> bit range).  A
column predicate becomes one masked search per page (point) or the §V-C
range plan (range), and ranges over several columns one exact conjunctive
plan (``select_where``); gather returns only the matching encoded rows,
from which the host decodes e.g. the user id.

Predicates execute through a MatchBackend: every page's plan command is
enqueued and flushed together, so a table scan is one batched launch (and
one follow-up gather launch) on the kernel backend instead of a per-page
command loop.  Range predicates ride ``Op.PLAN`` — the multi-pass §V-C
decomposition accumulates OR/AND-NOT in-latch (Fig 10) and only the
combined 64 B bitmap per page crosses the bus, independent of pass count.
Sequential page allocation stripes the table across a
``ShardedSsdBackend``'s channels x dies, so a full-table predicate is the
best case for the stacked launch: every chip matches its own shard of the
table in parallel within ONE device dispatch.
"""
from __future__ import annotations

import numpy as np

from repro import trace
from repro.backend import MatchBackend, as_backend
from repro.core.bits import (CHUNK_BYTES, CHUNKS_PER_PAGE, SLOTS_PER_CHUNK,
                             SLOTS_PER_PAGE)
from repro.core.bitweaving import RowCodec
from repro.core.commands import Command
from repro.core.page import mask_header_slots
from repro.core.range_query import RangePlan, evaluate_plan_on_pages
from repro.reliability import require_clean
from repro.trace import span

ROWS_PER_PAGE = 504


class IncompleteGatherError(RuntimeError):
    """A gather returned fewer chunks than its chunk bitmap selected, so
    matching rows cannot be read back."""


class SimSecondaryIndex:
    def __init__(self, backend, codec: RowCodec, *, first_page: int = 0):
        self.backend: MatchBackend = as_backend(backend)
        self.codec = codec
        self.first_page = first_page
        self.n_pages = 0
        self.n_rows = 0
        self._rows_in_page = np.zeros(0, dtype=np.int64)
        self.io_bitmap_bytes = 0
        self.io_chunk_bytes = 0

    @property
    def chips(self):
        return self.backend.chips

    def load_rows(self, rows: dict[str, np.ndarray]) -> None:
        keys = self.codec.encode_rows(rows)
        self.n_rows = len(keys)
        starts = np.arange(0, len(keys), ROWS_PER_PAGE)
        self._rows_in_page = np.minimum(ROWS_PER_PAGE, len(keys) - starts)
        for start in starts.tolist():
            page = self.first_page + self.n_pages
            self.backend.program_entries(page,
                                         keys[start:start + ROWS_PER_PAGE])
            self.n_pages += 1

    # ---------------------------------------------------------- predicates
    def _page_addrs(self) -> list[int]:
        return [self.first_page + p for p in range(self.n_pages)]

    def _collect_pages(self, bitmaps: np.ndarray) -> np.ndarray:
        """Gather matching rows of all pages -> encoded uint64 keys, in page
        and slot order.

        Slots past a page's row count are vacant (all-ones sentinel) and
        can alias masked predicates (e.g. any column test with all-set
        bits), so the host strips them — the same software-side
        responsibility as the header-chunk mask.  A page's gather still
        selects every chunk its header-masked bitmap hits.  The bitmaps
        are worked as one ``(pages, 512)`` array; only the gather
        commands are per page, all enqueued before one flush.  Raises
        IncompleteGatherError when a gather did not return exactly the
        chunks it selected.
        """
        with span(trace.SELECT_COLLECT):
            words = mask_header_slots(bitmaps).astype("<u4", copy=False)
            hits = np.unpackbits(words.view(np.uint8), axis=-1,
                                 bitorder="little").view(bool)  # (P, 512)
            chunks = hits.reshape(-1, CHUNKS_PER_PAGE,
                                  SLOTS_PER_CHUNK).any(axis=-1)  # (P, 64)
            hits &= (np.arange(SLOTS_PER_PAGE)
                     < SLOTS_PER_CHUNK + self._rows_in_page[:, None])
            hit_pages = np.flatnonzero(hits.any(axis=-1))
            hits, chunks = hits[hit_pages], chunks[hit_pages]
            chunk_bitmaps = np.packbits(chunks, axis=-1,
                                    bitorder="little").view("<u8")[:, 0]
            pages = (self.first_page + hit_pages).tolist()
            tickets = [self.backend.submit_gather(Command.gather(page, cb))
                       for page, cb in zip(pages, chunk_bitmaps.tolist())]
        self.backend.flush()

        with span(trace.SELECT_DECODE):
            got = [require_clean(t.result()) for t in tickets]
            n_got = np.array([g.chunk_ids.size for g in got], dtype=np.int64)
            self.io_chunk_bytes += CHUNK_BYTES * int(n_got.sum())
            if not got:
                return np.zeros(0, dtype=np.uint64)
            if not (np.array_equal(n_got, chunks.sum(axis=-1)) and
                    np.array_equal(np.concatenate([g.chunk_ids for g in got]),
                                   np.nonzero(chunks)[1])):
                for page, selected, g in zip(pages, chunks, got):
                    want = np.flatnonzero(selected)
                    if not np.array_equal(g.chunk_ids, want):
                        missing = sorted(set(want.tolist())
                                         - set(g.chunk_ids.tolist()))
                        raise IncompleteGatherError(
                            f"page {page}: chunks {missing} not gathered"
                            if missing else f"page {page}: chunks "
                            f"{g.chunk_ids.tolist()} gathered, "
                            f"{want.tolist()} selected")
            # Position of each selected chunk among all gathered chunks.
            pos = np.cumsum(chunks).reshape(chunks.shape) - 1
            slot_words = np.concatenate([g.chunks for g in got]).view("<u8")
            page_row, slot = np.nonzero(hits)
            return slot_words[pos[page_row, slot // SLOTS_PER_CHUNK],
                              slot % SLOTS_PER_CHUNK]

    def _select(self, plan: RangePlan) -> np.ndarray:
        """Rows the plan selects: one ``Op.PLAN`` per page in one flush (all
        passes accumulate in-latch and 64 B per page crosses the bus, no
        matter how many passes), then the matching rows gathered."""
        with span(trace.SELECT, pages=self.n_pages, passes=plan.n_passes):
            bitmaps = evaluate_plan_on_pages(self.backend, plan,
                                             self._page_addrs())
            self.io_bitmap_bytes += 64 * self.n_pages   # combined, pass-free
            return self._collect_pages(bitmaps)

    def select_equals(self, column: str, value: int) -> np.ndarray:
        """Fig 9: e.g. all rows with gender == female -> encoded rows."""
        return self._select(RangePlan(include=(self.codec.equals(column,
                                                                 value),)))

    def select_range(self, column: str, lo: int, hi: int, *,
                     exact: bool = True) -> np.ndarray:
        """Fig 10: lo <= column < hi via the masked-equality range plan.

        Exact, it is ``select_where`` on one column.  With ``exact=False``
        the one-pass-per-bound approximate plan is used and the (superset)
        result is refined on the host — the workflow the paper proposes
        for analytical scans.
        """
        if exact:
            return self.select_where({column: (lo, hi)})
        got = self._select(self.codec.range(column, lo, hi, exact=False))
        if got.size:
            vals = self.codec.decode_rows(got, column)
            got = got[(vals >= lo) & (vals < hi)]   # host-side refinement
        return got

    def select_where(self, predicates: dict[str, tuple[int, int]]
                     ) -> np.ndarray:
        """Rows where every ``lo <= column < hi`` of ``predicates`` holds,
        as encoded keys in page and slot order (e.g. TPC-H Q6's shipdate,
        discount and quantity ranges).

        The conjunction is one exact plan (``RowCodec.where``): one
        ``Op.PLAN`` per page in one flush, then one gather per page with a
        match in one flush, then the rows are read out of the gathered
        chunks.  Nothing is refined on the host.
        """
        return self._select(self.codec.where(predicates))
