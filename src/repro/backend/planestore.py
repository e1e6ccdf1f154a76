"""Device-resident page-plane store for the kernel backend (sharded.py).

The SiM chip's entire advantage is that stored pages never cross the bus —
only queries and 64 B bitmaps move (paper §III-B).  The TPU analogue: keep
every staged page's word planes *resident on the device* so a steady-state
flush ships only the (Q, 2) query operands, not 4 KiB per page per flush.

The store is a block-aligned arena of persistent JAX arrays:

    _lo, _hi    : (cap, 512) uint32   — the de-interleaved word planes
    _ids        : (cap, 1)   uint32   — chip-local flash address per row
    _seeds      : (cap, 1)   uint32   — device seed per row

Rows are assigned lazily the first time a flush references a page and are
re-staged *incrementally*: the store subscribes to the write path of its
``SimChipArray`` (``add_observer``), so a ``program_entries`` — or a bit-error
injection or ECC repair, anything that mutates the stored image — marks only
that page's row dirty.  The next flush that touches the page ships exactly
one 4 KiB row host->device; untouched pages ship zero bytes.  The arena
capacity grows by power-of-two blocks and existing rows are carried over
with a device-side copy, so growth never re-ships resident pages.

``staged_bytes``/``staged_rows`` count actual host->device page-plane
traffic; the kernel-micro benchmark asserts they stop growing once the
working set is warm (the zero-restage claim of the ROADMAP's hot-path
mandate).
"""
from __future__ import annotations

import weakref

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.bits import PAGE_BYTES, SLOTS_PER_PAGE
from repro.core.engine import SimChipArray
from repro.kernels.layout import pages_to_planes
from repro.trace import STAGE, span


def next_pow2(n: int) -> int:
    """Smallest power of two >= n (and >= 1)."""
    return 1 << max(n - 1, 0).bit_length() if n > 1 else 1


def padded_rows(n: int, block: int) -> int:
    """Pad a row count to a power-of-two multiple of ``block``.

    Both flush paths use this geometry so repeated bursts of *similar* (not
    identical) size reuse the same compiled kernel instead of retracing on
    every distinct burst size.
    """
    return block * next_pow2(-(-n // block))


def _rows(plane, ridx):
    """``plane[ridx]`` for row indices known to be in range: the gather
    drops the negative-index fix-up and the out-of-bounds clamp."""
    return plane.at[ridx].get(mode="promise_in_bounds",
                              wrap_negative_indices=False)


@jax.jit
def _gather_operands(lo, hi, ids, seeds, ridx):
    """Arena rows ``ridx`` (any index shape) of all four planes, as ONE
    compiled program: eager indexing would dispatch several small device
    programs per plane, and that host dispatch, not the bytes, is what an
    operand gather costs."""
    return (_rows(lo, ridx), _rows(hi, ridx),
            _rows(ids, ridx)[..., 0], _rows(seeds, ridx)[..., 0])


@jax.jit
def _gather_lookup_operands(lo, hi, ids, seeds, ridx):
    """A lookup burst's key rows ``ridx[0]`` (all four planes) and value
    rows ``ridx[1]`` (word planes only), as ONE compiled program."""
    return (_rows(lo, ridx[0]), _rows(hi, ridx[0]),
            _rows(ids, ridx[0])[..., 0], _rows(seeds, ridx[0])[..., 0],
            _rows(lo, ridx[1]), _rows(hi, ridx[1]))


class PlaneStore:
    """Arena of device-resident page planes, invalidated by the write path."""

    def __init__(self, chips: SimChipArray, *, block: int = 32,
                 log_staging: bool = False):
        self.chips = chips
        self.block = block
        self.log_staging = log_staging
        self._row: dict[int, int] = {}      # global page addr -> arena row
        self._addrs: list[int] = []         # arena row -> global page addr
        self._dirty: set[int] = set()
        self._cap = 0
        self._lo = self._hi = None          # (cap, 512) uint32
        self._ids = self._seeds = None      # (cap, 1) uint32
        self.staged_rows = 0                # rows shipped host->device, ever
        self.staged_bytes = 0               # page-plane bytes shipped, ever
        # With ``log_staging``: addresses whose *dirty* planes restaged
        # since the log was last drained — the sharded backend groups
        # these per chip to charge write-back bytes on the right
        # channel-bus timeline (see flash/timeline.py).  Cold first-touch
        # staging is deliberately not logged, and the log is off by
        # default so backends that never drain it don't accumulate it.
        self.staged_log: list[int] = []
        # Subscribe through a weakref so an abandoned store (and its device
        # arena) stays collectable — the chip array outlives backends.
        ref = weakref.ref(self)
        chips.add_observer(lambda addr, _r=ref: (
            _r()._on_write(addr) if _r() is not None else None))

    # ------------------------------------------------------------ bookkeeping
    @property
    def resident_rows(self) -> int:
        return len(self._addrs)

    def _on_write(self, page_addr: int) -> None:
        if page_addr in self._row:
            self._dirty.add(page_addr)

    def _grow(self, need: int) -> None:
        cap = max(self._cap, self.block)
        while cap < need:
            cap *= 2
        if cap == self._cap:
            return
        pad = ((0, cap - self._cap), (0, 0))
        if self._lo is None:
            self._lo = jnp.zeros((cap, SLOTS_PER_PAGE), jnp.uint32)
            self._hi = jnp.zeros((cap, SLOTS_PER_PAGE), jnp.uint32)
            self._ids = jnp.zeros((cap, 1), jnp.uint32)
            self._seeds = jnp.zeros((cap, 1), jnp.uint32)
        else:
            # Device-side copy: growth never re-ships resident pages.
            self._lo = jnp.pad(self._lo, pad)
            self._hi = jnp.pad(self._hi, pad)
            self._ids = jnp.pad(self._ids, pad)
            self._seeds = jnp.pad(self._seeds, pad)
        self._cap = cap

    # ---------------------------------------------------------------- staging
    def rows_for(self, page_addrs) -> np.ndarray:
        """Arena rows for global page addresses, staging new + dirty pages.

        Raises KeyError (via the chip model) on unprogrammed pages, like the
        per-flush staging it replaces.  Returns (len(page_addrs),) int32.
        """
        rows = np.empty(len(page_addrs), np.int32)
        stage: list[int] = []
        dirty_staged: list[int] = []
        queued = set()
        for i, a in enumerate(page_addrs):
            a = int(a)
            r = self._row.get(a)
            if r is None:
                chip, local = self.chips.route(a)
                chip._get(local)            # KeyError on unprogrammed
                r = len(self._addrs)
                self._row[a] = r
                self._addrs.append(a)
                if a not in queued:
                    stage.append(a)
                    queued.add(a)
            elif a in self._dirty and a not in queued:
                stage.append(a)
                dirty_staged.append(a)
                queued.add(a)
            rows[i] = r
        if len(self._addrs) > self._cap:
            self._grow(len(self._addrs))
        if stage:
            self._stage(stage)
            if self.log_staging:
                # Only *dirty* restages enter the log: cold first-touch
                # staging is arena population (a TPU-residency artifact),
                # not write-caused channel traffic (see flash/timeline.py).
                self.staged_log.extend(dirty_staged)
        return rows

    def stage_group(self, page_addrs) -> int:
        """Re-stage a group of just-programmed pages in ONE device update.

        The deferred write path (``MatchBackend.submit_program``) calls this
        right after its grouped chip programs: every listed page that is
        resident-and-dirty, or not yet resident, ships in a single
        ``_stage`` scatter — N programs cost one ``.at[idx].set`` per plane
        instead of N per-page invalidate-then-restage round trips through
        later ``rows_for`` calls.  Clean resident pages are skipped, and
        dirty restages enter ``staged_log``, both exactly as in
        ``rows_for`` — which does all the work here; this entry point only
        discards the row indices.  Returns the number of rows staged.
        """
        before = self.staged_rows
        self.rows_for([int(a) for a in page_addrs])
        return self.staged_rows - before

    def _stage(self, addrs: list[int]) -> None:
        """Ship the listed pages' planes host->device (the only page bytes
        that ever cross after warm-up: new rows and dirty rows)."""
        with span(STAGE, rows=len(addrs)):
            idx = jnp.asarray(np.array([self._row[a] for a in addrs],
                                       np.int32))
            raws, ids, seeds = [], [], []
            for a in addrs:
                chip, local = self.chips.route(a)
                raws.append(chip.pages[local].raw)
                ids.append(local)
                seeds.append(chip.device_seed & 0xFFFFFFFF)
            lo, hi = pages_to_planes(np.stack(raws))
            self._lo = self._lo.at[idx].set(jnp.asarray(lo))
            self._hi = self._hi.at[idx].set(jnp.asarray(hi))
            self._ids = self._ids.at[idx].set(
                jnp.asarray(np.asarray(ids, np.uint32)[:, None]))
            self._seeds = self._seeds.at[idx].set(
                jnp.asarray(np.asarray(seeds, np.uint32)[:, None]))
        self._dirty.difference_update(addrs)
        self.staged_rows += len(addrs)
        self.staged_bytes += len(addrs) * PAGE_BYTES

    # ----------------------------------------------------------------- access
    # Each access method is one call of a compiled gather with a host-built
    # index array, whose rows come from ``rows_for`` and so are in range.
    # Its compile cache follows the arena capacity and the padded index
    # shape, both powers of two.
    def take(self, rows: np.ndarray, pad_to: int):
        """Device-side row gather, padded to ``pad_to`` rows (repeats row 0).

        Returns (lo (P, 512), hi (P, 512), ids (P,), seeds (P,)) as device
        arrays — no page bytes cross the bus here, only the row indices.
        """
        r = np.zeros(pad_to, np.int32)
        r[:len(rows)] = rows
        return _gather_operands(self._lo, self._hi, self._ids, self._seeds, r)

    def take2d(self, rows: np.ndarray):
        """Row gather for a (C, R) index matrix, in one device program.

        Returns (lo (C, R, 512), hi (C, R, 512), ids (C, R), seeds (C, R)).
        This is how the sharded backend stacks every chip's operand rows
        for its single vmapped launch without a per-chip gather+stack
        cascade.
        """
        return _gather_operands(self._lo, self._hi, self._ids, self._seeds,
                                np.asarray(rows, np.int32))

    def take_lookup(self, key_rows: np.ndarray, value_rows: np.ndarray,
                    pad_to: int):
        """A lookup burst's key and value rows in one device program, each
        padded to ``pad_to`` rows (repeats row 0).

        Returns (klo, khi (P, 512), kids, kseeds (P,), vlo, vhi (P, 512)):
        what ``take`` returns for the key rows, and the word planes of the
        value rows.
        """
        r = np.zeros((2, pad_to), np.int32)
        r[0, :len(key_rows)] = key_rows
        r[1, :len(value_rows)] = value_rows
        return _gather_lookup_operands(self._lo, self._hi, self._ids,
                                       self._seeds, r)
