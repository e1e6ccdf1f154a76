"""The plane store's staging: one donated ``scatter_planes`` per slab.

``PlaneStore._stage`` writes new and dirty rows into the arena through one
compiled scatter over all four planes, with the arena donated.  A group of
at most ``SLAB`` rows is padded to ``padded_rows`` by repeating its last
row; a larger group goes in slabs of ``SLAB`` rows.  Whatever the entry
point (``stage_group`` or ``rows_for``) and group size, the arena must
equal a plain numpy image of the chips' stored pages, pads must never
count as staged, rows outside the group must not move, and the scatter
must compile once per padded shape.
"""
import numpy as np
import pytest

from repro.backend import planestore
from repro.backend.planestore import PlaneStore, padded_rows, scatter_planes
from repro.core.bits import PAGE_BYTES
from repro.core.engine import SimChipArray

N_CHIPS, PAGES_PER_CHIP = 6, 48
ENTRIES_PER_PAGE = 96
SLAB = 64                                    # lowered from 4,096
SIZES = (1, 16, 33, SLAB + 1)


@pytest.fixture
def chips():
    """Every page of a six-chip array programmed (distinct ids and seeds
    per chip)."""
    rng = np.random.default_rng(19)
    arr = SimChipArray(n_chips=N_CHIPS, pages_per_chip=PAGES_PER_CHIP,
                       device_seed=41)
    for p in range(N_CHIPS * PAGES_PER_CHIP):
        arr.program_entries(p, _entries(rng))
    return arr


@pytest.fixture(autouse=True)
def small_slab(monkeypatch):
    monkeypatch.setattr(planestore, "SLAB", SLAB)


def _entries(rng):
    return rng.integers(1, 2**62, ENTRIES_PER_PAGE, dtype=np.uint64)


def _arena(store):
    return (np.asarray(store._lo), np.asarray(store._hi),
            np.asarray(store._ids), np.asarray(store._seeds))


def _reference(store, chips):
    """The arena as plain numpy: each resident page's stored bytes split
    into the low and high 32-bit words of its 8-byte slots, its chip-local
    address and its chip's seed; rows no page holds stay zero."""
    lo = np.zeros((store._cap, 512), np.uint32)
    hi = np.zeros_like(lo)
    ids = np.zeros((store._cap, 1), np.uint32)
    seeds = np.zeros_like(ids)
    for addr, row in store._row.items():
        chip, local = chips.route(addr)
        words = np.frombuffer(chip.pages[local].raw.tobytes(), "<u4")
        lo[row], hi[row] = words[0::2], words[1::2]
        ids[row] = local
        seeds[row] = chip.device_seed & 0xFFFFFFFF
    return lo, hi, ids, seeds


def _assert_arena(store, chips):
    for got, want in zip(_arena(store), _reference(store, chips)):
        np.testing.assert_array_equal(got, want)


def _stage(store, entry, addrs):
    """Stage ``addrs`` through ``entry``; returns the rows staged."""
    before = store.staged_rows
    if entry == "stage_group":
        store.stage_group(addrs)
    else:
        store.rows_for(addrs)
    return store.staged_rows - before


@pytest.mark.parametrize("size", SIZES)
@pytest.mark.parametrize("entry", ["stage_group", "rows_for"])
def test_stage_matches_numpy_reference(chips, entry, size):
    """Cold rows, then dirty rows mixed with clean ones through the other
    entry point, then a cold and a dirty group in one call: after each
    step the arena is the numpy image, and only the real rows count."""
    other = "rows_for" if entry == "stage_group" else "stage_group"
    rng = np.random.default_rng(size)
    store = PlaneStore(chips)
    pages = rng.permutation(N_CHIPS * PAGES_PER_CHIP)[:3 * size + 4]
    cold, fresh = [int(a) for a in pages[:size]], \
        [int(a) for a in pages[size:2 * size]]
    clean = [int(a) for a in pages[2 * size:]]

    assert _stage(store, entry, cold + cold[:1]) == size   # a repeat
    assert store.staged_bytes == size * PAGE_BYTES
    _assert_arena(store, chips)

    _stage(store, other, clean)
    for a in cold:
        chips.program_entries(a, _entries(rng))
    before = _arena(store)
    assert _stage(store, other, clean[:3] + cold) == size
    _assert_arena(store, chips)
    moved = {store._row[a] for a in cold}
    for got, was in zip(_arena(store), before):
        keep = [r for r in range(store._cap) if r not in moved]
        np.testing.assert_array_equal(got[keep], was[keep])

    for a in cold[::2]:
        chips.program_entries(a, _entries(rng))
    staged = store.staged_rows
    assert _stage(store, entry, fresh + cold) == size + len(cold[::2])
    assert store.staged_rows - staged == size + len(cold[::2])
    assert store.staged_bytes == store.staged_rows * PAGE_BYTES
    assert store.staged_rows == 3 * size + len(clean) + len(cold[::2])
    _assert_arena(store, chips)


def test_pads_never_count(chips):
    """A 5-row group launches a 32-row scatter (``padded_rows``) yet
    counts 5 rows and 5 pages of bytes; rows past the resident ones stay
    zero."""
    store = PlaneStore(chips)
    store.rows_for(list(range(5)))
    assert padded_rows(5, store.block) == 32
    assert (store.staged_rows, store.staged_bytes) == (5, 5 * PAGE_BYTES)
    assert store._cap == 32 and store.resident_rows == 5
    for plane in _arena(store):
        assert not plane[5:].any()
    _assert_arena(store, chips)


def test_one_compile_per_padded_shape(chips):
    """Groups of 1-32 rows share one padded shape, 33-64 the next; a
    group past ``SLAB`` reuses both: its full slab and its padded tail."""
    store = PlaneStore(chips)
    store.rows_for(list(range(100)))         # an arena of 128 rows
    scatter_planes.clear_cache()
    for sizes, compiled in (((1, 7, 32, 23), 1), ((33, 50, 64, SLAB + 5), 2)):
        for size in sizes:
            group = list(range(size))
            rng = np.random.default_rng(size)
            for a in group:
                chips.program_entries(a, _entries(rng))
            assert store.stage_group(group) == size
            assert scatter_planes._cache_size() == compiled
    _assert_arena(store, chips)


def test_stage_donates_the_old_arena(chips):
    """A restage that needs no growth writes in place: the old arena
    arrays are deleted, and ``take`` reads the new rows."""
    store = PlaneStore(chips)
    store.rows_for(list(range(40)))          # capacity 64, no growth below
    old = (store._lo, store._hi, store._ids, store._seeds)
    rng = np.random.default_rng(5)
    for a in (3, 17, 30):
        chips.program_entries(a, _entries(rng))
    rows = store.rows_for([3, 17, 30, 4])
    assert all(plane.is_deleted() for plane in old)
    lo, hi, ids, seeds = (np.asarray(x) for x in store.take(rows, 8))
    want = _reference(store, chips)
    np.testing.assert_array_equal(lo[:4], want[0][rows])
    np.testing.assert_array_equal(hi[:4], want[1][rows])
    np.testing.assert_array_equal(ids[:4], want[2][rows, 0])
    np.testing.assert_array_equal(seeds[:4], want[3][rows, 0])
