"""The plane store's operand gathers: one compiled program per flush.

``PlaneStore.take``/``take2d``/``take_lookup`` each run ONE jitted gather
over the resident arena.  Their outputs must be bit-identical to plain
numpy indexing of the arena planes (pad rows repeat row 0), every kernel
launch of the sharded backend must come with exactly one such gather
(``BackendStats.operand_programs``), and the gather's compile cache must
stay O(log max burst) across burst sizes.
"""
import math

import numpy as np
import pytest

from repro.backend import ShardedSsdBackend
from repro.backend import planestore
from repro.backend.planestore import PlaneStore
from repro.core.commands import Command
from repro.core.engine import SimChipArray

N_PAGES = 40
ENTRIES_PER_PAGE = 120


@pytest.fixture(scope="module")
def store():
    """A store over 40 programmed pages on five chips (distinct ids and
    seeds per row), all resident."""
    rng = np.random.default_rng(3)
    arr = SimChipArray(n_chips=5, pages_per_chip=8, device_seed=23)
    for p in range(N_PAGES):
        arr.program_entries(p, rng.integers(1, 2**62, ENTRIES_PER_PAGE,
                                            dtype=np.uint64))
    st = PlaneStore(arr)
    st.rows_for(list(range(N_PAGES)))
    return st


def _arena(store):
    return (np.asarray(store._lo), np.asarray(store._hi),
            np.asarray(store._ids), np.asarray(store._seeds))


def _padded(rows, pad_to):
    r = np.zeros(pad_to, np.int32)
    r[:len(rows)] = rows
    return r


def _expect(store, ridx):
    """Plain numpy indexing of the arena: what ``take``/``take2d`` return."""
    lo, hi, ids, seeds = _arena(store)
    return lo[ridx], hi[ridx], ids[ridx, 0], seeds[ridx, 0]


ROWS = np.array([7, 3, 31, 0, 12, 39, 12, 5, 26], np.int32)   # a repeat


@pytest.mark.parametrize("method,index,pad_to", [
    ("take", "1d", len(ROWS)),               # pad equal to the row count
    ("take", "1d", 16),                      # pad beyond it: row-0 rows
    ("take", "1d", 1),
    ("take2d", "1d", len(ROWS)),
    ("take2d", "2d", 4),                     # (C, R) with row-0 padding
    ("take2d", "2d", 8),
    ("take_lookup", "1d", len(ROWS)),
    ("take_lookup", "1d", 32),
])
def test_gather_bit_identical_to_numpy_indexing(store, method, index,
                                                pad_to):
    if method == "take":
        rows = ROWS[:pad_to]
        ridx = _padded(rows, pad_to)
        got = store.take(rows, pad_to)
        want = _expect(store, ridx)
        shapes = [(pad_to, 512), (pad_to, 512), (pad_to,), (pad_to,)]
    elif method == "take2d":
        if index == "1d":
            ridx = ROWS.copy()
        else:                                # chips x padded rows
            ridx = np.zeros((3, pad_to), np.int32)
            ridx[0, :3], ridx[1, :pad_to], ridx[2, :1] = \
                ROWS[:3], ROWS[:pad_to], ROWS[-1:]
        got = store.take2d(ridx)
        want = _expect(store, ridx)
        shapes = [ridx.shape + (512,)] * 2 + [ridx.shape] * 2
    else:
        k_rows, v_rows = ROWS, ROWS[::-1][:len(ROWS) - 2]
        got = store.take_lookup(k_rows, v_rows, pad_to)
        kidx, vidx = _padded(k_rows, pad_to), _padded(v_rows, pad_to)
        want = _expect(store, kidx) + _expect(store, vidx)[:2]
        shapes = [(pad_to, 512), (pad_to, 512), (pad_to,), (pad_to,),
                  (pad_to, 512), (pad_to, 512)]
    assert len(got) == len(want)
    for g, w, shape in zip(got, want, shapes):
        g = np.asarray(g)
        assert g.dtype == np.uint32 and g.shape == shape
        np.testing.assert_array_equal(g, w)


def test_one_operand_program_per_launch_and_bounded_cache():
    """Lookup, search and plan flushes of every burst size 1-64: each
    flush runs one compiled gather per kernel launch, and each gather
    entry point compiles at most log2(64) + 1 shapes."""
    rng = np.random.default_rng(11)
    be = ShardedSsdBackend.from_geometry(
        channels=2, dies_per_channel=2, pages_per_chip=32, device_seed=5,
        use_kernel=False)
    keys = [rng.integers(1, 2**62, ENTRIES_PER_PAGE, dtype=np.uint64)
            for _ in range(128)]
    for p, k in enumerate(keys):
        be.program_entries(p, k)
    be.store.stage_group(range(128))         # fixed arena capacity
    entries = (planestore._gather_operands,
               planestore._gather_lookup_operands)
    for fn in entries:
        fn.clear_cache()

    def flush_one(cmds, submit):
        tickets = [submit(c) for c in cmds]
        launches = be.stats.kernel_launches
        programs = be.stats.operand_programs
        be.flush()
        assert be.stats.kernel_launches - launches == 1
        assert be.stats.operand_programs - programs == 1
        return [t.result() for t in tickets]

    for n in range(1, 65):
        pages = rng.choice(64, n, replace=False)
        flush_one([Command.lookup(int(p), int(p) + 64,
                                  int(keys[p][rng.integers(0, 100)]))
                   for p in pages], be.submit_lookup)
        flush_one([Command.search(int(p), int(keys[p][0])) for p in pages],
                  be.submit_search)
        flush_one([Command.plan(int(p), [(int(keys[p][1]), 2**64 - 1)],
                                [(int(keys[p][2]), 2**64 - 16)])
                   for p in pages], be.submit_plan)
    bound = int(math.log2(64)) + 1
    for fn in entries:
        assert 0 < fn._cache_size() <= bound, fn.__name__
