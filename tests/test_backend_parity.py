"""Cross-backend parity: the sharded Pallas backend must be bit-identical
to the scalar SimChip reference on every programmed page — packed search
bitmaps, match counts, gather chunk bytes/ids/parities — including the
randomized=True in-kernel stream regeneration across chips with different
device seeds, and end-to-end through the index and workload layers.
"""
import numpy as np
import pytest

from repro.backend import ScalarBackend, ShardedSsdBackend, make_backend
from repro.core.bits import chunk_bitmap_from_slot_bitmap, pair_to_u64
from repro.core.commands import Command
from repro.core.engine import SimChipArray
from repro.core.page import mask_header_slots
from repro.core.range_query import evaluate_plan_on_pages, exact_range
from repro.index.btree import SimBTree
from repro.index.hashindex import SimHashIndex
from repro.frontend import RunConfig, replay
from repro.workload.ycsb import generate

N_PAGES = 12
ENTRIES_PER_PAGE = 300


def _programmed_pair(seed=7):
    """Two identically-programmed chip arrays (one per backend)."""
    arrays = []
    rng = np.random.default_rng(seed)
    page_keys = [rng.integers(1, 2**62, ENTRIES_PER_PAGE, dtype=np.uint64)
                 for _ in range(N_PAGES)]
    for _ in range(2):
        # several chips -> staged pages span different device seeds, so the
        # per-page seed operand of the search kernel is really exercised
        arr = SimChipArray(n_chips=5, pages_per_chip=8, device_seed=31)
        for p, keys in enumerate(page_keys):
            arr.program_entries(p, keys)
        arrays.append(arr)
    return arrays[0], arrays[1], page_keys


@pytest.fixture(scope="module")
def backends():
    arr_s, arr_b, page_keys = _programmed_pair()
    return ScalarBackend(arr_s), ShardedSsdBackend(arr_b), page_keys


def test_search_bitmaps_bit_identical(backends):
    sb, bb, page_keys = backends
    rng = np.random.default_rng(1)
    cmds = []
    for _ in range(48):
        p = int(rng.integers(0, N_PAGES))
        if rng.random() < 0.5:                      # planted hit
            q = int(page_keys[p][rng.integers(0, ENTRIES_PER_PAGE)])
            mask = 0xFFFFFFFFFFFFFFFF
        else:                                       # masked / miss
            q = int(rng.integers(1, 2**62))
            mask = int(rng.integers(0, 2**64, dtype=np.uint64))
        cmds.append(Command.search(p, q, mask))
    cmds.append(Command.search(0, 0, 0))            # §V-D match-all

    ts = [sb.submit_search(c) for c in cmds]
    tb = [bb.submit_search(c) for c in cmds]
    sb.flush()
    bb.flush()
    for a, b in zip(ts, tb):
        ra, rb = a.result(), b.result()
        np.testing.assert_array_equal(ra.bitmap_words, rb.bitmap_words)
        assert ra.match_count == rb.match_count


def test_gather_chunks_ids_parity_bit_identical(backends):
    sb, bb, page_keys = backends
    rng = np.random.default_rng(2)
    cmds = []
    for p in range(N_PAGES):
        # random multi-chunk bitmaps, plus the empty and full selections
        cmds.append(Command.gather(p, int(rng.integers(0, 2**64,
                                                       dtype=np.uint64))))
    cmds.append(Command.gather(0, 0))
    cmds.append(Command.gather(1, 0xFFFFFFFFFFFFFFFF))

    ts = [sb.submit_gather(c) for c in cmds]
    tb = [bb.submit_gather(c) for c in cmds]
    sb.flush()
    bb.flush()
    for a, b in zip(ts, tb):
        ra, rb = a.result(), b.result()
        np.testing.assert_array_equal(ra.chunks, rb.chunks)
        np.testing.assert_array_equal(ra.chunk_ids, rb.chunk_ids)
        np.testing.assert_array_equal(ra.parity_ok, rb.parity_ok)
        assert ra.parity_ok.all()                   # clean pages


def test_search_then_gather_pipeline(backends):
    """The Fig 8 point-lookup command sequence end to end on both."""
    sb, bb, page_keys = backends
    p = 3
    q = int(page_keys[p][17])
    for be in (sb, bb):
        resp = be.search(Command.search(p, q))
        bitmap = mask_header_slots(resp.bitmap_words)
        cb = int(pair_to_u64(*chunk_bitmap_from_slot_bitmap(bitmap)))
        g = be.gather(Command.gather(p, cb))
        assert g.parity_ok.all()
    ga = sb.gather(Command.gather(p, 0b1010))
    gb = bb.gather(Command.gather(p, 0b1010))
    np.testing.assert_array_equal(ga.chunks, gb.chunks)


def test_ticket_result_autoflushes(backends):
    sb, bb, page_keys = backends
    t = bb.submit_search(Command.search(0, int(page_keys[0][0])))
    assert not t.done and bb.pending == 1
    resp = t.result()                               # implicit flush
    assert t.done and bb.pending == 0
    ref = sb.search(Command.search(0, int(page_keys[0][0])))
    np.testing.assert_array_equal(resp.bitmap_words, ref.bitmap_words)


def test_range_plan_parity(backends):
    sb, bb, page_keys = backends
    lo = int(np.percentile(page_keys[0], 30))
    hi = int(np.percentile(page_keys[0], 60))
    plan = exact_range(lo, hi, width=64)
    pages = list(range(N_PAGES))
    out_s = evaluate_plan_on_pages(sb, plan, pages)
    out_b = evaluate_plan_on_pages(bb, plan, pages)
    np.testing.assert_array_equal(out_s, out_b)
    assert bb.stats.kernel_launches > 0


def test_launch_amortization(backends):
    """A burst of searches over shared pages is one launch (§IV-E)."""
    _, bb, page_keys = backends
    before = bb.stats.kernel_launches
    tickets = [bb.submit_search(Command.search(p, int(page_keys[p][i])))
               for p in range(N_PAGES) for i in range(4)]
    bb.flush()
    assert bb.stats.kernel_launches == before + 1
    assert all(t.done for t in tickets)


def _index_dataset():
    rng = np.random.default_rng(5)
    keys = (rng.choice(10**9, size=1200, replace=False) + 1).astype(np.uint64)
    return keys, keys * np.uint64(13)


@pytest.mark.parametrize("backend_name", ["scalar", "sharded"])
def test_btree_results_identical_on_both_backends(backend_name):
    keys, values = _index_dataset()
    be = make_backend(backend_name,
                      SimChipArray(n_chips=8, pages_per_chip=64))
    bt = SimBTree(be)
    bt.bulk_load(keys, values)
    probes = [int(k) for k in keys[::97]] + [int(keys[0]) + 1]
    got = bt.lookup_batch(probes)
    want = [int(k) * 13 if k in set(keys.tolist()) else None for k in probes]
    assert got == want
    lo, hi = int(np.percentile(keys, 45)), int(np.percentile(keys, 50))
    expect = sorted((int(k), int(k) * 13) for k in keys
                    if lo <= int(k) < hi)
    assert sorted(bt.range_query(lo, hi)) == expect


def test_hash_index_parity():
    keys, values = _index_dataset()
    results = []
    for name in ("scalar", "sharded"):
        h = SimHashIndex(make_backend(
            name, SimChipArray(n_chips=8, pages_per_chip=512)))
        for k, v in zip(keys[:800], values[:800]):
            h.insert(int(k), int(v))
        results.append(h.lookup_batch([int(k) for k in keys[:800:23]]
                                      + [10**15 + 3]))
    assert results[0] == results[1]
    assert results[0][-1] is None
    assert results[0][0] == int(keys[0]) * 13


def test_fused_lookup_parity_vs_scalar_split(backends):
    """The fused single-launch lookup path must be bit-identical to the
    scalar reference's split search+gather — bitmap, slot, value bytes and
    inner-code verdict — including misses and multi-match pages."""
    sb, bb, page_keys = backends
    rng = np.random.default_rng(4)
    cmds = []
    for _ in range(24):
        kp = int(rng.integers(0, N_PAGES // 2))
        vp = kp + N_PAGES // 2
        if rng.random() < 0.7:                      # planted hit
            q = int(page_keys[kp][rng.integers(0, ENTRIES_PER_PAGE)])
        else:                                       # miss
            q = int(rng.integers(2**62, 2**63))
        cmds.append(Command.lookup(kp, vp, q))

    ts = [sb.submit_lookup(c) for c in cmds]
    tb = [bb.submit_lookup(c) for c in cmds]
    launches = bb.stats.kernel_launches
    sb.flush()
    bb.flush()
    assert bb.stats.kernel_launches == launches + 1   # whole burst, 1 launch
    saw_hit = saw_miss = False
    for _c, a, b in zip(cmds, ts, tb):
        ra, rb = a.result(), b.result()
        np.testing.assert_array_equal(ra.search.bitmap_words,
                                      rb.search.bitmap_words)
        assert ra.search.match_count == rb.search.match_count
        assert ra.value_slot == rb.value_slot
        assert ra.value == rb.value
        assert ra.parity_ok == rb.parity_ok
        saw_hit |= ra.value_slot is not None
        saw_miss |= ra.value_slot is None
    assert saw_hit and saw_miss

    # The fused lookup must also agree with an explicit split decomposition.
    c = cmds[0]
    resp = bb.lookup(c)
    s = bb.search(Command.search(c.page_addr, pair_to_u64(*c.query)))
    np.testing.assert_array_equal(resp.search.bitmap_words, s.bitmap_words)
    if resp.value_slot is not None:
        g = bb.gather(Command.gather(
            c.value_page, 1 << (resp.value_slot // 8)))
        off = (resp.value_slot % 8) * 8
        assert resp.value == bytes(g.chunks[0][off:off + 8])


def test_planestore_invalidation_on_reprogram():
    """program -> search -> reprogram same page -> search must reflect the
    new image on both backends, and the sharded backend must restage only
    the dirty row (4 KiB), nothing else."""
    rng = np.random.default_rng(9)
    keys_a = rng.integers(1, 2**62, 100, dtype=np.uint64)
    keys_b = rng.integers(1, 2**62, 100, dtype=np.uint64)
    arrays = [SimChipArray(n_chips=3, pages_per_chip=8, device_seed=17)
              for _ in range(2)]
    backends_ = [ScalarBackend(arrays[0]), ShardedSsdBackend(arrays[1])]
    for arr in arrays:
        for p in range(6):
            arr.program_entries(p, keys_a)

    probe = Command.search(2, int(keys_b[7]))       # only in the NEW image
    first = [be.search(probe) for be in backends_]
    np.testing.assert_array_equal(first[0].bitmap_words,
                                  first[1].bitmap_words)
    assert first[0].match_count == 0

    bb = backends_[1]
    warm = bb.stats.staged_bytes
    for arr in arrays:
        arr.program_entries(2, keys_b)              # dirties one arena row
    second = [be.search(probe) for be in backends_]
    np.testing.assert_array_equal(second[0].bitmap_words,
                                  second[1].bitmap_words)
    assert second[0].match_count == 1
    assert bb.stats.staged_bytes - warm == 4096     # exactly the dirty row

    # ...and further searches of the (clean, resident) page restage nothing.
    warm = bb.stats.staged_bytes
    resp = bb.search(Command.search(2, int(keys_a[0])))   # old key: miss now
    assert resp.match_count == 0
    assert bb.stats.staged_bytes == warm


def test_planestore_zero_restage_after_warmup(backends):
    """Steady-state flushes of a warm working set ship zero page bytes —
    only query operands cross host->device (the §III-B in-array analogue)."""
    _, bb, page_keys = backends
    cmds = [Command.search(p, int(page_keys[p][3])) for p in range(N_PAGES)]
    for c in cmds:
        bb.submit_search(c)
    bb.flush()                                      # warm the arena
    for _ in range(3):
        before = bb.stats.staged_bytes
        for c in cmds:
            bb.submit_search(c)
        bb.flush()
        assert bb.stats.staged_bytes == before


def test_ycsb_run_functional_fused_identical():
    """Fused replay: bit-identical read values on every backend x mode, and
    the fused burst is ONE kernel launch (vs 2 on the split path)."""
    wl = generate(300, n_key_pages=6, read_ratio=0.8, alpha=0.5, seed=11)
    outs = {}
    for name, fused in (("scalar", False), ("scalar", True),
                        ("sharded", False), ("sharded", True)):
        arr = SimChipArray(n_chips=4, pages_per_chip=16, device_seed=3)
        outs[(name, fused)] = replay(wl, make_backend(name, arr),
                                     RunConfig(burst=32, fused=fused))
    ref = outs[("scalar", False)]
    for r in outs.values():
        np.testing.assert_array_equal(ref.read_values, r.read_values)
        np.testing.assert_array_equal(ref.read_hits, r.read_hits)
    split, fused = outs[("sharded", False)], outs[("sharded", True)]
    assert fused.kernel_launches == fused.flushes          # 1 launch/burst
    assert split.kernel_launches == 2 * fused.kernel_launches
    assert fused.staged_bytes > 0 and ref.staged_bytes == 0


def test_ycsb_run_functional_identical():
    """Full workload replay: identical read values on both backends, and
    the sharded backend actually batches (2 launches per read burst)."""
    wl = generate(300, n_key_pages=6, read_ratio=0.8, alpha=0.5, seed=11)
    outs = {}
    for name in ("scalar", "sharded"):
        arr = SimChipArray(n_chips=4, pages_per_chip=16, device_seed=3)
        outs[name] = replay(wl, make_backend(name, arr),
                            RunConfig(burst=32))
    np.testing.assert_array_equal(outs["scalar"].read_values,
                                  outs["sharded"].read_values)
    np.testing.assert_array_equal(outs["scalar"].read_hits,
                                  outs["sharded"].read_hits)
    assert outs["scalar"].read_hits[wl.ops == 0].all()
    assert outs["scalar"].kernel_launches == 0
    assert outs["sharded"].kernel_launches > 0
    assert outs["sharded"].kernel_launches <= outs["sharded"].flushes
