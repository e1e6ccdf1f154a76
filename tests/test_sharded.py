"""ShardedSsdBackend: addressing round-trips, cross-geometry bit-parity,
one-dispatch-per-burst, index wiring and timeline-coupled accounting.

The sharded backend owns channels x dies chips behind the MatchBackend
contract; stored-image randomization cancels between program and search,
so responses must be bit-identical across EVERY geometry — 1x1, 4x4 —
and against the scalar reference.
"""
import numpy as np
import pytest

from repro.backend import ScalarBackend, ShardedSsdBackend, make_backend
from repro.backend.sharded import compose, decompose
from repro.core.commands import Command
from repro.core.engine import SimChipArray
from repro.flash.timeline import BurstTimeline, ChipBurst
from repro.index.btree import SimBTree
from repro.index.hashindex import SimHashIndex
from repro.frontend import RunConfig, replay
from repro.workload.ycsb import generate

N_PAGES = 16
ENTRIES_PER_PAGE = 250


# ------------------------------------------------------------- addressing
def test_decompose_compose_roundtrip_sweep():
    """Any page set round-trips the (chip, local) decomposition."""
    for n_chips in (1, 2, 3, 5, 8, 16):
        for addr in range(0, 2000, 7):
            chip, local = decompose(addr, n_chips)
            assert 0 <= chip < n_chips
            assert compose(chip, local, n_chips) == addr
        # ...and every (chip, local) pair maps to a distinct address.
        seen = {compose(c, p, n_chips)
                for c in range(n_chips) for p in range(64)}
        assert len(seen) == n_chips * 64


def test_decompose_matches_simchiparray_route():
    """The sharded namespace and the chip array stripe identically, so
    stored images (which depend on local address + per-chip seed) agree."""
    arr = SimChipArray(n_chips=6, pages_per_chip=8, device_seed=3)
    for addr in range(40):
        chip, local = decompose(addr, 6)
        routed_chip, routed_local = arr.route(addr)
        assert routed_chip is arr.chips[chip]
        assert routed_local == local


def test_decompose_roundtrip_property():
    hypothesis = pytest.importorskip("hypothesis")
    given = hypothesis.given
    st = hypothesis.strategies

    @hypothesis.settings(max_examples=200, deadline=None)
    @given(st.integers(0, 2**40), st.integers(1, 1024))
    def roundtrip(addr, n_chips):
        chip, local = decompose(addr, n_chips)
        assert 0 <= chip < n_chips and local >= 0
        assert compose(chip, local, n_chips) == addr

    roundtrip()


def test_geometry_validation():
    arr = SimChipArray(n_chips=6, pages_per_chip=8)
    with pytest.raises(ValueError):
        ShardedSsdBackend(arr, channels=4, dies_per_channel=4)
    be = ShardedSsdBackend(arr, channels=3, dies_per_channel=2)
    assert (be.channels, be.dies_per_channel, be.n_chips) == (3, 2, 6)
    with pytest.raises(ValueError):
        ShardedSsdBackend(SimChipArray(n_chips=4, pages_per_chip=8),
                          timeline=BurstTimeline.for_chips(16))


# ----------------------------------------------------------------- parity
def _programmed(page_keys, make):
    be = make()
    for p, keys in enumerate(page_keys):
        be.program_entries(p, keys)
    return be


@pytest.fixture(scope="module")
def backends():
    """scalar (16-chip array layout) + sharded 1x1 and 4x4 — three
    backends over identically-keyed page sets."""
    rng = np.random.default_rng(7)
    page_keys = [rng.integers(1, 2**62, ENTRIES_PER_PAGE, dtype=np.uint64)
                 for _ in range(N_PAGES)]
    mk = {
        "scalar": lambda: ScalarBackend(
            SimChipArray(n_chips=16, pages_per_chip=8, device_seed=31)),
        "sharded1x1": lambda: ShardedSsdBackend.from_geometry(
            channels=1, pages_per_chip=N_PAGES, device_seed=31),
        "sharded4x4": lambda: ShardedSsdBackend.from_geometry(
            channels=4, dies_per_channel=4, pages_per_chip=8,
            device_seed=31),
    }
    return {k: _programmed(page_keys, m) for k, m in mk.items()}, page_keys


def test_search_bitmaps_bit_identical_across_geometries(backends):
    bes, page_keys = backends
    rng = np.random.default_rng(1)
    cmds = []
    for _ in range(40):
        p = int(rng.integers(0, N_PAGES))
        if rng.random() < 0.5:                      # planted hit
            q, mask = int(page_keys[p][rng.integers(
                0, ENTRIES_PER_PAGE)]), 0xFFFFFFFFFFFFFFFF
        else:                                       # masked / miss
            q = int(rng.integers(1, 2**62))
            mask = int(rng.integers(0, 2**64, dtype=np.uint64))
        cmds.append(Command.search(p, q, mask))
    cmds.append(Command.search(0, 0, 0))            # §V-D match-all

    results = {}
    for name, be in bes.items():
        ts = [be.submit_search(c) for c in cmds]
        before = be.stats.kernel_launches
        be.flush()
        if isinstance(be, ShardedSsdBackend):       # one dispatch per burst
            assert be.stats.kernel_launches == before + 1
        results[name] = [t.result() for t in ts]
    ref = results["scalar"]
    for got in results.values():
        for a, b in zip(ref, got):
            np.testing.assert_array_equal(a.bitmap_words, b.bitmap_words)
            assert a.match_count == b.match_count


def test_gathers_bit_identical_across_geometries(backends):
    bes, page_keys = backends
    rng = np.random.default_rng(2)
    cmds = [Command.gather(p, int(rng.integers(0, 2**64, dtype=np.uint64)))
            for p in range(N_PAGES)]
    cmds += [Command.gather(0, 0), Command.gather(1, 0xFFFFFFFFFFFFFFFF)]
    results = {name: [t.result() for t in
                      [be.submit_gather(c) for c in cmds]]
               for name, be in bes.items()}
    ref = results["scalar"]
    for got in results.values():
        for a, b in zip(ref, got):
            np.testing.assert_array_equal(a.chunks, b.chunks)
            np.testing.assert_array_equal(a.chunk_ids, b.chunk_ids)
            np.testing.assert_array_equal(a.parity_ok, b.parity_ok)


def test_lookups_bit_identical_across_geometries(backends):
    """Fused lookups whose key and value pages live on different chips."""
    bes, page_keys = backends
    rng = np.random.default_rng(4)
    cmds = []
    for _ in range(20):
        kp = int(rng.integers(0, N_PAGES // 2))
        vp = kp + N_PAGES // 2                      # different chip in 4x4
        q = int(page_keys[kp][rng.integers(0, ENTRIES_PER_PAGE)]) \
            if rng.random() < 0.7 else int(rng.integers(2**62, 2**63))
        cmds.append(Command.lookup(kp, vp, q))
    results = {}
    for name, be in bes.items():
        ts = [be.submit_lookup(c) for c in cmds]
        before = be.stats.kernel_launches
        be.flush()
        if isinstance(be, ShardedSsdBackend):
            assert be.stats.kernel_launches == before + 1
        results[name] = [t.result() for t in ts]
    ref = results["scalar"]
    misses = 0
    for got in results.values():
        for a, b in zip(ref, got):
            np.testing.assert_array_equal(a.search.bitmap_words,
                                          b.search.bitmap_words)
            assert a.value_slot == b.value_slot
            assert a.value == b.value
            assert a.parity_ok == b.parity_ok
            misses += a.value_slot is None
    assert misses and misses < len(cmds) * len(results)


def test_reprogram_invalidates_one_arena_row(backends):
    bes, page_keys = backends
    be = bes["sharded4x4"]
    be.search(Command.search(5, int(page_keys[5][0])))      # warm page 5
    warm = be.stats.staged_bytes
    new_keys = page_keys[5][::-1].copy()
    be.program_entries(5, new_keys)
    resp = be.search(Command.search(5, int(new_keys[3])))
    assert resp.match_count >= 1
    assert be.stats.staged_bytes - warm == 4096             # one dirty row


# ---------------------------------------------------------- index wiring
def test_btree_on_sharded_backend():
    rng = np.random.default_rng(5)
    keys = (rng.choice(10**9, size=900, replace=False) + 1).astype(np.uint64)
    values = keys * np.uint64(13)
    bt = SimBTree(ShardedSsdBackend.from_geometry(
        channels=4, dies_per_channel=2, pages_per_chip=32))
    bt.bulk_load(keys, values)
    # §V-A pairing: consecutive (key, value) pages stripe to distinct chips
    for leaf in bt.leaves:
        assert decompose(leaf.key_page, 8)[0] != \
            decompose(leaf.value_page, 8)[0]
    probes = [int(k) for k in keys[::83]] + [int(keys[0]) + 1]
    want = [int(k) * 13 if k in set(keys.tolist()) else None for k in probes]
    assert bt.lookup_batch(probes) == want
    lo, hi = int(np.percentile(keys, 40)), int(np.percentile(keys, 45))
    expect = sorted((int(k), int(k) * 13) for k in keys if lo <= int(k) < hi)
    assert sorted(bt.range_query(lo, hi)) == expect


def test_secondary_index_on_sharded_backend():
    """A full-table predicate scan: every chip matches its shard of the
    table inside one stacked launch, same rows as the scalar reference."""
    from repro.core.bitweaving import Column, RowCodec
    from repro.index.secondary import SimSecondaryIndex
    codec = RowCodec((Column("uid", 40), Column("age", 7),
                      Column("gender", 1)))
    rng = np.random.default_rng(8)
    rows = {"uid": rng.integers(0, 2**40, 1500, dtype=np.uint64),
            "age": rng.integers(0, 100, 1500, dtype=np.uint64),
            "gender": rng.integers(0, 2, 1500, dtype=np.uint64)}
    got = {}
    for name, make in (("scalar", lambda: make_backend(
            "scalar", SimChipArray(n_chips=8, pages_per_chip=8))),
            ("sharded", lambda: ShardedSsdBackend.from_geometry(
                channels=4, dies_per_channel=2, pages_per_chip=8))):
        idx = SimSecondaryIndex(make(), codec)
        idx.load_rows(rows)
        eq = idx.select_equals("gender", 1)
        rg = idx.select_range("age", 30, 40)
        got[name] = (np.sort(eq), np.sort(rg))
        if name == "sharded":
            assert idx.backend.stats.kernel_launches > 0
    np.testing.assert_array_equal(got["scalar"][0], got["sharded"][0])
    np.testing.assert_array_equal(got["scalar"][1], got["sharded"][1])
    want_age = np.sort(codec.encode_rows(rows)[
        (rows["age"] >= 30) & (rows["age"] < 40)])
    np.testing.assert_array_equal(got["sharded"][1], want_age)


def test_hash_index_on_sharded_backend():
    rng = np.random.default_rng(6)
    keys = (rng.choice(10**9, size=500, replace=False) + 1).astype(np.uint64)
    results = []
    for make in (lambda: make_backend(
            "scalar", SimChipArray(n_chips=8, pages_per_chip=512)),
            lambda: ShardedSsdBackend.from_geometry(
                channels=4, dies_per_channel=2, pages_per_chip=512)):
        h = SimHashIndex(make())
        for k in keys:
            h.insert(int(k), int(k) * 7)
        results.append(h.lookup_batch([int(k) for k in keys[::19]]
                                      + [10**15 + 3]))
    assert results[0] == results[1]
    assert results[0][-1] is None


# -------------------------------------------------------------- workloads
@pytest.fixture(scope="module")
def ycsb_replays():
    wl = generate(240, n_key_pages=6, read_ratio=0.8, alpha=0.5, seed=11)
    outs = {}
    for name, make in {
        "scalar": lambda: make_backend("scalar", SimChipArray(
            n_chips=4, pages_per_chip=16, device_seed=3)),
        "sharded1x1": lambda: ShardedSsdBackend.from_geometry(
            channels=1, pages_per_chip=64, device_seed=3, timeline=True),
        "sharded4x4": lambda: ShardedSsdBackend.from_geometry(
            channels=4, dies_per_channel=4, pages_per_chip=8,
            device_seed=3, timeline=True),
    }.items():
        for fused in (False, True):
            outs[(name, fused)] = replay(wl, make(),
                                         RunConfig(burst=32, fused=fused))
    return wl, outs


def test_ycsb_replay_bit_identical_across_geometries(ycsb_replays):
    """4-channel x 4-die replay == scalar reference, split and fused."""
    wl, outs = ycsb_replays
    ref = outs[("scalar", False)]
    assert ref.read_hits[wl.ops == 0].all()
    for r in outs.values():
        np.testing.assert_array_equal(ref.read_values, r.read_values)
        np.testing.assert_array_equal(ref.read_hits, r.read_hits)


def test_ycsb_fused_burst_is_one_dispatch(ycsb_replays):
    _, outs = ycsb_replays
    fused = outs[("sharded4x4", True)]
    assert fused.kernel_launches == fused.flushes    # 1 launch per burst
    split = outs[("sharded4x4", False)]
    assert split.kernel_launches == 2 * fused.kernel_launches


# --------------------------------------------------------------- timeline
def test_timeline_couples_functional_run(ycsb_replays):
    _, outs = ycsb_replays
    r = outs[("sharded4x4", True)]
    assert r.burst_latencies_ns is not None
    assert len(r.burst_latencies_ns) == r.flushes
    assert (r.burst_latencies_ns > 0).all()
    assert r.write_latencies_ns is not None and len(r.write_latencies_ns)
    assert r.sim_makespan_ns > 0 and r.sim_energy_pj > 0
    assert np.percentile(r.burst_latencies_ns, 99) >= \
        np.percentile(r.burst_latencies_ns, 50)


def test_timeline_die_channel_parallelism(ycsb_replays):
    """The same op stream finishes faster on 16 dies than on 1 — the
    channel/die overlap the paper's speedups come from (§VI-A)."""
    _, outs = ycsb_replays
    one = outs[("sharded1x1", True)]
    many = outs[("sharded4x4", True)]
    assert many.sim_makespan_ns < one.sim_makespan_ns
    assert np.median(many.burst_latencies_ns) < \
        np.median(one.burst_latencies_ns)


def test_timeline_charges_bus_writeback_only_for_dirty_planes():
    """Cold first-touch arena staging is a TPU artifact, not SSD channel
    traffic: a read-only replay must accrue zero storage-mode bus bytes,
    while a reprogram charges exactly one page's write-back crossing."""
    be = ShardedSsdBackend.from_geometry(
        channels=2, dies_per_channel=2, pages_per_chip=8, timeline=True)
    rng = np.random.default_rng(3)
    keys = [rng.integers(1, 2**62, 50, dtype=np.uint64) for _ in range(8)]
    for p, k in enumerate(keys):
        be.program_entries(p, k)
    be.timeline.reset()
    bus0 = be.timeline.sim.stats.internal_bytes
    for p in range(8):                      # cold first-touch searches
        be.search(Command.search(p, int(keys[p][0])))
    # all bus traffic so far is match-mode (opens + bitmaps): 320 B per
    # search, nowhere near the 4 KiB/page a restage charge would add
    match_only = be.timeline.sim.stats.internal_bytes - bus0
    assert match_only == 8 * (256 + 64)
    lat_before = list(be.timeline.burst_latencies)
    be.program_entries(3, keys[3][::-1].copy())         # dirty one plane
    be.search(Command.search(3, int(keys[3][-1])))
    assert len(be.timeline.burst_latencies) == len(lat_before) + 1
    # the dirty burst carries the 4 KiB storage-mode write-back crossing
    assert be.timeline.burst_latencies[-1] > np.median(lat_before)


def test_timeline_resource_accounting():
    """Flush reports drive SSDSim's timelines: senses/matches/bytes land
    on the right counters and chips on one channel serialize their bus."""
    tl = BurstTimeline.for_chips(4)
    lat_parallel = tl.observe_flush(
        [ChipBurst(c, senses=1, matches=2, bus_match_bytes=128,
                   pcie_bytes=64) for c in range(4)])
    assert tl.sim.stats.senses == 4 and tl.sim.stats.matches == 8
    tl2 = BurstTimeline(tl.params)
    lat_serial = tl2.observe_flush(
        [ChipBurst(0, senses=4, matches=8, bus_match_bytes=512,
                   pcie_bytes=256)])
    assert lat_serial > lat_parallel       # 4 dies overlap their senses
    before = tl.sim.stats.programs
    tl.observe_program(2)
    assert tl.sim.stats.programs == before + 1
    assert tl.write_latencies and tl.energy_pj > 0
