"""Write path: the coalescing DRAM write buffer, the deferred Op.PROGRAM
group path, and the timing executor's scan-op accounting.

Contracts held here:

  * ``WriteBuffer`` semantics — last-wins coalescing, read-your-writes
    overlay, high-water trip, one deferred program per dirty page per flush;
  * ``MatchBackend.submit_program`` — per-page last-wins coalescing inside
    a burst, programs execute before the burst's other commands, grouped
    plane-store staging ships each programmed row exactly once;
  * buffered ``replay`` — bit-identical ``read_values``/
    ``read_hits`` to the eager unbuffered scalar reference across scalar /
    sharded x split / fused, with ``programs < n_writes`` on the
    skewed YCSB-A stream (hot-page coalescing) and overlay reads counted;
  * the timing executor ``run()`` — YCSB-E scans are match-mode multi-page
    READS: a scan-bearing workload issues zero writes and zero programs
    (they used to fall into the write branch).
"""
import numpy as np
import pytest

from repro.backend import make_backend
from repro.backend.sharded import ShardedSsdBackend
from repro.buffer.writebuffer import WriteBuffer
from repro.core.commands import Command
from repro.core.engine import SimChipArray
from repro.flash.params import DEFAULT_PARAMS, PAGE_BYTES
from repro.frontend import RunConfig, replay
from repro.workload.runner import run
from repro.workload.ycsb import (KEYS_PER_PAGE, Workload, generate,
                                 value_page_of)


# --------------------------------------------------------------------------
# WriteBuffer unit semantics
# --------------------------------------------------------------------------

def test_writebuffer_coalesces_and_overlays():
    wb = WriteBuffer(high_water=4)
    a = np.arange(1, 11, dtype=np.uint64)
    b = a * np.uint64(3)
    wb.put(7, a)
    src = a.copy()
    a[:] = 0                              # callers may mutate their mirror
    np.testing.assert_array_equal(wb.get(7), src)
    wb.put(7, b)                          # coalesce: last image wins
    np.testing.assert_array_equal(wb.get(7), b)
    assert wb.get(8) is None              # clean pages served by the device
    assert wb.stats.writes == 2 and wb.stats.coalesced == 1
    assert wb.stats.read_hits == 2
    assert wb.n_dirty == 1 and not wb.should_flush
    wb.put(8, b)
    wb.put(9, b)
    wb.put(10, b)
    assert wb.should_flush and wb.stats.max_dirty == 4


def test_writebuffer_flush_is_one_program_group():
    arr = SimChipArray(n_chips=2, pages_per_chip=8)
    be = make_backend("scalar", arr)
    wb = WriteBuffer(high_water=8)
    img = np.arange(1, 101, dtype=np.uint64)
    for _ in range(5):                    # five writes, one page
        wb.put(3, img)
    wb.put(4, img * np.uint64(2))
    assert wb.flush(be) == 2              # two dirty pages -> two programs
    assert be.stats.programs == 2
    assert wb.n_dirty == 0 and wb.stats.flushes == 1
    assert wb.flush(be) == 0              # empty flush is free
    r = be.search(Command.search(3, int(img[6])))
    assert r.match_count == 1


def test_high_water_validation():
    with pytest.raises(ValueError):
        WriteBuffer(high_water=0)


# --------------------------------------------------------------------------
# Deferred Op.PROGRAM on the backends
# --------------------------------------------------------------------------

@pytest.mark.parametrize("name", ["scalar", "sharded"])
def test_submit_program_coalesces_last_wins(name):
    arr = SimChipArray(n_chips=2, pages_per_chip=8)
    be = make_backend(name, arr)
    keys = np.arange(1, 101, dtype=np.uint64)
    be.program_entries(0, keys)
    t1 = be.submit_program(0, keys * np.uint64(2))
    t2 = be.submit_program(0, keys * np.uint64(3))
    assert be.pending == 1                # coalesced before the chip
    be.flush()
    assert be.stats.programs == 1 and be.stats.programs_coalesced == 1
    assert t1.result() is t2.result()     # both resolve to the final image
    assert be.search(Command.search(0, 30)).match_count == 1   # 10*3
    assert be.search(Command.search(0, 20)).match_count == 0   # 10*2 gone


def test_programs_execute_before_flushed_searches():
    """A search flushed alongside a program of its page must match the NEW
    image — same ordering as the eager program_entries path."""
    for name in ("scalar", "sharded"):
        arr = SimChipArray(n_chips=2, pages_per_chip=8)
        be = make_backend(name, arr)
        keys = np.arange(1, 101, dtype=np.uint64)
        be.program_entries(0, keys)
        be.submit_program(0, keys + np.uint64(1000))
        t = be.submit_search(Command.search(0, 1005))
        be.flush()
        assert t.result().match_count == 1, name


def test_grouped_staging_ships_each_programmed_row_once():
    arr = SimChipArray(n_chips=4, pages_per_chip=8)
    be = make_backend("sharded", arr)
    keys = np.arange(1, 405, dtype=np.uint64)
    for p in range(6):
        be.program_entries(p, keys + np.uint64(p))
    for p in range(6):                    # warm the arena
        be.search(Command.search(p, int(keys[0]) + p))
    warm = be.stats.staged_bytes
    for p in range(4):                    # grouped reprogram of 4 pages
        be.submit_program(p, keys * np.uint64(2) + np.uint64(p))
    be.flush()
    assert be.stats.staged_bytes - warm == 4 * PAGE_BYTES
    # rows are current: the next burst re-ships NOTHING
    for p in range(6):
        q = int(keys[3]) * 2 + p if p < 4 else int(keys[3]) + p
        assert be.search(Command.search(p, q)).match_count == 1
    assert be.stats.staged_bytes - warm == 4 * PAGE_BYTES


def test_sharded_program_group_reports_to_timeline():
    be = ShardedSsdBackend.from_geometry(
        channels=2, dies_per_channel=2, pages_per_chip=8, timeline=True)
    keys = np.arange(1, 101, dtype=np.uint64)
    for p in range(4):
        be.program_entries(p, keys + np.uint64(p))
    for p in range(4):
        be.search(Command.search(p, int(keys[0]) + p))
    be.timeline.reset()
    prog_free_0 = be.timeline.sim.die_prog_free.copy()
    for p in range(4):
        be.submit_program(p, keys * np.uint64(5) + np.uint64(p))
    be.flush()
    # one write latency per program, programs queued on the die lines,
    # dirty restages charged to the storage-mode bus
    assert len(be.timeline.write_latencies) == 4
    assert (be.timeline.sim.die_prog_free > prog_free_0).all()
    assert be.timeline.sim.stats.programs == 4
    assert be.timeline.sim.stats.internal_bytes == 4 * PAGE_BYTES


# --------------------------------------------------------------------------
# Buffered replay: read-your-writes + parity + coalescing
# --------------------------------------------------------------------------

def _manual_workload(ops, keys, n_key_pages):
    ops = np.asarray(ops, dtype=np.uint8)
    keys = np.asarray(keys, dtype=np.int64)
    kp = (keys // KEYS_PER_PAGE).astype(np.int32)
    vp = value_page_of(kp, n_key_pages).astype(np.int32)
    return Workload(ops=ops, key_pages=kp, value_pages=vp, alpha=0.0,
                    read_ratio=0.5, n_index_pages=2 * n_key_pages,
                    keys=keys)


def test_read_your_writes_served_from_buffer():
    """read - write - read - write - read of one key inside one burst: the
    post-write reads come from the DRAM overlay (no device command) and
    still equal the eager reference bit for bit."""
    n_key_pages = 2
    wl = _manual_workload([0, 1, 0, 1, 0, 0],
                          [5, 5, 5, 5, 5, 900], n_key_pages)

    def mk(name):
        return make_backend(name, SimChipArray(n_chips=2, pages_per_chip=8,
                                               device_seed=3))

    ref = replay(wl, mk("scalar"), RunConfig(burst=64))
    for name in ("scalar", "sharded"):
        r = replay(wl, mk(name), RunConfig(
            burst=64, fused=(name == "sharded"), write_buffer=True))
        np.testing.assert_array_equal(ref.read_values, r.read_values)
        np.testing.assert_array_equal(ref.read_hits, r.read_hits)
        # reads 2 and 4 hit the dirty page in the buffer; key 900 lives on
        # the other (clean) page and goes to the device
        assert r.buffer_read_hits == 2
        # two writes to one hot page coalesce to ONE program at end drain
        assert r.n_writes == 2 and r.programs == 1 and r.write_flushes == 1
    assert ref.programs == ref.n_writes == 2   # eager path: 1 program/write


def test_high_water_groups_programs_mid_stream():
    n_key_pages = 8
    # 10 writes / 8 distinct pages, repeats inside one buffer window, with
    # high_water=4 -> two mid-stream group flushes + the end drain, and the
    # two same-window repeat writes coalesce away
    keys = [0, 3, 7 * KEYS_PER_PAGE, 7 * KEYS_PER_PAGE + 9] \
        + [p * KEYS_PER_PAGE for p in range(1, 7)]
    wl = _manual_workload([1] * 10, keys, n_key_pages)
    be = make_backend("sharded", SimChipArray(n_chips=2, pages_per_chip=16,
                                              device_seed=1))
    r = replay(wl, be, RunConfig.buffered(burst=64, write_high_water=4))
    assert r.write_flushes == 2
    assert r.programs == 10 - 2            # pages 0 and 7 written twice
    assert be.stats.programs == r.programs


@pytest.mark.parametrize("fused", [False, True])
def test_ycsb_a_buffered_parity_all_backends(fused):
    """YCSB-A (read_ratio=0.5, alpha=0.9): buffered replay is bit-identical
    to the eager unbuffered scalar reference on the scalar and sharded
    backends, with measurable hot-page coalescing."""
    wl = generate(400, n_key_pages=8, read_ratio=0.5, alpha=0.9, seed=11)
    pages_per_chip = max(wl.n_index_pages // 4 + 1, 8)

    def mk(name):
        if name == "sharded":
            return ShardedSsdBackend.from_geometry(
                channels=2, dies_per_channel=2,
                pages_per_chip=pages_per_chip, device_seed=3)
        return make_backend(name, SimChipArray(
            n_chips=4, pages_per_chip=pages_per_chip, device_seed=3))

    ref = replay(wl, mk("scalar"), RunConfig(burst=64))
    assert ref.programs == ref.n_writes
    for name in ("scalar", "sharded"):
        r = replay(wl, mk(name), RunConfig.buffered(
            burst=64, fused=fused, write_high_water=8))
        np.testing.assert_array_equal(ref.read_values, r.read_values)
        np.testing.assert_array_equal(ref.read_hits, r.read_hits)
        assert r.n_writes == ref.n_writes
        assert r.programs < r.n_writes, \
            f"{name}: no hot-page coalescing ({r.programs} programs)"
        assert r.buffer_read_hits > 0


def test_buffered_sharded_timeline_write_accounting():
    wl = generate(300, n_key_pages=8, read_ratio=0.5, alpha=0.9, seed=5)
    be = ShardedSsdBackend.from_geometry(
        channels=2, dies_per_channel=2,
        pages_per_chip=max(wl.n_index_pages // 4 + 1, 8),
        device_seed=3, timeline=True)
    r = replay(wl, be, RunConfig.buffered(burst=64, fused=True,
                                           write_high_water=4))
    assert r.programs < r.n_writes
    assert len(r.write_latencies_ns) == r.programs
    assert (r.write_latencies_ns > 0).all()
    assert r.sim_energy_pj > 0


def test_buffered_scan_workload_parity():
    """Scans + buffered writes in one stream stay bit-identical."""
    wl = generate(300, n_key_pages=8, read_ratio=0.5, alpha=0.5, seed=3,
                  scan_ratio=0.2)
    pages_per_chip = max(wl.n_index_pages // 4 + 1, 8)

    def mk(name):
        return make_backend(name, SimChipArray(
            n_chips=4, pages_per_chip=pages_per_chip, device_seed=3))

    ref = replay(wl, mk("scalar"), RunConfig(burst=64))
    r = replay(wl, mk("sharded"), RunConfig.buffered(
        burst=64, fused=True, write_high_water=8))
    np.testing.assert_array_equal(ref.read_values, r.read_values)
    np.testing.assert_array_equal(ref.scan_counts, r.scan_counts)
    assert r.n_scans == ref.n_scans > 0


# --------------------------------------------------------------------------
# Timing executor: scans are reads, not writes
# --------------------------------------------------------------------------

def test_run_scan_ops_issue_zero_programs():
    """ops == 2 used to fall into the write branch of run(): every scan
    was simulated as a page write.  A scan-bearing read/scan workload must
    issue ZERO writes and ZERO programs."""
    wl = generate(2000, n_key_pages=64, read_ratio=0.7, alpha=0.5, seed=2,
                  scan_ratio=0.3)
    assert int((wl.ops == 2).sum()) > 0 and int((wl.ops == 1).sum()) == 0
    for system in ("sim", "baseline"):
        r = run(wl, params=DEFAULT_PARAMS, system=system,
                cache_coverage=0.25)
        assert r.writes == 0, system
        assert r.programs == 0, system
        assert r.scans > 0, system


def test_run_scan_latency_not_in_write_path():
    """Scan latencies accumulate on their own distribution and scans/writes
    are counted separately when both appear in one stream."""
    wl = generate(2000, n_key_pages=64, read_ratio=0.5, alpha=0.5, seed=4,
                  scan_ratio=0.2)
    n_scan = int((wl.ops == 2).sum())
    n_write = int((wl.ops == 1).sum())
    assert n_scan > 0 and n_write > 0
    r = run(wl, params=DEFAULT_PARAMS, system="sim", cache_coverage=0.25)
    # post-warmup counts: scans + writes partition the non-read ops
    assert 0 < r.scans < n_scan + 1
    assert 0 < r.writes < n_write + 1
    assert r.scans + r.writes <= n_scan + n_write


# --------------------------------------------------------------------------

def test_flush_raises_on_unresolved_program_tickets():
    """SIM001 regression: flush() must verify every buffered page program
    resolved in THIS backend flush.  A backend that defers the program to a
    later burst would break read-your-writes once the overlay is clean."""

    class _StuckTicket:
        done = False

    class _DeferringBackend:
        def submit_program(self, page_addr, entries, **kw):
            return _StuckTicket()

        def flush(self):
            pass        # leaves the ticket unresolved

    buf = WriteBuffer(high_water=4)
    buf.put(3, np.arange(8, dtype=np.uint64))
    with pytest.raises(RuntimeError, match="unresolved"):
        buf.flush(_DeferringBackend())
    # the dirty set drained before the check: no double-program on retry
    assert buf.n_dirty == 0


def test_flush_counts_resolved_programs():
    chips = SimChipArray(n_chips=2, pages_per_chip=16, device_seed=5)
    backend = make_backend("sharded", chips, page_block=8)
    buf = WriteBuffer(high_water=4)
    buf.put(0, np.arange(8, dtype=np.uint64))
    buf.put(1, np.arange(8, 16, dtype=np.uint64))
    assert buf.flush(backend) == 2
    assert buf.stats.programs == 2 and buf.stats.flushes == 1
