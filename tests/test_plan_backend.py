"""Op.PLAN — fused in-latch range-plan execution — and the lazy result path.

The contract (ISSUE 4): PLAN results are bit-identical across the scalar
(per-pass split reference) and sharded backends AND identical to
the per-pass ``evaluate_plan_per_pass`` combine, for exact and approximate
plans; device->host result bytes drop by the plan's pass count; ticket
resolution is lazy (launch outputs stay on-device until the first
``result()``) without changing any observable value.
"""
import dataclasses

import numpy as np
import pytest

from repro.backend import ScalarBackend, ShardedSsdBackend, make_backend
from repro.core.commands import Command, Op
from repro.core.engine import SimChipArray
from repro.core.range_query import (MaskedQuery, RangePlan,
                                    approximate_range,
                                    evaluate_plan_on_pages,
                                    evaluate_plan_per_pass, exact_range)
from repro.frontend import RunConfig, replay
from repro.workload.ycsb import generate

N_PAGES = 12
ENTRIES_PER_PAGE = 300
KEY_SPAN = 2**48


def _page_keys(seed=7):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, KEY_SPAN, ENTRIES_PER_PAGE, dtype=np.uint64)
            for _ in range(N_PAGES)]


def _programmed(page_keys, make):
    be = make()
    for p, keys in enumerate(page_keys):
        be.program_entries(p, keys)
    return be


@pytest.fixture(scope="module")
def backends():
    page_keys = _page_keys()
    mk = {
        "scalar": lambda: ScalarBackend(
            SimChipArray(n_chips=4, pages_per_chip=8, device_seed=31)),
        "sharded4x2": lambda: ShardedSsdBackend.from_geometry(
            channels=4, dies_per_channel=2, pages_per_chip=8,
            device_seed=31),
    }
    return {k: _programmed(page_keys, m) for k, m in mk.items()}, page_keys


def _plans(page_keys):
    allk = np.concatenate(page_keys)
    lo = int(np.percentile(allk, 35))
    hi = int(np.percentile(allk, 65))
    return {
        "exact": exact_range(lo, hi, width=64),
        "approx": approximate_range(lo, hi, width=64),
        "exact_narrow": exact_range(lo, lo + 3, width=64),
        "include_only": RangePlan(include=(MaskedQuery(
            query=int(page_keys[0][0]), mask=0xFFFFFFFFFFFFFFFF),)),
        "match_all": RangePlan(include=(MaskedQuery(query=0, mask=0),)),
    }


# ------------------------------------------------------------------ parity
def test_plan_bit_identical_across_backends_and_per_pass(backends):
    """PLAN == per-pass split combine, on every backend, for exact and
    approximate plans — the Fig 10 in-latch accumulation is semantically
    invisible."""
    bes, page_keys = backends
    pages = list(range(N_PAGES))
    for label, plan in _plans(page_keys).items():
        ref = evaluate_plan_per_pass(bes["scalar"], plan, pages)
        for name, be in bes.items():
            got = evaluate_plan_on_pages(be, plan, pages)
            np.testing.assert_array_equal(ref, got, err_msg=f"{label}/{name}")
        # ...and the combined bitmap agrees with direct key evaluation.
        for p in (0, N_PAGES - 1):
            want = plan.evaluate(page_keys[p])
            from repro.core.bits import unpack_bitmap
            got_bits = unpack_bitmap(ref[p], 512)[8:8 + ENTRIES_PER_PAGE]
            np.testing.assert_array_equal(got_bits.astype(bool), want,
                                          err_msg=f"{label}/page{p}")


def test_plan_burst_is_one_launch_with_dedup(backends):
    """Many pages x few distinct plans = ONE launch; identical plans dedup
    into shared plan groups like identical queries dedup into query rows."""
    bes, page_keys = backends
    plan_a = exact_range(1000, 2**40, width=64)
    plan_b = approximate_range(1000, 2**40, width=64)
    be = bes["sharded4x2"]
    before = be.stats.kernel_launches
    tickets = [be.submit_plan(Command.plan(p, pl.include, pl.exclude))
               for pl in (plan_a, plan_b) for p in range(N_PAGES)]
    be.flush()
    assert be.stats.kernel_launches == before + 1
    assert all(t.done for t in tickets)
    # Same plan twice on the same page -> same launch cell, shared copy.
    t1 = be.submit_plan(Command.plan(3, plan_a.include, plan_a.exclude))
    t2 = be.submit_plan(Command.plan(3, plan_a.include, plan_a.exclude))
    rb = be.stats.result_bytes
    be.flush()
    np.testing.assert_array_equal(t1.result().bitmap_words,
                                  t2.result().bitmap_words)
    assert be.stats.result_bytes - rb == 64       # one transfer, not two


def test_plan_result_bytes_drop_by_pass_count(backends):
    """The headline bandwidth claim: fused PLAN ships 64 B/page, the
    per-pass path 64 B/pass/page — an exact result_bytes contract."""
    bes, page_keys = backends
    plan = _plans(page_keys)["exact"]
    assert plan.n_passes > 10
    pages = list(range(N_PAGES))
    be = bes["sharded4x2"]
    before = be.stats.result_bytes
    evaluate_plan_on_pages(be, plan, pages)
    fused_bytes = be.stats.result_bytes - before
    before = be.stats.result_bytes
    evaluate_plan_per_pass(be, plan, pages)
    per_pass_bytes = be.stats.result_bytes - before
    assert fused_bytes == 64 * N_PAGES
    assert per_pass_bytes == 64 * plan.n_passes * N_PAGES
    assert per_pass_bytes // fused_bytes == plan.n_passes


def test_plan_validation():
    be = ScalarBackend(SimChipArray(n_chips=1, pages_per_chip=4))
    with pytest.raises(ValueError):
        be.submit_plan(Command.search(0, 123))
    cmd = Command.plan(0, [(5, 0xFF)], [(1, 0x0F)])
    assert cmd.op is Op.PLAN and cmd.n_passes == 2
    # pass pairs accept MaskedQuery objects and raw (q, m) tuples alike
    cmd2 = Command.plan(0, [MaskedQuery(query=5, mask=0xFF)],
                        [MaskedQuery(query=1, mask=0x0F)])
    assert cmd2.plan_include == cmd.plan_include
    assert cmd2.plan_exclude == cmd.plan_exclude


def test_plan_on_pages_submits_command_plan_per_page(backends,
                                                     monkeypatch):
    """``evaluate_plan_on_pages`` converts the passes once and shares them:
    each page's command equals ``Command.plan(p, include, exclude)``
    field for field, submitted in page order."""
    bes, page_keys = backends
    be = bes["scalar"]
    plan = _plans(page_keys)["approx"]
    plan = RangePlan(include=plan.include + _plans(page_keys)["exact"].include,
                     exclude=plan.exclude, exact=False)
    assert len(plan.include) > 1 and plan.exclude
    submitted = []
    submit_plan = be.submit_plan

    def submit(cmd):
        submitted.append(cmd)
        return submit_plan(cmd)

    monkeypatch.setattr(be, "submit_plan", submit)
    pages = [5, 0, 11, 3]
    evaluate_plan_on_pages(be, plan, pages)
    want = [Command.plan(p, plan.include, plan.exclude) for p in pages]
    assert [dataclasses.asdict(c) for c in submitted] == \
        [dataclasses.asdict(c) for c in want]


# ------------------------------------------------------------- lazy tickets
def test_lazy_ticket_out_of_order_resolution(backends):
    """Two bursts flushed back-to-back, the first drained AFTER the second:
    lazy batches must resolve independently and bit-identically."""
    bes, page_keys = backends
    be = bes["sharded4x2"]
    ref = bes["scalar"]
    cmds_a = [Command.search(p, int(page_keys[p][5])) for p in range(6)]
    cmds_b = [Command.search(p, int(page_keys[p][6])) for p in range(6)]
    ta = [be.submit_search(c) for c in cmds_a]
    be.flush()                               # dispatched, not yet drained
    tb = [be.submit_search(c) for c in cmds_b]
    be.flush()
    assert all(t.done for t in ta + tb)      # resolvable without new flush
    for c, t in list(zip(cmds_b, tb)) + list(zip(cmds_a, ta)):  # B first
        np.testing.assert_array_equal(t.result().bitmap_words,
                                      ref.search(c).bitmap_words)


def test_lazy_ticket_survives_interleaved_reprogram(backends):
    """A reprogram AFTER a flush must not leak into that flush's deferred
    results — the launch captured the pre-write plane snapshot."""
    page_keys = _page_keys(seed=23)
    be = _programmed(page_keys, lambda: ShardedSsdBackend(
        SimChipArray(n_chips=4, pages_per_chip=8, device_seed=9)))
    sc = _programmed(page_keys, lambda: ScalarBackend(
        SimChipArray(n_chips=4, pages_per_chip=8, device_seed=9)))
    probe = Command.search(2, int(page_keys[2][0]))
    want = sc.search(probe)                  # pre-write reference
    t = be.submit_search(probe)
    be.flush()                               # launch dispatched
    be.program_entries(2, page_keys[2][::-1].copy())   # then reprogram
    np.testing.assert_array_equal(t.result().bitmap_words,
                                  want.bitmap_words)
    # ...and a new search sees the new image.
    sc.chips.program_entries(2, page_keys[2][::-1].copy())
    np.testing.assert_array_equal(
        be.search(probe).bitmap_words,
        sc.search(probe).bitmap_words)


def test_lazy_lookup_parity_survives_interleaved_reprogram():
    """CRC verification of a deferred lookup must use the parities as of
    flush time: a reprogram of the value page between flush() and the
    first result() must not flip parity_ok (the launch captured the
    pre-write plane snapshot, so the old parities are the right ones)."""
    rng = np.random.default_rng(3)
    keys = rng.integers(1, 2**50, 100, dtype=np.uint64)
    vals = rng.integers(1, 2**50, 100, dtype=np.uint64)
    for name in ("scalar", "sharded"):
        be = make_backend(name, SimChipArray(n_chips=2, pages_per_chip=8,
                                             device_seed=1))
        be.program_entries(0, keys)
        be.program_entries(1, vals)
        t = be.submit_lookup(Command.lookup(0, 1, int(keys[7])))
        g = be.submit_gather(Command.gather(1, 0b110))
        be.flush()
        be.program_entries(1, vals[::-1].copy())    # between flush + drain
        r = t.result()
        assert r.parity_ok and r.value_slot is not None, name
        assert r.value == int(vals[7]).to_bytes(8, "little"), name
        gr = g.result()
        assert gr.parity_ok.all(), name


# ---------------------------------------------------------------- workload
def test_ycsb_scan_replay_bit_identical():
    """YCSB-E scans (op 2) replay through the fused PLAN path and must be
    bit-identical — counts and read values — across all three backends."""
    wl = generate(180, n_key_pages=4, read_ratio=0.7, alpha=0.5, seed=5,
                  scan_ratio=0.1, max_scan_len=40)
    assert (wl.ops == 2).sum() > 0
    outs = {}
    for name, make in {
        "scalar": lambda: make_backend("scalar", SimChipArray(
            n_chips=4, pages_per_chip=16, device_seed=3)),
        "sharded4x1": lambda: make_backend("sharded", SimChipArray(
            n_chips=4, pages_per_chip=16, device_seed=3)),
        "sharded2x2": lambda: ShardedSsdBackend.from_geometry(
            channels=2, dies_per_channel=2, pages_per_chip=16,
            device_seed=3, timeline=True),
    }.items():
        outs[name] = replay(wl, make(), RunConfig(burst=32, fused=True))
    ref = outs["scalar"]
    n_keys = 4 * 504
    for r in outs.values():
        np.testing.assert_array_equal(ref.read_values, r.read_values)
        np.testing.assert_array_equal(ref.scan_counts, r.scan_counts)
        assert r.n_scans == ref.n_scans > 0
    # All stored keys in a scan window exist, so counts == window size.
    for qi in np.nonzero(wl.ops == 2)[0]:
        lo = int(wl.keys[qi]) + 1
        hi = min(lo + int(wl.scan_lens[qi]), n_keys + 1)
        assert ref.scan_counts[qi] == hi - lo
    # Timeline coupling still holds with scans in the stream.
    sh = outs["sharded2x2"]
    assert sh.burst_latencies_ns is not None
    assert len(sh.burst_latencies_ns) == sh.flushes


def test_scan_free_generate_stream_unchanged():
    """scan_ratio=0 must leave the historical op/key stream bit-identical
    (the RNG consumption is untouched)."""
    a = generate(200, n_key_pages=4, read_ratio=0.8, alpha=0.5, seed=11)
    b = generate(200, n_key_pages=4, read_ratio=0.8, alpha=0.5, seed=11,
                 scan_ratio=0.0)
    np.testing.assert_array_equal(a.ops, b.ops)
    np.testing.assert_array_equal(a.keys, b.keys)
    assert b.scan_lens is None
