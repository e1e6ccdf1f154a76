"""Event-driven frontend: RunConfig/RunReport API + event-loop contracts.

Contracts held here:

  * ``RunConfig`` — frozen, validated at construction (bad enums, event
    knobs in serial mode, poisson without a rate all refuse); presets
    build the documented shapes (the ``run_functional`` shim is gone —
    ``repro.frontend.replay`` is the only functional entry point);
  * ``RunReport`` — one schema for all three executors, legacy flat
    aliases reading through to the nested sections;
  * **bit-parity anchor** — ``RunConfig.event_serial()`` (one stream,
    zero inter-arrival, FIFO) replays bit-identically to
    ``mode="serial"`` across scalar/sharded (4x1, 2x2) x split/fused x
    buffered/reliability configs: same values, hits, errors, programs
    AND the same flush grouping (reliability epochs depend on it);
  * **determinism** — same seeds => identical event trace and report;
  * **NCQ bound** — queued + inflight never exceeds ``ncq_depth`` for
    any arrival trace (hypothesis property over traces);
  * **scheduling** — on a crafted program-backlog trace, FIFO reads
    queue behind the die-program backlog while read_priority reads
    program-suspend past it;
  * **device-fault tier** — an empty fault schedule with replicas=2 is
    still bit-identical to the plain serial replay (parity anchor); a
    dead chip fails reads over to replicas with results bit-identical
    to the healthy replay (zero wrong results); without replicas the
    same outage degrades to typed per-op errors, never wrong data; and
    same seed + same fault schedule => byte-identical RunReport
    (hypothesis property over schedules).
"""
import numpy as np
import pytest

from repro.backend import make_backend
from repro.backend.sharded import ShardedSsdBackend
from repro.core.engine import SimChipArray
from repro.frontend import (EventLoop, RunConfig, RunReport, replay)
from repro.reliability import (FaultModel, FaultSchedule,
                               ReliabilityPolicy, ReliabilityState)
from repro.workload.ycsb import KEYS_PER_PAGE, Workload, generate, \
    value_page_of


# --------------------------------------------------------------------------
# RunConfig validation + presets
# --------------------------------------------------------------------------

def test_runconfig_is_frozen_and_validated():
    cfg = RunConfig(burst=16, fused=True)
    with pytest.raises(Exception):      # frozen dataclass
        cfg.burst = 32
    with pytest.raises(ValueError):
        RunConfig(mode="turbo")
    with pytest.raises(ValueError):
        RunConfig(scheduler="lifo")
    with pytest.raises(ValueError):
        RunConfig(burst=0)
    with pytest.raises(ValueError):
        RunConfig(write_buffer="yes")


def test_runconfig_event_knobs_refused_in_serial_mode():
    for kw in (dict(concurrency=4), dict(scheduler="read_priority"),
               dict(arrival="poisson", arrival_rate_qps=1e5)):
        with pytest.raises(ValueError):
            RunConfig(**kw)
    with pytest.raises(ValueError):      # poisson needs a positive rate
        RunConfig(mode="event", arrival="poisson")
    with pytest.raises(ValueError):      # trace needs times
        RunConfig(mode="event", arrival="trace")
    with pytest.raises(ValueError):      # rate only applies to poisson
        RunConfig(mode="event", arrival_rate_qps=1e5)


def test_runconfig_presets():
    assert RunConfig.eager() == RunConfig()
    b = RunConfig.buffered(write_high_water=4)
    assert b.write_buffer is True and b.write_high_water == 4
    rel = ReliabilityState(ReliabilityPolicy(), FaultModel(seed=1))
    assert RunConfig.reliable(rel).reliability is rel
    with pytest.raises(ValueError):
        RunConfig.reliable(None)
    o = RunConfig.open_loop(2e5, concurrency=8)
    assert o.mode == "event" and o.arrival == "poisson"
    assert o.scheduler == "read_priority" and o.arrival_rate_qps == 2e5
    e = RunConfig.event_serial(burst=8)
    assert (e.mode, e.concurrency, e.arrival, e.scheduler) \
        == ("event", 1, "zero", "fifo")
    assert e.with_(fused=True).fused and not e.fused


def test_runconfig_trace_times_normalized():
    cfg = RunConfig(mode="event", arrival="trace",
                    arrival_times_ns=[0, 10, 20])
    assert cfg.arrival_times_ns == (0.0, 10.0, 20.0)
    with pytest.raises(ValueError):
        RunConfig(mode="event", arrival="trace", arrival_times_ns=[-1.0])


# --------------------------------------------------------------------------
# RunReport shape
# --------------------------------------------------------------------------

def _mk(name="scalar", n_chips=4, pages=32, **kw):
    return make_backend(name, SimChipArray(
        n_chips=n_chips, pages_per_chip=pages, device_seed=3), **kw)


def test_run_functional_shim_is_gone():
    # The deprecation cycle promised in the shim's docstring is over:
    # repro.frontend.replay is the one functional entry point.
    import repro.workload.runner as runner
    assert not hasattr(runner, "run_functional")


def test_runreport_legacy_aliases_read_nested_sections():
    wl = generate(120, n_key_pages=4, read_ratio=0.7, alpha=0.5, seed=2)
    r = replay(wl, _mk(), RunConfig(burst=16))
    assert r.n_reads == r.counters.reads > 0
    assert r.flushes == r.counters.flushes
    assert r.programs == r.counters.programs == r.n_writes
    assert r.sim_makespan_ns == r.latency.makespan_ns
    assert r.sim_energy_pj == r.energy.total_pj
    assert r.n_read_errors == r.reliability.n_read_errors == 0


def test_analytic_run_returns_runreport():
    from repro.flash.params import DEFAULT_PARAMS
    from repro.workload.runner import run
    wl = generate(800, n_key_pages=16, read_ratio=0.7, alpha=0.5, seed=4)
    r = run(wl, params=DEFAULT_PARAMS, system="sim", cache_coverage=0.25)
    assert isinstance(r, RunReport) and r.source == "analytic"
    assert r.qps == r.latency.qps > 0
    assert r.read_median_ns == r.latency.read_p50_ns > 0
    assert r.senses == r.counters.senses > 0
    assert r.energy_pj == r.energy.total_pj > 0


# --------------------------------------------------------------------------
# Bit-parity anchor: event_serial == serial
# --------------------------------------------------------------------------

def _assert_parity(rs, re):
    np.testing.assert_array_equal(rs.read_values, re.read_values)
    np.testing.assert_array_equal(rs.read_hits, re.read_hits)
    if rs.scan_counts is not None or re.scan_counts is not None:
        np.testing.assert_array_equal(rs.scan_counts, re.scan_counts)
    if rs.read_errors is not None or re.read_errors is not None:
        np.testing.assert_array_equal(rs.read_errors, re.read_errors)
    assert rs.programs == re.programs
    assert rs.flushes == re.flushes          # same burst grouping
    assert rs.write_flushes == re.write_flushes
    assert rs.buffer_read_hits == re.buffer_read_hits
    assert rs.kernel_launches == re.kernel_launches
    assert rs.refreshes == re.refreshes


@pytest.mark.parametrize("fused", [False, True])
@pytest.mark.parametrize("buffered", [False, True])
@pytest.mark.parametrize("name", ["scalar", "sharded4x1", "sharded"])
def test_event_serial_bit_parity(name, fused, buffered):
    wl = generate(300, n_key_pages=8, read_ratio=0.5, alpha=0.9, seed=7,
                  scan_ratio=0.05)
    kw = dict(burst=32, fused=fused)
    if buffered:
        kw.update(write_buffer=True, write_high_water=4)

    def mk():
        if name == "sharded":
            return ShardedSsdBackend.from_geometry(
                channels=2, dies_per_channel=2,
                pages_per_chip=max(wl.n_index_pages // 4 + 1, 8),
                device_seed=3)
        # sharded4x1: make_backend gives each of the 4 chips its own channel
        return _mk(name.removesuffix("4x1"), n_chips=4,
                   pages=max(wl.n_index_pages // 4 + 1, 8))

    _assert_parity(replay(wl, mk(), RunConfig(**kw)),
                   replay(wl, mk(), RunConfig.event_serial(**kw)))


@pytest.mark.parametrize("buffered", [False, True])
def test_event_serial_bit_parity_reliability(buffered):
    wl = generate(200, n_key_pages=4, read_ratio=0.6, alpha=0.9, seed=9)
    kw = dict(burst=16, fused=True)
    if buffered:
        kw.update(write_buffer=True, write_high_water=4)

    def rel():
        return ReliabilityState(
            ReliabilityPolicy(verify_hits=True, fallback_on_miss=True),
            FaultModel(seed=11, base_ber=1e-4, retention_days=45.0,
                       sense_ber=2e-4))

    def mk():
        return make_backend("scalar", SimChipArray(
            n_chips=2, pages_per_chip=max(wl.n_index_pages // 2 + 1, 8),
            device_seed=3))

    rs = replay(wl, mk(), RunConfig.reliable(rel(), **kw))
    re = replay(wl, mk(),
                RunConfig.event_serial(reliability=rel(), **kw))
    _assert_parity(rs, re)
    assert rs.refreshes > 0          # the refresh path actually ran


# --------------------------------------------------------------------------
# Determinism
# --------------------------------------------------------------------------

def test_event_loop_deterministic_trace_and_report():
    wl = generate(400, n_key_pages=8, read_ratio=0.5, alpha=0.9, seed=1)
    cfg = RunConfig.open_loop(3e5, concurrency=4, burst=32, seed=12,
                              write_buffer=True, write_high_water=4,
                              record_trace=True)
    a = replay(wl, _mk(pages=16), cfg)
    b = replay(wl, _mk(pages=16), cfg)
    assert a.trace == b.trace and len(a.trace) > 0
    np.testing.assert_array_equal(a.read_values, b.read_values)
    assert a.latency.read_p99_ns == b.latency.read_p99_ns
    assert a.counters == b.counters
    # a different seed moves the arrivals -> different trace
    c = replay(wl, _mk(pages=16), cfg.with_(seed=13))
    assert c.trace != a.trace


def test_event_counters_account_for_every_op():
    wl = generate(300, n_key_pages=8, read_ratio=0.6, alpha=0.9, seed=2)
    r = replay(wl, _mk(pages=16),
               RunConfig.open_loop(3e5, concurrency=4, ncq_depth=16,
                                   burst=16))
    c = r.counters
    assert c.admitted + c.admission_waits == len(wl.ops)
    assert c.ncq_peak <= 16
    assert c.dispatches > 0 and c.events >= len(wl.ops)
    assert r.latency.qps > 0 and r.latency.makespan_ns > 0
    assert len(r.latency.read_latencies_ns) == c.reads


# --------------------------------------------------------------------------
# NCQ depth bound: hypothesis property over arrival traces
# --------------------------------------------------------------------------

def test_ncq_depth_bound_property():
    hypothesis = pytest.importorskip("hypothesis")
    given = hypothesis.given
    st = hypothesis.strategies

    wl = generate(60, n_key_pages=4, read_ratio=0.5, alpha=0.9, seed=5)

    @given(times=st.lists(st.floats(min_value=0.0, max_value=2e6,
                                    allow_nan=False),
                          min_size=60, max_size=60),
           depth=st.integers(min_value=1, max_value=8),
           sched=st.sampled_from(["fifo", "read_priority", "fair_share"]))
    @hypothesis.settings(max_examples=25, deadline=None)
    def prop(times, depth, sched):
        loop = EventLoop(wl, _mk(pages=16), RunConfig(
            mode="event", arrival="trace", arrival_times_ns=times,
            concurrency=3, scheduler=sched, ncq_depth=depth, burst=8,
            write_buffer=True, write_high_water=4))
        r = loop.run()
        assert loop.ncq_peak <= depth
        assert r.counters.admitted + r.counters.admission_waits == 60
        assert r.counters.reads + r.counters.writes \
            + r.counters.scans == 60

    prop()


def test_ncq_depth_bound_seeded_traces():
    """No-hypothesis fallback: the same bound over seeded random traces,
    so the invariant is exercised even where hypothesis is absent."""
    wl = generate(60, n_key_pages=4, read_ratio=0.5, alpha=0.9, seed=5)
    for seed in range(6):
        rng = np.random.default_rng(seed)
        times = np.sort(rng.uniform(0.0, 2e6, 60)).tolist()
        depth = int(rng.integers(1, 9))
        sched = ["fifo", "read_priority", "fair_share"][seed % 3]
        loop = EventLoop(wl, _mk(pages=16), RunConfig(
            mode="event", arrival="trace", arrival_times_ns=times,
            concurrency=3, scheduler=sched, ncq_depth=depth, burst=8,
            write_buffer=True, write_high_water=4))
        r = loop.run()
        assert loop.ncq_peak <= depth, (seed, sched, depth)
        assert r.counters.admitted + r.counters.admission_waits == 60


# --------------------------------------------------------------------------
# Scheduling: read-priority bypasses the program backlog, FIFO queues
# --------------------------------------------------------------------------

def _backlog_workload_and_times(n_key_pages=2):
    """Ten writes land a program backlog on every die, then ten reads
    arrive while the programs are still in flight (t_program = 80 us)."""
    writes = list(range(10))                      # keys on page 0
    reads = [k + KEYS_PER_PAGE for k in range(10)]  # keys on page 1
    keys = np.asarray(writes + reads, dtype=np.int64)
    ops = np.asarray([1] * 10 + [0] * 10, dtype=np.uint8)
    kp = (keys // KEYS_PER_PAGE).astype(np.int32)
    vp = value_page_of(kp, n_key_pages).astype(np.int32)
    wl = Workload(ops=ops, key_pages=kp, value_pages=vp, alpha=0.0,
                  read_ratio=0.5, n_index_pages=2 * n_key_pages,
                  keys=keys)
    # Writes at t=0, reads 1 us later — well inside the 80 us programs.
    times = [0.0] * 10 + [1_000.0] * 10
    return wl, times


@pytest.mark.parametrize("sched,expect_stalled", [
    ("fifo", True), ("read_priority", False), ("fair_share", False)])
def test_read_priority_bypasses_program_backlog(sched, expect_stalled):
    wl, times = _backlog_workload_and_times()
    r = replay(wl, _mk(n_chips=2, pages=8), RunConfig(
        mode="event", arrival="trace", arrival_times_ns=times,
        scheduler=sched, burst=16, ncq_depth=32))
    assert r.read_hits.sum() == 10 and r.programs == 10
    p50 = r.latency.read_p50_ns
    # t_program = 80 us: FIFO reads queue behind the die backlog, so
    # their latency carries a program-sized wait; read-priority reads
    # program-suspend past it and finish in sense+bus time.
    assert (p50 > 50_000.0) == expect_stalled, p50


def test_fifo_vs_read_priority_same_totals_different_timing():
    """Above concurrency 1 the policies may legitimately reorder reads
    across writes from other streams (real NCQ semantics — individual
    read VALUES can differ; only the serial anchor is bit-exact), but
    the op accounting must agree and the FIFO tail must be worse."""
    wl = generate(400, n_key_pages=8, read_ratio=0.5, alpha=0.9, seed=3)
    reports = {}
    for sched in ("fifo", "read_priority"):
        reports[sched] = replay(wl, _mk(pages=16), RunConfig(
            mode="event", arrival="zero", concurrency=2, scheduler=sched,
            burst=32, write_buffer=True, write_high_water=4))
    fifo, rp = reports["fifo"], reports["read_priority"]
    # Functional totals agree (ordering may differ per policy) ...
    assert fifo.counters.reads == rp.counters.reads
    assert fifo.counters.writes == rp.counters.writes
    assert fifo.programs == rp.programs
    # ... but the FIFO tail carries the program waits.
    assert fifo.latency.read_p99_ns > rp.latency.read_p99_ns


# --------------------------------------------------------------------------
# Device-fault tier: replica parity anchor, failover, chaos determinism
# --------------------------------------------------------------------------

def _mk_replicated(wl, replicas=2):
    """Sharded backend with replica striping and spare headroom for the
    replica copies plus grown-bad-block remaps."""
    per_chip = (wl.n_index_pages // 4 + 1) * (replicas + 1)
    return ShardedSsdBackend(
        SimChipArray(n_chips=4, pages_per_chip=per_chip, device_seed=3),
        use_kernel=False, interpret=True, replicas=replicas)


@pytest.mark.parametrize("fused", [False, True])
@pytest.mark.parametrize("buffered", [False, True])
def test_replica_event_serial_bit_parity(fused, buffered):
    """Fault-free parity anchor: replicas=2 plus an attached (empty)
    fault schedule must not perturb a single bit of the replay — the
    whole fault tier is latency/bookkeeping until a fault actually
    fires."""
    wl = generate(300, n_key_pages=8, read_ratio=0.5, alpha=0.9, seed=7,
                  scan_ratio=0.05)
    kw = dict(burst=32, fused=fused)
    if buffered:
        kw.update(write_buffer=True, write_high_water=4)
    rs = replay(wl, _mk_replicated(wl), RunConfig(**kw))
    re = replay(wl, _mk_replicated(wl), RunConfig.event_serial(
        faults=FaultSchedule.healthy(seed=7), **kw))
    _assert_parity(rs, re)
    # the tier was live (replica mirrors programmed), yet fired nothing
    f = re.faults
    assert f.replica_programs > 0
    assert (f.timeouts, f.retries, f.failovers, f.degraded_ops,
            f.remapped_blocks, f.shed_requests, f.n_op_errors) \
        == (0, 0, 0, 0, 0, 0, 0)


def test_dead_chip_failover_bit_identical_to_healthy():
    """Chip 0 dead from t=0 with replicas=2: every read of its pages
    fails over to a replica, and the answers are bit-identical to the
    healthy replay — faults surface as latency and counters, never as
    wrong data."""
    wl = generate(300, n_key_pages=8, read_ratio=0.6, alpha=0.9, seed=7,
                  scan_ratio=0.05)
    kw = dict(burst=16, fused=True, seed=7)
    healthy = replay(wl, _mk_replicated(wl), RunConfig.event_serial(
        faults=FaultSchedule.healthy(seed=7), **kw))
    dead = replay(wl, _mk_replicated(wl), RunConfig.event_serial(
        faults=FaultSchedule.dead_chip(chip=0, seed=7), **kw))
    np.testing.assert_array_equal(healthy.read_values, dead.read_values)
    np.testing.assert_array_equal(healthy.read_hits, dead.read_hits)
    if healthy.scan_counts is not None:
        np.testing.assert_array_equal(healthy.scan_counts,
                                      dead.scan_counts)
    assert dead.faults.failovers > 0       # the replica path actually ran
    assert dead.faults.degraded_ops > 0
    assert dead.faults.n_op_errors == 0    # zero ops lost, zero wrong


def test_dead_chip_without_replicas_fails_typed():
    """replicas=1 and a dead chip: reads of its pages have nowhere to
    fail over — they must surface as typed per-op errors (op_errors),
    never as fabricated values, and every other op still completes."""
    wl = generate(300, n_key_pages=8, read_ratio=0.6, alpha=0.9, seed=7)
    r = replay(wl, _mk_replicated(wl, replicas=1), RunConfig.event_serial(
        faults=FaultSchedule.dead_chip(chip=0, seed=7), burst=16))
    f = r.faults
    assert f.n_op_errors > 0
    assert f.failovers == 0                # no replicas to fail over to
    # errored reads report miss/zero, completed ones match the healthy run
    healthy = replay(wl, _mk_replicated(wl, replicas=1),
                     RunConfig.event_serial(burst=16))
    ok = ~f.op_errors
    np.testing.assert_array_equal(r.read_values[ok],
                                  healthy.read_values[ok])
    assert not r.read_hits[f.op_errors].any()
    assert not r.read_values[f.op_errors].any()


def test_chaos_determinism_property():
    """Same seed + same fault schedule => identical RunReport: values,
    per-op errors, every fault counter, the event trace and the latency
    tail, for every schedule shape."""
    hypothesis = pytest.importorskip("hypothesis")
    given = hypothesis.given
    st = hypothesis.strategies

    wl = generate(160, n_key_pages=8, read_ratio=0.6, alpha=0.9, seed=4)

    def run_once(sched, deadline):
        return replay(wl, _mk_replicated(wl), RunConfig.chaos(
            sched, deadline_ns=deadline, max_retries=3,
            backoff_base_ns=100_000.0, concurrency=4, burst=16, seed=5,
            record_trace=True))

    @given(kind=st.sampled_from(["healthy", "transient_stall",
                                 "dying_die", "dead_chip"]),
           fault_seed=st.integers(min_value=0, max_value=5),
           deadline=st.sampled_from([400_000.0, 800_000.0]))
    @hypothesis.settings(max_examples=8, deadline=None)
    def prop(kind, fault_seed, deadline):
        mk_sched = {
            "healthy": lambda: FaultSchedule.healthy(seed=fault_seed),
            "transient_stall": lambda: FaultSchedule.transient_stall(
                die=0, t_start_ms=0.05, dur_ms=1.0, seed=fault_seed),
            "dying_die": lambda: FaultSchedule.dying_die(
                die=1, t_fail_ms=0.5, program_fail_prob=0.05,
                seed=fault_seed),
            "dead_chip": lambda: FaultSchedule.dead_chip(
                chip=0, seed=fault_seed),
        }[kind]
        a = run_once(mk_sched(), deadline)
        b = run_once(mk_sched(), deadline)
        np.testing.assert_array_equal(a.read_values, b.read_values)
        np.testing.assert_array_equal(a.faults.op_errors,
                                      b.faults.op_errors)
        for field in ("timeouts", "retries", "backoff_waits",
                      "hedges_won", "failovers", "remapped_blocks",
                      "degraded_ops", "shed_requests", "replica_programs",
                      "program_failures", "n_op_errors"):
            assert getattr(a.faults, field) == getattr(b.faults, field), \
                field
        assert a.counters == b.counters
        assert a.latency.read_p99_ns == b.latency.read_p99_ns
        assert a.trace == b.trace and len(a.trace) > 0

    prop()


def test_chaos_determinism_seeded_fallback():
    """No-hypothesis fallback: one deterministic pass per schedule shape
    so the same-seed contract is exercised even where hypothesis is
    absent."""
    wl = generate(160, n_key_pages=8, read_ratio=0.6, alpha=0.9, seed=4)
    for sched in (FaultSchedule.transient_stall(die=0, t_start_ms=0.05,
                                                dur_ms=1.0, seed=3),
                  FaultSchedule.dead_chip(chip=0, seed=3)):
        cfg = RunConfig.chaos(sched, deadline_ns=400_000.0, max_retries=3,
                              backoff_base_ns=100_000.0, concurrency=4,
                              burst=16, seed=5)
        a = replay(wl, _mk_replicated(wl), cfg)
        b = replay(wl, _mk_replicated(wl), cfg)
        np.testing.assert_array_equal(a.read_values, b.read_values)
        np.testing.assert_array_equal(a.faults.op_errors,
                                      b.faults.op_errors)
        assert a.counters == b.counters
        assert a.latency.read_p99_ns == b.latency.read_p99_ns
