"""The YCSB family: core YCSB workloads replayed through ``ReplayCore``.

A run generates the op stream from the seed (``bench/ycsb.py``, driven by
the cell's traffic file), bulk-loads the index into the program's
``ReplayCore`` over a ``ShardedSsdBackend``, stages every index page into
the device arena, warms up, drives the window, and checks every answer
against ``bench/oracle.py``.

The loop is closed with one client: the serial replay loop of
``repro.frontend.replay``, copied here with a clock around each call.
Reads coalesce into bursts of the configuration's ``burst``; writes and
scans run where they fall in the stream.  An op's latency runs from the
moment it is handed to ``ReplayCore`` until its answer is readable: for a
read, until a call into the core returns with the read's hit set (the
fused path drains a burst when it flushes the next one); for a write,
until it is acknowledged; for a scan, until ``scan`` returns.
"""
from __future__ import annotations

import contextlib
import math
import time

import numpy as np

from bench import oracle, roofline
from bench.ycsb import (KEYS_PER_PAGE, OP_READ, OP_SCAN, OP_UPDATE, Stream,
                        generate, n_key_pages, rng_for)

READBACK_MAX = 4096          # acknowledged writes read back after the window
KIND_LABELS = ((OP_READ, "read"), (OP_UPDATE, "update"), (OP_SCAN, "scan"))
LOOKUP_PROGRAM = "jit_sim_lookup_kernel"
PLAN_PROGRAM = "jit__stacked_plan"


class StreamExhausted(RuntimeError):
    """The window reached the end of the generated stream."""


# -------------------------------------------------------------- the system
def build(cell, stream: Stream, *, seed: int, interpret: bool,
          marks: dict):
    """Bulk-load the index and stage every page; returns (core, backend).
    Sets ``marks["loaded"]`` and ``marks["staged"]`` (host clock)."""
    import jax
    from repro.backend.sharded import ShardedSsdBackend
    from repro.frontend import RunConfig
    from repro.frontend.replay import ReplayCore
    from repro.reliability import FaultSchedule

    cfg = cell.config
    geo = cfg["geometry"]
    n_chips = geo["channels"] * geo["dies_per_channel"]
    backend = ShardedSsdBackend.from_geometry(
        channels=geo["channels"], dies_per_channel=geo["dies_per_channel"],
        pages_per_chip=-(-stream.n_index_pages // n_chips),
        timeline=True, use_kernel=True, interpret=interpret)
    rc = cfg["run_config"]
    kw = {"fused": rc["fused"], "burst": rc["burst"]}
    if cfg["faults"] != "healthy":
        raise ValueError(f"fault schedule {cfg['faults']!r} is not built")
    kw["faults"] = FaultSchedule.healthy(seed=seed % (1 << 31))
    if rc["preset"] == "buffered":
        config = RunConfig.buffered(
            write_high_water=rc["write_high_water"], **kw)
    elif rc["preset"] == "eager":
        config = RunConfig.eager(**kw)
    else:
        raise ValueError(f"RunConfig preset {rc['preset']!r} is not built")
    core = ReplayCore(stream, backend, config)
    marks["loaded"] = time.perf_counter()
    store = backend.store
    store.stage_group(range(stream.n_index_pages))
    jax.block_until_ready(store.take(np.zeros(1, np.int32), 1))
    if store.resident_rows != stream.n_index_pages:
        raise RuntimeError(f"{store.resident_rows} resident rows, index "
                           f"has {stream.n_index_pages} pages")
    marks["staged"] = time.perf_counter()
    return core, backend


class Probe:
    """The family's clock and hooks on one replay.

    It records when each op is handed to the core (``t_in``) and when its
    answer is readable (``t_out``), the host seconds inside the calls into
    the core (``drive`` adds those) and inside the backend's ``flush``, and
    the reads of every burst flushed while ``recording``.  A read's answer
    is readable once a call into the core has returned with its hit, or
    its error, set; the probe looks at the reads still outstanding after
    each call that flushed the open burst.  With ``trace`` it writes a
    profiler span around each call into the core (``drive``'s
    ``bench.replay``) and into the backend's flush (``bench.flush``).
    """

    def __init__(self, core, backend, n_ops: int, *, trace: bool):
        import jax
        self.core = core
        self.t_in = np.full(n_ops, np.nan)
        self.t_out = np.full(n_ops, np.nan)
        self.host = {"replay": 0.0, "flush": 0.0}
        self.bursts: list[np.ndarray] = []
        self.open: list[int] = []           # queued reads, burst not flushed
        self.outstanding: list[int] = []    # flushed reads, no answer yet
        self.recording = False
        self.span = (jax.profiler.TraceAnnotation if trace
                     else contextlib.nullcontext)
        clock, host, span = time.perf_counter, self.host, self.span
        inner_flush = backend.flush

        def flush():
            t = clock()
            with span("bench.flush"):
                inner_flush()
            host["flush"] += clock() - t
        backend.flush = flush

    def after(self, now: float, read: int | None = None) -> None:
        """Bookkeeping after a call into the core returned at ``now``;
        ``read`` is the op it queued into the open burst, if any."""
        if read is not None:
            self.open.append(read)
        if self.core.pending:
            return
        if self.open:                       # the call flushed the burst
            if self.recording:
                self.bursts.append(np.array(self.open))
            self.outstanding += self.open
            self.open = []
        if self.outstanding:
            core = self.core
            q = np.array(self.outstanding)
            done = core.hits[q] | core.read_errors[q] | core.op_errors[q]
            self.t_out[q[done]] = now
            self.outstanding = q[~done].tolist()


def drive(core, probe: Probe, stream: Stream, start: int, stop: int, *,
          burst: int, deadline: float | None = None,
          n_ops: int | None = None) -> int:
    """Hand ops ``start..`` to the core in stream order.

    Stops at the first point with no read pending after ``deadline`` has
    passed or ``n_ops`` ops have been handed, so every burst it flushes
    is a full one unless the stream itself cut it.  Returns the next op
    position; raises StreamExhausted at ``stop``.
    """
    clock = time.perf_counter
    ops, t_in, t_out, host = stream.ops, probe.t_in, probe.t_out, probe.host
    span, after = probe.span, probe.after
    end = start + n_ops if n_ops is not None else stop
    qi = start
    while True:
        if qi >= stop:
            raise StreamExhausted(f"the window reached op {qi}, the end of "
                                  "the generated stream")
        op = ops[qi]
        queued = False
        t = clock()
        t_in[qi] = t
        with span("bench.replay"):
            if op == OP_READ:
                queued = core.queue_read(qi)
                if queued and len(core.pending) >= burst:
                    core.resolve_burst()
            elif op == OP_SCAN:
                core.scan(qi)
            else:
                core.write(qi)
        now = clock()
        host["replay"] += now - t
        if not queued:          # a scan, a write, or a read the buffer served
            t_out[qi] = now
        after(now, qi if queued else None)
        qi += 1
        if core.pending:
            continue
        if qi >= end or (deadline is not None and now >= deadline):
            return qi


# ------------------------------------------------------ traffic and warm-up
def burst_sizes(cell) -> list[int]:
    """Read-burst sizes the cell's traffic can produce: every size up to
    ``burst`` where writes, scans or the write buffer cut bursts short,
    else only full bursts."""
    burst = cell.config["run_config"]["burst"]
    t = cell.traffic
    if (t["update_proportion"] or t["scan_proportion"]
            or cell.config["run_config"]["preset"] == "buffered"):
        return list(range(1, burst + 1))
    return [burst]


def warmup_scans(traffic: dict, n_keys: int, seed: int) -> list[tuple]:
    """One scan ``(key, length)`` for each (pages touched, plan passes)
    pair that the traffic's scans can produce, so that every plan shape
    is compiled before the window.

    Both numbers depend on a scan's start only through its place in a
    page and its low bits, which repeat every ``period`` keys, so every
    start within one period and every length is a candidate.  Which
    candidate stands for a pair, and where in the index it runs, is drawn
    from the seed."""
    max_len = traffic["max_scan_length"]
    period = min(math.lcm(KEYS_PER_PAGE, 1 << max_len.bit_length()), n_keys)
    k = np.repeat(np.arange(period, dtype=np.int64), max_len)
    n = np.tile(np.arange(1, max_len + 1, dtype=np.int64), period)
    lo, hi = k + 1, np.minimum(k + 1 + n, n_keys + 1)
    pages = (hi - 2) // KEYS_PER_PAGE - (lo - 1) // KEYS_PER_PAGE + 1
    sig = pages * (1 << 32) + roofline.exact_range_passes(lo, hi)
    rng = rng_for(seed, 1)
    order = rng.permutation(len(sig))
    _, first = np.unique(sig[order], return_index=True)
    pick = order[first]
    shift = period * rng.integers(0, max(1, (n_keys - max_len) // period),
                                  len(pick))
    return list(zip((k[pick] + shift).tolist(), n[pick].tolist()))


def warm_up(cell, core, probe: Probe, stream: Stream, stop: int,
            log, scans: list[tuple]) -> int:
    """Compile every shape the window will use; returns the window's
    first op position.

    First one read burst of each size the traffic can produce and one
    scan of each plan shape, then the head of the stream until a stretch
    of ``quiet_ops`` ops passes with no compile (at least ``min_ops``, at
    most ``max_ops``)."""
    burst = cell.config["run_config"]["burst"]
    pos = 0
    for size in burst_sizes(cell):
        stream.ops[pos:pos + size] = OP_READ
        for qi in range(pos, pos + size):
            core.queue_read(qi)
        core.resolve_burst()
        pos += size
    for k, n in scans:
        stream.set_op(pos, OP_SCAN, k, n)
        core.scan(pos)
        pos += 1
    core.drain_inflight()
    w = cell.traffic["warmup"]
    head, quiet_from, seen = pos, pos, log.compiles
    while True:
        pos = drive(core, probe, stream, pos, stop, burst=burst,
                    n_ops=w["chunk_ops"])
        if log.compiles != seen:
            seen, quiet_from = log.compiles, pos
        done = pos - head
        if (done >= w["min_ops"] and pos - quiet_from >= w["quiet_ops"]) \
                or done >= w["max_ops"]:
            break
    core.drain_inflight()
    probe.after(time.perf_counter())
    return pos


def stream_length(cell, seconds: float, n_scans: int) -> int:
    """Ops generated for one run: the warm-up bursts and ``n_scans``
    scans, the longest warm-up and a window at ``stream_ops_per_s``,
    several times what the system does."""
    t = cell.traffic
    burst = cell.config["run_config"]["burst"]
    return (sum(burst_sizes(cell)) + n_scans + t["warmup"]["max_ops"]
            + t["warmup"]["chunk_ops"] + int(t["stream_ops_per_s"] * seconds)
            + burst)


def make_stream(cell, seconds: float, seed: int):
    """The run's op stream from the seed; returns (stream, window stream
    length, warm-up scans).  Positions past the window length hold the
    read-back."""
    scans = []
    if cell.traffic["scan_proportion"]:
        scans = warmup_scans(cell.traffic, n_key_pages(
            cell.config["records"]) * KEYS_PER_PAGE, seed)
    n_main = stream_length(cell, seconds, len(scans))
    stream = generate(n_main + READBACK_MAX, cell.traffic,
                      records=cell.config["records"], seed=seed)
    return stream, n_main, scans


# ---------------------------------------------------------------- checks
def readback_keys(stream: Stream, end: int, seed: int) -> np.ndarray:
    """The records written before ``end``, or a sample of READBACK_MAX of
    them drawn from the seed, in key order."""
    written = np.unique(stream.keys[:end][stream.ops[:end] == OP_UPDATE])
    if len(written) > READBACK_MAX:
        written = np.sort(rng_for(seed, 2).choice(written, READBACK_MAX,
                                                  replace=False))
    return written


def read_back(core, stream: Stream, first: int, end: int, burst: int,
              seed: int) -> np.ndarray:
    """Drain the write buffer, then read back ``readback_keys`` through
    the same read path, at positions ``first..``.  Returns those
    positions."""
    written = readback_keys(stream, end, seed)
    if not len(written):
        return np.zeros(0, np.int64)
    core.flush_write_buffer()
    pos = first + np.arange(len(written))
    stream.set_op(pos, OP_READ, written)
    for qi in pos.tolist():
        if core.queue_read(qi) and len(core.pending) >= burst:
            core.resolve_burst()
    core.resolve_burst()
    core.drain_inflight()
    return pos


def check(core, stream: Stream, end: int, readback: np.ndarray,
          window: slice) -> tuple[dict, int]:
    """Every executed answer against the reference.

    Returns ({name: (value, limit)}, failed ops in the window).  The
    numbers are exact counts, so each limit is 0."""
    pos = np.r_[np.arange(end), readback]
    verdict = oracle.compare(
        stream.ops[pos], stream.keys[pos], stream.scan_lens[pos], pos,
        core.out[pos], core.hits[pos], core.scan_counts[pos],
        n_keys=oracle.n_keys_of(stream.n_index_pages // 2))
    bad = verdict["wrong_read"] | verdict["wrong_scan"] \
        | core.op_errors[pos] | core.read_errors[pos]
    fs = core.fault_state.stats if core.fault_state is not None else None
    host_served = int(core.op_errors.sum() + core.read_errors.sum())
    if fs is not None:
        host_served += fs.degraded_ops + fs.failovers
    checks = {
        "wrong_reads": (int(verdict["wrong_read"][:end].sum()), 0),
        "wrong_scans": (int(verdict["wrong_scan"].sum()), 0),
        "lost_writes": (int(verdict["wrong_read"][end:].sum()), 0),
        "host_served": (host_served, 0),
    }
    return checks, int(bad[window].sum())


def plan_bytes(stream: Stream, scans: np.ndarray) -> list[int]:
    """Required bytes of each scan's one plan launch."""
    n_keys = oracle.n_keys_of(stream.n_index_pages // 2)
    lo = stream.keys[scans] + 1
    hi = np.minimum(lo + stream.scan_lens[scans], n_keys + 1)
    pages = (hi - 2) // KEYS_PER_PAGE - (lo - 1) // KEYS_PER_PAGE + 1
    passes = roofline.exact_range_passes(lo, hi)
    return [roofline.plan_bytes(int(p), int(q))
            for p, q in zip(pages, passes)]


# -------------------------------------------------------------- one run
class Deployment:
    """One run of a YCSB cell, for ``harness.run_cell``.

    The constructor generates the stream, builds the system and calls
    ``prepare(core, backend)`` if given (the breaks' hook); then
    ``warm_up``, ``window``, and after it ``check``, ``window_ops`` and
    ``kernel_bytes``.  ``backend`` and ``host`` (host seconds by layer:
    ``replay``, ``flush``) are what the harness reads around the window.
    """

    def __init__(self, cell, *, seed: int, seconds: float, interpret: bool,
                 trace: bool, marks: dict, prepare=None):
        self.cell, self.seed = cell, seed
        self.stream, self.n_main, self.scans = make_stream(cell, seconds,
                                                           seed)
        marks["generated"] = time.perf_counter()
        self.core, self.backend = build(cell, self.stream, seed=seed,
                                        interpret=interpret, marks=marks)
        if prepare is not None:
            prepare(self.core, self.backend)
        self.burst = cell.config["run_config"]["burst"]
        self.probe = Probe(self.core, self.backend, len(self.stream.ops),
                           trace=trace)
        self.host = self.probe.host

    def warm_up(self, log) -> int:
        """Compile every shape; returns the ops the warm-up handed."""
        self.w0 = warm_up(self.cell, self.core, self.probe, self.stream,
                          self.n_main, log, self.scans)
        return self.w0

    def window(self, deadline: float) -> float:
        """Drive the stream until ``deadline``, then drain; returns the
        host clock at which the last answer was readable."""
        clock, probe = time.perf_counter, self.probe
        probe.recording = True
        self.end = drive(self.core, probe, self.stream, self.w0, self.n_main,
                         burst=self.burst, deadline=deadline)
        t_drain = clock()
        with probe.span("bench.replay"):
            self.core.drain_inflight()
        t1 = clock()
        probe.host["replay"] += t1 - t_drain
        probe.after(t1)
        probe.recording = False
        return t1

    def check(self) -> tuple[dict, int]:
        """Read back the written records, then every answer against
        ``bench/oracle.py``: ({name: (value, limit)}, failed window ops)."""
        readback = read_back(self.core, self.stream, self.n_main, self.end,
                             self.burst, self.seed)
        return check(self.core, self.stream, self.end, readback,
                     slice(self.w0, self.end))

    def window_ops(self) -> tuple[np.ndarray, np.ndarray]:
        """(op kind, hand-off to answer in seconds) of each window op."""
        window = slice(self.w0, self.end)
        return (self.stream.ops[window].copy(),
                self.probe.t_out[window] - self.probe.t_in[window])

    def kernel_bytes(self) -> dict:
        """{device program: (launches, required bytes)} of the window's
        lookup bursts and scans (``bench/roofline.py``)."""
        stream = self.stream
        lb = [roofline.lookup_bytes(stream.key_pages[b], stream.value_pages[b])
              for b in self.probe.bursts]
        scans = np.arange(self.w0, self.end)[
            stream.ops[self.w0:self.end] == OP_SCAN]
        pb = plan_bytes(stream, scans)
        return {LOOKUP_PROGRAM: (len(lb), sum(lb)),
                PLAN_PROGRAM: (len(pb), sum(pb))}
