"""One benchmark cell, end to end.

``run_cell`` does a whole run: it generates the op stream from the seed,
bulk-loads the index into the program's ``ReplayCore`` over a
``ShardedSsdBackend``, stages every index page into the device arena,
warms up, measures a window of ``seconds``, checks every answer against
``bench/oracle.py``, and returns the result line.  Everything that belongs
to one configuration, traffic mix or metric is a file found by its name:

* ``BENCHMARK.json`` names each cell's configuration and traffic;
* ``bench/configs/<config>.json`` holds the deployment;
* ``bench/traffic/<traffic>.json`` holds the op mix for ``bench/ycsb.py``;
* ``bench/metrics/<metric>.py`` holds a ``read(run)`` that returns the
  metric's value from a :class:`Run`, or None where it has nothing to read.

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
import dataclasses
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time

import numpy as np

from bench import oracle, roofline
from bench.ycsb import (KEYS_PER_PAGE, OP_READ, OP_SCAN, OP_UPDATE, Stream,
                        generate, n_key_pages, rng_for)

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
READBACK_MAX = 4096          # acknowledged writes read back after the window


class StreamExhausted(RuntimeError):
    """The window reached the end of the generated stream."""


# ----------------------------------------------------------------- lookup
@dataclasses.dataclass
class Cell:
    name: str
    config: dict
    traffic: dict
    chips: int
    end_to_end: list
    per_layer: list


def _read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, root: str = ROOT) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json`` with its files."""
    spec = _read_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                       f"{sorted(cells)}")
    w = cells[name]
    files = {c["name"]: c["file"] for c in spec["configs"]}

    def mine(metrics):
        return [m for m in metrics if name in m.get("workloads", [name])]
    return Cell(
        name=name, chips=w["chips"],
        config=_read_json(os.path.join(root, files[w["config"]])),
        traffic=_read_json(os.path.join(BENCH_DIR, "traffic",
                                        w["traffic"] + ".json")),
        end_to_end=mine(spec["end_to_end"]), per_layer=mine(spec["per_layer"]))


def load_module(directory: str, name: str, prefix: str):
    """The module ``<directory>/<name>.py``, found by its name."""
    path = os.path.join(directory, name + ".py")
    spec = importlib.util.spec_from_file_location(
        prefix + name.replace(".", "_").replace("-", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def metric_reader(name: str, bench_dir: str = BENCH_DIR):
    """``read`` of ``bench/metrics/<name>.py``."""
    return load_module(os.path.join(bench_dir, "metrics"), name,
                       "bench_metric_").read


# ------------------------------------------------------------- the record
@dataclasses.dataclass
class Run:
    """What a metric reader may read about one run's window."""
    setup_s: float
    window_s: float
    kinds: np.ndarray            # op kind of each window op
    latency_s: np.ndarray        # hand-off to answer, per window op
    host_s: dict                 # host seconds inside each layer's calls
    counters: dict               # BackendStats deltas over the window
    lookup_launches: int
    lookup_bytes: int            # roofline.lookup_bytes over those launches
    plan_launches: int
    plan_bytes: int              # roofline.plan_bytes over those launches
    peaks: dict | None
    memory_peak_bytes: int | None
    trace: object | None = None  # trace_reduce.Reduced of a traced window

    @property
    def n_ops(self) -> int:
        return len(self.kinds)


class CompileLog:
    """Counts JAX's backend compiles (a load from the persistent cache
    counts too) while installed."""

    _EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.compiles, self.compile_s = 0, 0.0

    def _duration(self, event: str, duration_secs: float, **_) -> None:
        if event == self._EVENT:
            self.compiles += 1
            self.compile_s += duration_secs

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._duration)


# -------------------------------------------------------------- the system
def build(cell: Cell, stream: Stream, *, seed: int, interpret: bool,
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
    """The harness's clock and hooks on one replay.

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


def burst_sizes(cell: Cell) -> list[int]:
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


def warm_up(cell: Cell, core, probe: Probe, stream: Stream, stop: int,
            log: CompileLog, scans: list[tuple]) -> int:
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


def stream_length(cell: Cell, seconds: float, n_scans: int) -> int:
    """Ops generated for one run: the warm-up bursts and ``n_scans``
    scans, the longest warm-up and a window at ``stream_ops_per_s``,
    several times what the system does."""
    t = cell.traffic
    burst = cell.config["run_config"]["burst"]
    return (sum(burst_sizes(cell)) + n_scans + t["warmup"]["max_ops"]
            + t["warmup"]["chunk_ops"] + int(t["stream_ops_per_s"] * seconds)
            + burst)


# ---------------------------------------------------------------- checks
def read_back(core, stream: Stream, first: int, end: int, burst: int,
              seed: int) -> np.ndarray:
    """Drain the write buffer, then read back (a sample drawn from the
    seed of) the records written before ``end`` through the same read
    path, at positions ``first..``.  Returns those positions."""
    written = np.unique(stream.keys[:end][stream.ops[:end] == OP_UPDATE])
    if not len(written):
        return np.zeros(0, np.int64)
    if len(written) > READBACK_MAX:
        written = np.sort(rng_for(seed, 2).choice(written, READBACK_MAX,
                                                  replace=False))
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


# -------------------------------------------------------------- one run
def device_info(devices) -> dict:
    peak = [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in devices]
    peak = [p for p in peak if p is not None]
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices),
            "memory_peak_bytes": max(peak) if peak else None}


def reduce_trace(path: str):
    import glob
    from bench import trace_reduce
    files = glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one trace file, found {files}")
    return trace_reduce.reduce_file(files[0])


def run_cell(cell: Cell, *, seed: int, seconds: float, trace: bool,
             interpret: bool, t_start: float, keep_trace: str | None = None,
             prepare=None) -> tuple[dict, dict]:
    """One whole run of ``cell``.

    Returns the result line as a dict, and a dict of what ``emit`` prints
    before it: the set-up's phases in seconds, the compiles inside the
    window with their seconds, and latency percentiles by op kind.
    ``prepare(core, backend)``, if given, runs after the bulk load and
    before the warm-up (the fault tests break the path there)."""
    import jax
    clock = time.perf_counter
    scans = []
    if cell.traffic["scan_proportion"]:
        scans = warmup_scans(cell.traffic, n_key_pages(
            cell.config["records"]) * KEYS_PER_PAGE, seed)
    n_main = stream_length(cell, seconds, len(scans))
    stream = generate(n_main + READBACK_MAX, cell.traffic,
                      records=cell.config["records"], seed=seed)
    marks = {"generated": clock()}
    core, backend = build(cell, stream, seed=seed, interpret=interpret,
                          marks=marks)
    if prepare is not None:
        prepare(core, backend)
    burst = cell.config["run_config"]["burst"]
    probe = Probe(core, backend, len(stream.ops), trace=trace)
    with CompileLog() as log:
        w0 = warm_up(cell, core, probe, stream, n_main, log, scans)
        warm = (w0, log.compiles, log.compile_s)
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
        if trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        stats0 = dataclasses.asdict(backend.stats)
        host0 = dict(probe.host)
        compiles0 = (log.compiles, log.compile_s)
        probe.recording = True
        load = os.getloadavg()[0]
        with probe.span("bench.window"):
            cpu0 = time.process_time()
            t0 = clock()
            end = drive(core, probe, stream, w0, n_main, burst=burst,
                        deadline=t0 + seconds)
            t_drain = clock()
            with probe.span("bench.replay"):
                core.drain_inflight()
            t1 = clock()
            cpu1 = time.process_time()
        probe.host["replay"] += t1 - t_drain
        probe.after(t1)
        probe.recording = False
        window_compiles = (log.compiles - compiles0[0],
                           log.compile_s - compiles0[1])
        if trace:
            jax.profiler.stop_trace()
    dev = device_info(jax.devices())
    counters = {k: v - stats0[k]
                for k, v in dataclasses.asdict(backend.stats).items()}
    host = {k: v - host0[k] for k, v in probe.host.items()}

    readback = read_back(core, stream, n_main, end, burst, seed)
    window = slice(w0, end)
    checks, failed = check(core, stream, end, readback, window)

    kinds = stream.ops[window].copy()
    latency = probe.t_out[window] - probe.t_in[window]
    lb = [roofline.lookup_bytes(stream.key_pages[b], stream.value_pages[b])
          for b in probe.bursts]
    scans = np.arange(w0, end)[kinds == OP_SCAN]
    pb = plan_bytes(stream, scans)
    reduced = None
    if trace:
        reduced = reduce_trace(trace_dir)
        if keep_trace:
            shutil.copytree(trace_dir, keep_trace, dirs_exist_ok=True)
        shutil.rmtree(trace_dir, ignore_errors=True)
    run = Run(setup_s=t0 - t_start, window_s=t1 - t0, kinds=kinds,
              latency_s=latency, host_s=host, counters=counters,
              lookup_launches=len(lb), lookup_bytes=sum(lb),
              plan_launches=len(pb), plan_bytes=sum(pb),
              peaks=roofline.peaks(dev["kind"]) if trace else None,
              memory_peak_bytes=dev["memory_peak_bytes"], trace=reduced)
    specs = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for m in specs:
        value = metric_reader(m["name"])(run)
        if value is not None and math.isfinite(value):
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    if trace:
        dev["busy_s"] = reduced.busy_s
        dev["window_s"] = reduced.window_s
    line = {"correct": all(v <= lim for v, lim in checks.values())
            and not np.isnan(latency).any(),
            "attempted": end - w0, "failed": failed, "metrics": metrics,
            "device": dev}
    if trace:
        line["breakdown"] = reduced.breakdown()
    line["checks"] = {k: {"value": v, "limit": lim}
                      for k, (v, lim) in checks.items()}
    setup = {"start_to_generated": marks["generated"] - t_start,
             "bulk_load": marks["loaded"] - marks["generated"],
             "staging": marks["staged"] - marks["loaded"],
             "warm_up": t0 - marks["staged"], "warm_up_ops": warm[0],
             "warm_up_compiles": warm[1], "warm_up_compile_s": warm[2]}
    return line, {"setup": setup, "window_compiles": window_compiles,
                  "window": {"wall_s": t1 - t0, "process_cpu_s": cpu1 - cpu0,
                             "loadavg_1m_before": load},
                  "latency_ms": latency_by_kind(kinds, latency)}


def latency_by_kind(kinds: np.ndarray, latency: np.ndarray) -> dict:
    """{kind: (count, p50, p90, p95, p99 in ms)} over the window's ops."""
    out = {}
    for kind, label in ((OP_READ, "read"), (OP_UPDATE, "update"),
                        (OP_SCAN, "scan")):
        lat = latency[kinds == kind]
        if len(lat):
            out[label] = (len(lat), *(float(np.percentile(lat, q)) * 1e3
                                      for q in (50, 90, 95, 99)))
    return out


def plan_bytes(stream: Stream, scans: np.ndarray) -> list[int]:
    """Required bytes of each scan's one plan launch."""
    n_keys = oracle.n_keys_of(stream.n_index_pages // 2)
    lo = stream.keys[scans] + 1
    hi = np.minimum(lo + stream.scan_lens[scans], n_keys + 1)
    pages = (hi - 2) // KEYS_PER_PAGE - (lo - 1) // KEYS_PER_PAGE + 1
    passes = roofline.exact_range_passes(lo, hi)
    return [roofline.plan_bytes(int(p), int(q))
            for p, q in zip(pages, passes)]


def emit(line: dict, info: dict, out=None, err=None) -> None:
    """Print a result: the set-up's phases and the window's compiles
    first, the compared numbers as the last lines of standard error, the
    JSON line last on standard output."""
    out, err = out or sys.stdout, err or sys.stderr
    print("setup: " + ", ".join(f"{k} {v}" for k, v in info["setup"].items()),
          file=out)
    for kind, (n, p50, p90, p95, p99) in info.get("latency_ms", {}).items():
        print(f"latency {kind}: n {n}, p50 {p50} ms, p90 {p90} ms, "
              f"p95 {p95} ms, p99 {p99} ms", file=out)
    compiles, compile_s = info["window_compiles"]
    print(f"window compiles: {compiles} ({compile_s} s)", file=out)
    if "window" in info:
        print("window: " + ", ".join(f"{k} {v}" for k, v in
                                     info["window"].items()), file=out)
    for name, c in line["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})", file=err)
    err.flush()
    print(json.dumps(line), file=out, flush=True)
