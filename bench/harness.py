"""One benchmark cell, end to end.

``run_cell`` does a whole run: set-up, warm-up, a measured window of
``seconds``, the check of every answer, and the result line.  What is the
same for every deployment is here: the clock from process start, the
``bench.window`` span, the count of compiles, the profiler trace and its
reduction, the backend's counters over the window, and the metrics and the
line.  Everything that belongs to one configuration, traffic mix, metric or
deployment family is a file found by its name:

* ``BENCHMARK.json`` names each cell's configuration and traffic;
* ``bench/configs/<config>.json`` holds the deployment, and its
  ``"family"`` names the module that runs it;
* ``bench/families/<family>.py`` holds a ``Deployment`` class (one object
  per run: the data from the seed, the system, the warm-up, the window's
  loop, the checks against the family's own reference, the kernels' bytes)
  and ``KIND_LABELS``, ``(kind, label)`` pairs of its ops;
* ``bench/traffic/<traffic>.json`` holds the mix the family reads;
* ``bench/metrics/<metric>.py`` holds a ``read(run)`` that returns the
  metric's value from a :class:`Run`, or None where it has nothing to read.
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

from bench import roofline

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


# ----------------------------------------------------------------- lookup
@dataclasses.dataclass
class Cell:
    name: str
    config: dict
    traffic: dict
    chips: int
    end_to_end: list
    per_layer: list
    family: object               # the module bench/families/<family>.py
    bench_dir: str               # where its traffic, family and metrics are


def _read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, root: str = ROOT) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json`` with its files, which
    lie under ``<root>/bench``."""
    spec = _read_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                       f"{sorted(cells)}")
    w = cells[name]
    files = {c["name"]: c["file"] for c in spec["configs"]}
    bench_dir = os.path.join(root, "bench")
    config = _read_json(os.path.join(root, files[w["config"]]))

    def mine(metrics):
        return [m for m in metrics if name in m.get("workloads", [name])]
    return Cell(
        name=name, chips=w["chips"], config=config,
        traffic=_read_json(os.path.join(bench_dir, "traffic",
                                        w["traffic"] + ".json")),
        end_to_end=mine(spec["end_to_end"]), per_layer=mine(spec["per_layer"]),
        family=load_family(config, bench_dir), bench_dir=bench_dir)


def load_family(config: dict, bench_dir: str):
    """The module ``bench/families/<family>.py`` that ``config`` names."""
    directory = os.path.join(bench_dir, "families")
    known = sorted(f[:-3] for f in os.listdir(directory)
                   if f.endswith(".py") and not f.startswith("_"))
    family = config.get("family")
    if family not in known:
        raise ValueError(f"configuration {config.get('name')!r} names "
                         f"family {family!r}; known families: {known}")
    return load_module(directory, family, "bench_family_")


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
    kernel_bytes: dict           # device program -> (launches, required
                                 # bytes by bench/roofline.py) in the window
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
    ``prepare(core, backend)``, if given, is handed to the family, which
    calls it after the bulk load and before the warm-up (the fault tests
    break the path there)."""
    import jax
    clock = time.perf_counter
    span = jax.profiler.TraceAnnotation if trace else contextlib.nullcontext
    marks = {}
    deploy = cell.family.Deployment(cell, seed=seed, seconds=seconds,
                                    interpret=interpret, trace=trace,
                                    marks=marks, prepare=prepare)
    with CompileLog() as log:
        warm = (deploy.warm_up(log), log.compiles, log.compile_s)
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
        if trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        stats0 = dataclasses.asdict(deploy.backend.stats)
        host0 = dict(deploy.host)
        compiles0 = (log.compiles, log.compile_s)
        load = os.getloadavg()[0]
        with span("bench.window"):
            cpu0 = time.process_time()
            t0 = clock()
            t1 = deploy.window(t0 + seconds)
            cpu1 = time.process_time()
        window_compiles = (log.compiles - compiles0[0],
                           log.compile_s - compiles0[1])
        if trace:
            jax.profiler.stop_trace()
    dev = device_info(jax.devices())
    counters = {k: v - stats0[k]
                for k, v in dataclasses.asdict(deploy.backend.stats).items()}
    host = {k: v - host0[k] for k, v in deploy.host.items()}

    checks, failed = deploy.check()
    kinds, latency = deploy.window_ops()
    kernel_bytes = deploy.kernel_bytes()
    reduced = None
    if trace:
        reduced = reduce_trace(trace_dir)
        if keep_trace:
            shutil.copytree(trace_dir, keep_trace, dirs_exist_ok=True)
        shutil.rmtree(trace_dir, ignore_errors=True)
    run = Run(setup_s=t0 - t_start, window_s=t1 - t0, kinds=kinds,
              latency_s=latency, host_s=host, counters=counters,
              kernel_bytes=kernel_bytes,
              peaks=roofline.peaks(dev["kind"]) if trace else None,
              memory_peak_bytes=dev["memory_peak_bytes"], trace=reduced)
    specs = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for m in specs:
        value = metric_reader(m["name"], cell.bench_dir)(run)
        if value is not None and math.isfinite(value):
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    if trace:
        dev["busy_s"] = reduced.busy_s
        dev["window_s"] = reduced.window_s
    line = {"correct": all(v <= lim for v, lim in checks.values())
            and not np.isnan(latency).any(),
            "attempted": run.n_ops, "failed": failed, "metrics": metrics,
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
                  "latency_ms": latency_by_kind(
                      kinds, latency, cell.family.KIND_LABELS)}


def latency_by_kind(kinds: np.ndarray, latency: np.ndarray,
                    labels) -> dict:
    """{label: (count, p50, p90, p95, p99 in ms)} over the window's ops,
    for each ``(kind, label)`` of ``labels``."""
    out = {}
    for kind, label in labels:
        lat = latency[kinds == kind]
        if len(lat):
            out[label] = (len(lat), *(float(np.percentile(lat, q)) * 1e3
                                      for q in (50, 90, 95, 99)))
    return out


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
