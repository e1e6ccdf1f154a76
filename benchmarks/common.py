"""Shared helpers for the paper-figure benchmarks."""
from __future__ import annotations

import json
import os
import time

from repro.backend import make_backend
from repro.core.engine import SimChipArray
from repro.flash.params import DEFAULT_PARAMS
from repro.frontend import RunConfig, RunReport, replay
from repro.kernels import enable_compile_cache
from repro.workload.runner import run
from repro.workload.ycsb import generate

# Every benchmark imports this module before its first compile.
enable_compile_cache()

# Paper grids (§VI-A4/A5, §VII)
COVERAGES = (0.0, 0.10, 0.25, 0.50, 0.75)
READ_RATIOS = (1.0, 0.8, 0.6, 0.4, 0.2)
DISTRIBUTIONS = (("uniform", 0.0), ("skewed", 0.5), ("very_skewed", 0.9))

# Simulation scale (queries per grid point).  Small enough for the full
# grid to run in ~a minute; pass --scale N to benchmarks.run to multiply.
N_QUERIES = 4000
N_KEY_PAGES = 1024

# Event-frontend scale: the functional executor programs real pages, so
# the keyspace is smaller than the closed-form grid's (which never
# materializes data).  Geometry mirrors the paper's 8-channel device.
EVENT_N_QUERIES = 1200
EVENT_N_KEY_PAGES = 32
EVENT_N_CHIPS = 8


def run_pair(read_ratio: float, alpha: float, coverage: float, *,
             n_queries: int = N_QUERIES, seed: int = 1,
             **kw) -> tuple[RunReport, RunReport]:
    """Closed-form analytic baseline-vs-SiM pair (the reference series)."""
    wl = generate(n_queries, n_key_pages=N_KEY_PAGES, read_ratio=read_ratio,
                  alpha=alpha, seed=seed)
    base = run(wl, params=DEFAULT_PARAMS, system="baseline",
               cache_coverage=coverage, **{k: v for k, v in kw.items()
                                           if k != "full_page_read_ratio"})
    sim = run(wl, params=DEFAULT_PARAMS, system="sim",
              cache_coverage=coverage, **kw)
    return base, sim


def run_event(read_ratio: float, alpha: float, *,
              n_queries: int = EVENT_N_QUERIES, seed: int = 1,
              qps: float = 3e5, scheduler: str = "read_priority",
              concurrency: int = 8, write_high_water: int = 16,
              **kw) -> RunReport:
    """Measured event-frontend run: the op stream replayed against real
    programmed pages under Poisson arrivals, NCQ admission and the given
    scheduler — per-request latency distributions rather than the
    closed-form model's per-op service times."""
    wl = generate(n_queries, n_key_pages=EVENT_N_KEY_PAGES,
                  read_ratio=read_ratio, alpha=alpha, seed=seed)
    arr = SimChipArray(
        n_chips=EVENT_N_CHIPS,
        pages_per_chip=max(wl.n_index_pages // EVENT_N_CHIPS + 1, 8),
        device_seed=7)
    cfg = RunConfig.open_loop(qps, concurrency=concurrency,
                              scheduler=scheduler, burst=64,
                              write_buffer=True,
                              write_high_water=write_high_water,
                              seed=seed, **kw)
    return replay(wl, make_backend("scalar", arr), cfg)


class Timer:
    def __enter__(self):
        self.t0 = time.perf_counter()
        self._end = None
        return self

    def __exit__(self, *a):
        self._end = time.perf_counter()

    @property
    def elapsed_us(self) -> float:
        end = self._end if self._end is not None else time.perf_counter()
        return (end - self.t0) * 1e6


_METRICS: list[dict] = []


def emit(name: str, value: float, derived: str) -> None:
    """Print a metric row and record it for ``write_bench_json``.

    ``value`` is microseconds per call for timing metrics, raw units
    (e.g. bytes) for the few counter metrics — the ``derived`` tag says
    which.
    """
    _METRICS.append({"name": name, "value": round(float(value), 2),
                     "derived": derived})
    print(f"{name},{value:.2f},{derived}")


def write_bench_json(bench_name: str, path: str | None = None) -> str:
    """Persist every metric emitted so far as ``BENCH_<name>.json``.

    CI uploads these files as build artifacts so the perf trajectory
    accumulates across commits.  The default output directory is
    ``benchmarks/`` (next to the committed baselines), independent of the
    caller's cwd; ``BENCH_JSON_DIR`` overrides it.
    """
    out_dir = os.environ.get("BENCH_JSON_DIR") \
        or os.path.dirname(os.path.abspath(__file__))
    path = path or os.path.join(out_dir, f"BENCH_{bench_name}.json")
    with open(path, "w") as f:
        json.dump({"bench": bench_name, "metrics": _METRICS}, f, indent=2)
    print(f"wrote {len(_METRICS)} metrics -> {path}")
    return path
