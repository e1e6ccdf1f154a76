#!/usr/bin/env python3
"""Smoke run of the SiM replay path on one TPU chip.

Drives ``repro.frontend.replay`` -> ``ShardedSsdBackend`` -> the Pallas
match kernels over a YCSB-sized index and holds every answer to a plain
numpy oracle.  Four phases run in this one process, each on a fresh
backend:

  ycsb_c  read only, fused lookups                    (sim_lookup)
  ycsb_a  50/50 read/update through the write buffer  (sim_lookup, restaging)
  ycsb_e  range scans as Op.PLAN plans                (sim_plan)
  split   search, then gather                         (sim_search, sim_gather)

Data follows YCSB's core workload: 10,000,000 records of 8-byte keys and
8-byte values in the §V-A two-page leaf (504 keys per 4 KiB page), and a
zipfian request distribution with constant 0.99.  Every key and value page
is staged into the device-resident PlaneStore arena before a phase's first
burst.  The geometry is the 8-channel SSD of the event benchmarks.

Run it from the root of a checkout:

    python chip_smoke.py

It exits non-zero, and prints no result, when JAX finds no TPU.  Each phase
prints its wall times, counters and mismatches; the last line is one JSON
object naming the device.
"""
from __future__ import annotations

import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

RECORDS = 10_000_000
ZIPF_CONSTANT = 0.99           # YCSB's zipfian request distribution
CHANNELS = 8
BURST = 64                     # RunConfig's default read burst
OPS = 4096
SCAN_OPS = 2048
MAX_SCAN_LEN = 100             # YCSB-E maxscanlength, uniform lengths
SEED = 7
PASSES = 16                    # bound on exact_range passes for <=100 keys
PHI64 = 0x9E3779B97F4A7C15     # initial value of key k: ((k+1)*PHI64) | 1


def phases():
    """name -> (generate() arguments, RunConfig) for the four phases.  A
    healthy fault schedule arms the failover path, so the run can show
    that no op was served host-side."""
    from repro.frontend import RunConfig
    from repro.reliability import FaultSchedule
    healthy = FaultSchedule.healthy(seed=SEED)
    return {
        "ycsb_c": (dict(n_queries=OPS, read_ratio=1.0),
                   RunConfig(fused=True, faults=healthy)),
        "ycsb_a": (dict(n_queries=OPS, read_ratio=0.5),
                   RunConfig.buffered(fused=True, faults=healthy)),
        "ycsb_e": (dict(n_queries=SCAN_OPS, read_ratio=0.05,
                        scan_ratio=0.95, max_scan_len=MAX_SCAN_LEN),
                   RunConfig(fused=True, faults=healthy)),
        "split": (dict(n_queries=OPS, read_ratio=1.0),
                  RunConfig(fused=False, faults=healthy)),
    }


def expected_reads(wl):
    """What every read must return: the loaded value of its key, or the
    tag ``qi * 2 + 1`` of the last write to that key before it in stream
    order."""
    exp = ((wl.keys.astype(np.uint64) + np.uint64(1)) * np.uint64(PHI64)) \
        | np.uint64(1)
    last: dict[int, int] = {}
    for qi in range(len(wl.ops)):
        k = int(wl.keys[qi])
        if wl.ops[qi] == 1:
            last[k] = qi
        elif wl.ops[qi] == 0 and k in last:
            exp[qi] = np.uint64(last[k] * 2 + 1)
    return exp


def expected_scan_counts(wl, n_keys: int):
    """Stored keys (1..n_keys) inside each scan's [k + 1, k + 1 + len)."""
    stored = np.arange(1, n_keys + 1, dtype=np.int64)
    lo = wl.keys.astype(np.int64) + 1
    hi = lo + wl.scan_lens
    return np.searchsorted(stored, hi) - np.searchsorted(stored, lo)


class CompileLog:
    """Counts JAX's backend compiles and the seconds they took, and its
    persistent-cache hits.  A compile served from the cache counts too,
    with the time it took to load."""

    def __init__(self):
        self.compiles, self.compile_s, self.cache_hits = 0, 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration_secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += duration_secs

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self) -> dict:
        return {"compiles": self.compiles, "compile_s": self.compile_s,
                "cache_hits": self.cache_hits}


class FirstFlush:
    """Wraps a backend's ``flush``.  At the replay's first flush, which
    comes right after its bulk load, it stages every index page into the
    device arena, then times that flush: trace, compile and dispatch of
    the first burst."""

    def __init__(self, backend, n_pages: int):
        self.backend, self.n_pages = backend, n_pages
        self.inner = backend.flush
        self.marks: dict[str, float] = {}
        backend.flush = self

    def __call__(self) -> None:
        if self.marks:
            return self.inner()
        self.marks["loaded"] = time.perf_counter()
        store = self.backend.store
        store.stage_group(range(self.n_pages))
        jax.block_until_ready(store.take(np.zeros(1, np.int32), 1))
        if store.resident_rows != self.n_pages:
            raise AssertionError(f"{store.resident_rows} resident rows, "
                                 f"index has {self.n_pages} pages")
        self.marks["staged"] = time.perf_counter()
        self.inner()
        self.marks["first"] = time.perf_counter()


def run_phase(name, gen_kw, config, *, records: int, interpret: bool):
    """Replay one phase on a fresh backend; returns its summary dict.
    Raises on any mismatch or any op not served by the kernels."""
    from repro.backend.sharded import ShardedSsdBackend
    from repro.core.bits import PAGE_BYTES
    from repro.frontend import replay
    from repro.workload.ycsb import KEYS_PER_PAGE, generate

    n_key_pages = -(-records // KEYS_PER_PAGE)
    t0 = time.perf_counter()
    wl = generate(n_key_pages=n_key_pages, alpha=ZIPF_CONSTANT, seed=SEED,
                  **gen_kw)
    backend = ShardedSsdBackend.from_geometry(
        channels=CHANNELS, dies_per_channel=1,
        pages_per_chip=-(-wl.n_index_pages // CHANNELS),
        timeline=True, use_kernel=True, interpret=interpret)
    clock = FirstFlush(backend, wl.n_index_pages)
    t_gen = time.perf_counter()
    rep = replay(wl, backend, config)
    t_end = time.perf_counter()

    is_read, is_scan = wl.ops == 0, wl.ops == 2
    read_bad = is_read & ((rep.read_values != expected_reads(wl))
                          | ~rep.read_hits)
    mismatches = int(read_bad.sum())
    if is_scan.any():
        scan_exp = expected_scan_counts(wl, n_key_pages * KEYS_PER_PAGE)
        mismatches += int((rep.scan_counts[is_scan]
                           != scan_exp[is_scan]).sum())
    c, f, m = rep.counters, rep.faults, clock.marks
    out = {
        "phase": name, "ops": len(wl.ops), "reads": c.reads,
        "writes": c.writes, "scans": c.scans,
        "resident_rows": backend.store.resident_rows,
        "resident_bytes": backend.store.resident_rows * PAGE_BYTES,
        "gen_s": t_gen - t0, "load_s": m["loaded"] - t_gen,
        "stage_s": m["staged"] - m["loaded"],
        "first_burst_s": m["first"] - m["staged"],
        "rest_s": t_end - m["first"],
        "flushes": c.flushes, "kernel_launches": c.kernel_launches,
        "write_flushes": c.write_flushes,
        "read_errors": rep.reliability.n_read_errors,
        "op_errors": f.n_op_errors, "degraded_ops": f.degraded_ops,
        "failovers": f.failovers, "mismatches": mismatches,
    }
    problems = [k for k in ("mismatches", "read_errors", "op_errors",
                            "degraded_ops", "failovers") if out[k]]
    if c.kernel_launches != c.flushes:
        problems.append("kernel_launches != flushes")
    if problems:
        raise AssertionError(f"phase {name}: {problems}: {out}")
    return out


def main_path_launches(sharding=None):
    """name -> zero-argument callable lowering one main-path launch with
    ``interpret=False``, at the largest shapes the phases send it (a burst
    of 64 reads all on one chip, scans of up to 100 keys).  ``sharding``
    places the operands on a device, for example one of a described
    topology."""
    from repro.backend.sharded import _stacked_plan, _stacked_search
    from repro.kernels.sim_fused.sim_fused import sim_lookup_kernel
    from repro.kernels.sim_gather.sim_gather import sim_gather_kernel

    def u32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.uint32, sharding=sharding)

    c, n, p = CHANNELS, BURST, PASSES
    block = dict(page_block=8, use_kernel=True, interpret=False)
    return {
        "sim_search": lambda: _stacked_search.lower(
            u32(c, n, 512), u32(c, n, 512), u32(c, n, 2), u32(c, n, 2),
            u32(c, n), u32(c, n), **block),
        "sim_plan": lambda: _stacked_plan.lower(
            u32(c, n, 512), u32(c, n, 512), u32(c, 1, p, 2),
            u32(c, 1, p, 2), u32(c, 1, p), u32(c, n), u32(c, n), **block),
        "sim_lookup": lambda: sim_lookup_kernel.lower(
            u32(n, 512), u32(n, 512), u32(n, 512), u32(n, 512), u32(n, 2),
            u32(n, 2), u32(n), u32(n), row_block=8, randomized=True,
            interpret=False),
        "sim_gather": lambda: sim_gather_kernel.lower(
            u32(n, 64, 16), u32(n, 2), page_block=8, max_out=64,
            interpret=False),
    }


def main() -> int:
    src = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print("chip_smoke.py: no src/repro beside this script; run it from "
              "a checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, src)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke.py: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 1
    from repro.kernels import enable_compile_cache
    cache = enable_compile_cache()
    log = CompileLog()

    print(f"jax {jax.__version__}, device {dev.platform} {dev.device_kind}"
          f" x{len(jax.devices())}, compile cache {cache}", flush=True)
    for name, lower in main_path_launches().items():
        text = lower().compile().as_text()
        if "tpu_custom_call" not in text:
            raise AssertionError(f"{name}: no tpu_custom_call in its "
                                 "compiled text")
        print(f"launch {name}: tpu_custom_call in compiled text", flush=True)

    for name, (gen_kw, config) in phases().items():
        before = log.snapshot()
        out = run_phase(name, gen_kw, config, records=RECORDS,
                        interpret=False)
        out.update({k: v - before[k] for k, v in log.snapshot().items()})
        stats = dev.memory_stats() or {}
        out["peak_bytes_in_use"] = stats.get("peak_bytes_in_use",
                                             "not reported")
        print(" ".join(f"{k}={v}" for k, v in out.items()), flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
