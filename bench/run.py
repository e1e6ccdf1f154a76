#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run it from the root of a checkout: the program under test is ``src/repro``
beside this directory, and the cells are those of ``BENCHMARK.json``.  It
exits non-zero, and prints no result, when JAX finds no TPU or fewer chips
than the cell asks for, or when the checkout holds no program.

With ``--trace 0`` the result line carries the cell's end-to-end metrics;
with ``--trace 1`` the window runs under the profiler and the line carries
its per-layer metrics, the device's busy time and the trace's breakdown.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print(f"run.py: no src/repro in {ROOT}; run it from a checkout of "
              "the repository", file=sys.stderr)
        return 2
    if sys.path and os.path.abspath(sys.path[0]) == HERE:
        sys.path.pop(0)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax
    from bench import harness

    cell = harness.load_cell(args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"run.py: needs a TPU, JAX found {devices[0].platform!r}",
              file=sys.stderr)
        return 1
    if len(devices) < cell.chips:
        print(f"run.py: {cell.name} needs {cell.chips} chips, JAX found "
              f"{len(devices)}", file=sys.stderr)
        return 1
    from repro.kernels import enable_compile_cache
    cache = enable_compile_cache()
    print(f"jax {jax.__version__}, {devices[0].device_kind} "
          f"x{len(devices)}, compile cache {cache}", flush=True)
    line, info = harness.run_cell(
        cell, seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
        interpret=False, t_start=T_START)
    harness.emit(line, info)
    return 0


if __name__ == "__main__":
    sys.exit(main())
