#!/usr/bin/env python3
"""Breaks of the timed path that the benchmark's check must catch.

Each break is a file ``bench/breaks/<name>.py`` with a ``KIND`` and a
context manager ``apply()`` that patches the program for one run and
yields an optional ``prepare(core, backend)`` hook for ``run_cell``.  Two
kinds:

* a control (``KIND = "control"``): the reference semantics with one
  guarantee the configuration states broken, as a later change might be
  tempted to break it for speed.  Each configuration names its control
  under ``"control"`` in its file;
* a fault (``KIND = "fault"``): a path that skips or alters work.  Its
  ``applies(cell)`` says whether a cell can have it.

A new configuration names a control; a new guarantee or fault is a new
file.  The benchmark's own runs never use this module.  Run a cell's
control on the chip at the cell's size, several seeds in one process:

    python3 bench/control.py --workload ycsb_c-10m --seconds 5 --seeds 1 2 3

It prints one line per seed with the compared numbers and ``correct``; a
sound control reads ``correct: false`` on every seed.
"""
from __future__ import annotations

import argparse
import contextlib
import os
import sys
import time

BREAKS_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "breaks")


@contextlib.contextmanager
def patched(module, name, value):
    """``module.name`` set to ``value`` while the context is open."""
    old = getattr(module, name)
    setattr(module, name, value)
    try:
        yield
    finally:
        setattr(module, name, old)


def load_break(name: str):
    """The module ``bench/breaks/<name>.py``."""
    from bench.harness import load_module
    return load_module(BREAKS_DIR, name, "bench_break_")


def control_of(cell):
    """The control that the cell's configuration names."""
    brk = load_break(cell.config["control"])
    if brk.KIND != "control":
        raise ValueError(f"{cell.config['control']!r} is not a control")
    return brk


def faults_of(cell) -> list[str]:
    """Names of the faults the cell can have."""
    names = sorted(f[:-3] for f in os.listdir(BREAKS_DIR)
                   if f.endswith(".py") and not f.startswith("_"))
    return [n for n in names
            if (m := load_break(n)).KIND == "fault" and m.applies(cell)]


def run_broken(cell, brk, *, seed: int, seconds: float, interpret: bool,
               t_start: float):
    """One run of ``cell`` under the break module ``brk``; returns
    run_cell's."""
    from bench import harness
    with brk.apply() as prepare:
        return harness.run_cell(cell, seed=seed, seconds=seconds,
                                trace=False, interpret=interpret,
                                t_start=t_start, prepare=prepare)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="run a cell's control")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    here = os.path.dirname(os.path.abspath(__file__))
    root = os.path.dirname(here)
    if sys.path and os.path.abspath(sys.path[0]) == here:
        sys.path.pop(0)
    sys.path[:0] = [root, os.path.join(root, "src")]
    import jax
    if jax.devices()[0].platform != "tpu":
        print("control.py: needs a TPU", file=sys.stderr)
        return 1
    from bench import harness
    from repro.kernels import enable_compile_cache
    enable_compile_cache()
    cell = harness.load_cell(args.workload)
    brk = control_of(cell)
    for seed in args.seeds:
        line, _ = run_broken(cell, brk, seed=seed, seconds=args.seconds,
                             interpret=False, t_start=time.perf_counter())
        checks = {k: c["value"] for k, c in line["checks"].items()}
        print(f"{args.workload} {cell.config['control']} seed={seed} "
              f"correct={line['correct']} attempted={line['attempted']} "
              f"failed={line['failed']} {checks}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
