#!/usr/bin/env python3
"""Run one cell traced and report the program's own spans.

    python3 bench/span_report.py --workload <cell> --seed <n> --seconds <s>

It runs the cell as ``bench/run.py --trace 1`` does (with
``launched_rows_per_page`` added to the cell's per-layer metrics) and
prints that result line.  Then it reduces the same trace with
``bench/span_reduce.py`` and prints one more JSON line: the span metrics
of ``bench/metrics/``, each ``sim.*`` span's count, total and self
seconds, the device's idle seconds by innermost ``bench.*``/``sim.*``
span, and three checks of the spans against the harness's own clocks.
It needs a TPU, as ``bench/run.py`` does.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from types import SimpleNamespace  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
SPAN_METRICS = {"flush_place_us_per_op": "us/op",
                "flush_operands_us_per_op": "us/op",
                "flush_launch_us_per_op": "us/op",
                "flush_account_us_per_op": "us/op",
                "flush_program_us_per_op": "us/op",
                "stage_host_us_per_op": "us/op",
                "tail_host_us_per_op": "us/op",
                "read_drain_lag_ms": "ms"}
COUNTER_METRIC = {"name": "launched_rows_per_page", "unit": "rows/page"}


def report(spans, line: dict) -> dict:
    """The span metrics, tables and checks for a run's result ``line``."""
    from bench import harness
    run = SimpleNamespace(spans=spans, n_ops=line["attempted"])
    metrics = {}
    for name, unit in SPAN_METRICS.items():
        value = harness.metric_reader(name)(run)
        if value is not None:
            metrics[name] = {"value": value, "unit": unit}
    flush_ns = spans.total_ns.get("sim.flush", 0.0)
    outside = {k: v["value"] for k, v in line["metrics"].items()}
    tail = metrics.get("tail_host_us_per_op", {}).get("value")
    checks = {"unpaired_lookup_tails": spans.unpaired_tails}
    if flush_ns:
        checks["flush_self_share"] = spans.self_ns.get("sim.flush", 0.0) \
            / flush_ns
        if "flush_host_us_per_op" in outside:
            checks["flush_inside_over_outside"] = \
                flush_ns / run.n_ops / 1e3 / outside["flush_host_us_per_op"]
    if tail is not None and "replay_self_us_per_op" in outside:
        checks["tail_within_replay_self"] = \
            tail <= outside["replay_self_us_per_op"]
    return {"span_metrics": metrics,
            "spans": {k: [spans.count[k], spans.total_ns[k] / 1e9,
                          spans.self_ns.get(k, 0.0) / 1e9]
                      for k in sorted(spans.count)},
            "breakdown": spans.breakdown(), "checks": checks}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    if sys.path and os.path.abspath(sys.path[0]) == HERE:
        sys.path.pop(0)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax
    from bench import harness, span_reduce

    if jax.devices()[0].platform != "tpu":
        print(f"span_report.py: needs a TPU, JAX found "
              f"{jax.devices()[0].platform!r}", file=sys.stderr)
        return 1
    from repro.kernels import enable_compile_cache
    enable_compile_cache()
    cell = harness.load_cell(args.workload)
    cell = dataclasses.replace(cell,
                               per_layer=cell.per_layer + [COUNTER_METRIC])
    keep = tempfile.mkdtemp(prefix="span_report_")
    try:
        line, info = harness.run_cell(
            cell, seed=args.seed, seconds=args.seconds, trace=True,
            interpret=False, t_start=T_START, keep_trace=keep)
        harness.emit(line, info)
        (path,) = glob.glob(os.path.join(keep, "**", "*.xplane.pb"),
                            recursive=True)
        spans = span_reduce.reduce_file(path)
    finally:
        shutil.rmtree(keep, ignore_errors=True)
    print(json.dumps(report(spans, line)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
