#!/usr/bin/env python3
"""Record ``tiny.xplane.pb``, the trace that bench/tests/test_bench_trace_reduce.py
checks the reduction against.  It needs a TPU:

    python3 bench/testdata/record_tiny_trace.py <out_dir>

It traces a short window of a tiny mixed cell (64 key pages, bursts of 8,
reads, updates through the write buffer and scans), so the trace holds
lookup and plan kernels, arena scatters and every harness span, and keeps
the trace directory at ``out_dir``; copy its ``.xplane.pb`` here.
"""
import copy
import dataclasses
import os
import sys
import time

T_START = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main(out_dir: str) -> int:
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax
    if jax.devices()[0].platform != "tpu":
        print("record_tiny_trace.py: needs a TPU", file=sys.stderr)
        return 1
    from bench import harness
    from repro.kernels import enable_compile_cache
    enable_compile_cache()
    cell = harness.load_cell("ycsb_a-10m-wb")
    config = copy.deepcopy(cell.config)
    config["records"] = 64 * 504
    config["run_config"]["burst"] = 8
    traffic = dict(cell.traffic, read_proportion=0.6, update_proportion=0.3,
                   scan_proportion=0.1, max_scan_length=20,
                   stream_ops_per_s=20000,
                   warmup={"chunk_ops": 64, "min_ops": 256, "quiet_ops": 256,
                           "max_ops": 4096})
    plan = [m for m in harness.load_cell("ycsb_e-10m").per_layer
            if m["name"] == "sim_plan_roofline"]
    cell = dataclasses.replace(cell, config=config, traffic=traffic,
                               per_layer=cell.per_layer + plan)
    line, info = harness.run_cell(cell, seed=77, seconds=0.3, trace=True,
                                  interpret=False, t_start=T_START,
                                  keep_trace=out_dir)
    harness.emit(line, info)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
