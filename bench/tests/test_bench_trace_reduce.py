"""bench/trace_reduce.py on a hand-built trace and on one recorded on a
TPU v5e (a tiny traced run of the harness, kept in bench/testdata)."""
from __future__ import annotations

import os
from types import SimpleNamespace as NS

import pytest

from bench import trace_reduce

FIXTURE = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "testdata", "tiny.xplane.pb")


def ev(name, start, end):
    return NS(name=name, start_ns=float(start), end_ns=float(end),
              duration_ns=float(end - start))


def profile(device_modules, device_ops, host_spans):
    device = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[ev(*m) for m in device_modules]),
        NS(name="XLA Ops", events=[ev(*o) for o in device_ops])])
    host = NS(name="/host:CPU", lines=[
        NS(name="python", events=[ev(*s) for s in host_spans]),
        NS(name="other", events=[ev("PjitFunction(x)", 0, 500)])])
    return NS(planes=[NS(name="/host:metadata", lines=[]), device, host])


def test_busy_union_modules_kernels_and_gaps():
    modules = [("jit_a(123)", 10, 30), ("jit_b(9)", 20, 40),   # overlap
               ("jit_a(123)", 70, 80), ("jit_c(1)", 150, 160)]  # last: out
    ops = [("%k.1 = u32[8] custom-call(u32[8] %x)", 12, 18),
           ("%copy.1 = u32[8] copy(u32[8] %x)", 72, 74),
           ("%k.1 = u32[8] custom-call(u32[8] %x)", 74, 79)]
    spans = [("bench.window", 0, 100), ("bench.flush", 5, 45),
             ("bench.drain", 50, 90), ("bench.stage", 60, 65)]
    r = trace_reduce.reduce_profile(profile(modules, ops, spans))
    assert r.window_ns == (0.0, 100.0)
    assert r.busy_ns == 40.0                      # [10, 40) and [70, 80)
    assert r.idle_share == pytest.approx(0.6)
    assert r.module_ns == {"jit_a": 30.0, "jit_b": 20.0}
    assert r.kernel_ns == {"jit_a": 11.0}
    # Idle: [0,10) window 5 + flush 5; [40,70) flush 5, window 5, drain 15
    # of which stage 5; [80,100) drain 10, window 10.
    assert r.idle_by_span == {"bench.window": 20.0, "bench.flush": 10.0,
                              "bench.drain": 25.0, "bench.stage": 5.0}
    assert sum(r.idle_by_span.values()) == 100.0 - r.busy_ns
    b = r.breakdown(top=2)
    assert b["device_ops"] == [["jit_a", 30e-9], ["jit_b", 20e-9]]
    assert [k for k, _ in b["idle_gaps"]] == ["bench.drain", "bench.window"]


def test_needs_one_window_and_a_device_program():
    with pytest.raises(ValueError, match="bench.window"):
        trace_reduce.reduce_profile(profile([("jit_a(1)", 1, 2)], [], []))
    with pytest.raises(ValueError, match="no device program"):
        trace_reduce.reduce_profile(profile(
            [("jit_a(1)", 200, 300)], [], [("bench.window", 0, 100)]))


def test_union_and_innermost_segments():
    assert trace_reduce.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [
        (0, 3), (5, 8)]
    segs = trace_reduce.innermost_segments(
        [(0, 10, "a"), (2, 4, "b"), (3, 4, "c")], 1, 12)
    assert segs == [(1, 2, "a"), (2, 3, "b"), (3, 4, "c"), (4, 10, "a"),
                    (10, 12, None)]


def test_recorded_tpu_trace():
    r = trace_reduce.reduce_file(FIXTURE)
    assert r.n_devices == 1
    assert 0 < r.busy_ns < r.window_ns[1] - r.window_ns[0]
    assert r.kernel_seconds("jit_sim_lookup_kernel") > 0
    assert r.kernel_seconds("jit__stacked_plan") > 0
    assert r.module_seconds("jit_scatter") > 0
    for name in ("jit_sim_lookup_kernel", "jit__stacked_plan"):
        assert r.kernel_ns[name] <= r.module_ns[name]
    idle = sum(r.idle_by_span.values())
    assert idle == pytest.approx(r.window_ns[1] - r.window_ns[0] - r.busy_ns)
    assert {"bench.flush", "bench.drain"} <= set(r.idle_by_span)
    b = r.breakdown()
    assert 0 < len(b["device_ops"]) <= 10 and 0 < len(b["idle_gaps"]) <= 10
