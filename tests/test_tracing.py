"""The program's profiler spans (``repro.trace``) on a small replay.

A mixed stream (reads, buffered updates that drain in groups, scans)
replays through ``ReplayCore`` over a ``ShardedSsdBackend`` with its
kernels in interpret mode, under a ``jax.profiler`` session.  The trace
must hold every span of the hot path, nest each flush phase in a flush,
and tie each lookup tail and each drain to the flush that launched it.
"""
from __future__ import annotations

import glob
import os

import jax
import pytest

from repro import trace
from repro.backend.sharded import ShardedSsdBackend
from repro.frontend import RunConfig
from repro.frontend.replay import ReplayCore
from repro.workload import ycsb

ALL_SPANS = {trace.FLUSH, trace.FLUSH_PROGRAM, trace.FLUSH_PLACE,
             trace.FLUSH_OPERANDS, trace.FLUSH_LAUNCH, trace.FLUSH_ACCOUNT,
             trace.STAGE, trace.TAIL, trace.TAIL_FETCH, trace.REPLAY_BURST,
             trace.REPLAY_DRAIN, trace.REPLAY_SCAN, trace.REPLAY_WB_DRAIN}
PHASES = {trace.FLUSH_PROGRAM, trace.FLUSH_PLACE, trace.FLUSH_OPERANDS,
          trace.FLUSH_LAUNCH, trace.FLUSH_ACCOUNT}


@pytest.fixture(scope="module")
def spans(tmp_path_factory):
    """(start, end, name, meta) of every ``sim.*`` span of the replay."""
    wl = ycsb.generate(48, n_key_pages=4, read_ratio=0.5, alpha=0.9,
                       seed=5, scan_ratio=0.1, max_scan_len=2)
    backend = ShardedSsdBackend.from_geometry(
        channels=2, dies_per_channel=2, pages_per_chip=2, timeline=True,
        use_kernel=True)
    core = ReplayCore(wl, backend, RunConfig.buffered(
        write_high_water=2, burst=8, fused=True))
    out = str(tmp_path_factory.mktemp("trace"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0        # spans only: a fast stop_trace
    opts.host_tracer_level = 1
    jax.profiler.start_trace(out, profiler_options=opts)
    try:
        for qi, op in enumerate(wl.ops):
            if op == 0:
                if core.queue_read(qi) and len(core.pending) >= 8:
                    core.resolve_burst()
            elif op == 2:
                core.scan(qi)
            else:
                core.write(qi)
        core.finish()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(out, "**", "*.xplane.pb"),
                        recursive=True)
    events = [(e.start_ns, e.end_ns, e.name, dict(e.stats))
              for plane in jax.profiler.ProfileData.from_file(path).planes
              if plane.name.startswith("/host:")
              for line in plane.lines for e in line.events
              if e.name.startswith("sim.")]
    assert core.hits[wl.ops == 0].all()
    return sorted(events)


def test_every_span_is_written(spans):
    assert {name for _, _, name, _ in spans} == ALL_SPANS


def test_flush_phases_nest_in_a_flush(spans):
    flushes = [(s, e) for s, e, name, _ in spans if name == trace.FLUSH]
    for s, e, name, _ in spans:
        if name in PHASES:
            assert any(fs <= s and e <= fe for fs, fe in flushes), name


def test_launch_spans_carry_kind_and_rows(spans):
    kinds = {meta["kind"] for _, _, name, meta in spans
             if name == trace.FLUSH_LAUNCH}
    assert kinds == {"lookup", "plan"}
    assert all(meta["rows"] > 0 for _, _, name, meta in spans
               if name in (trace.FLUSH_LAUNCH, trace.STAGE))


def test_tails_and_drains_name_an_earlier_flush(spans):
    ended = {}
    for s, e, name, meta in spans:
        if name == trace.FLUSH:
            assert meta["flush"] not in ended
            ended[meta["flush"]] = e
    lookup_tails = [(s, meta) for s, _, name, meta in spans
                    if name == trace.TAIL and meta["kind"] == "lookup"]
    drains = [(s, meta) for s, _, name, meta in spans
              if name == trace.REPLAY_DRAIN]
    assert lookup_tails and len(drains) == len(lookup_tails)
    for s, meta in lookup_tails + drains:
        assert ended[meta["flush"]] <= s
    assert sorted(m["flush"] for _, m in lookup_tails) \
        == sorted(m["flush"] for _, m in drains)
