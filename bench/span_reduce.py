"""Reduce the program's own spans in a profiler trace (``.xplane.pb``).

The program writes ``sim.*`` spans (``src/repro/trace.py``) at the layer
boundaries of its hot path: the backend's flush and its phases, arena
staging, result tails, and the replay core's bursts, drains, scans and
write-buffer drains.  This module reads them from the host line that
holds the harness's ``bench.window`` span, clipped to that window, and
gives:

* per span name: count, total time, and self time (the time no child
  ``sim.*`` span covers);
* for each lookup flush of the window, the lag from the end of
  ``sim.flush`` k to the start of the lookup ``sim.tail`` whose ``flush``
  is k: how long a read burst's answers wait on the host after launch;
* the device's idle time given to the innermost span of ``bench.*`` and
  ``sim.*`` the host was in, as ``trace_reduce`` does for ``bench.*``
  alone.

A trace with no ``sim.*`` span reduces to empty span tables and no lags,
and its idle attribution is ``trace_reduce``'s.  Everything
``trace_reduce`` gives is left to it.
"""
from __future__ import annotations

import collections
import dataclasses

from bench.trace_reduce import (WINDOW_SPAN, _attribute, _clip,
                                innermost_segments, union)

PREFIX = "sim."
FLUSH = "sim.flush"
TAIL = "sim.tail"
HOST_PREFIXES = ("bench.", PREFIX)


@dataclasses.dataclass
class Spans:
    window_ns: tuple[float, float]
    count: dict[str, int]
    total_ns: dict[str, float]
    self_ns: dict[str, float]
    drain_lag_ns: list[float]        # one per paired lookup flush
    unpaired_tails: int              # lookup tails with no one flush k
    idle_by_span: dict[str, float]   # bench.* or sim.* span -> idle ns

    def breakdown(self, top: int = 10) -> dict:
        gaps = sorted(self.idle_by_span.items(), key=lambda kv: -kv[1])[:top]
        return {"idle_gaps_by_program_span": [[k, v / 1e9] for k, v in gaps]}


def _sort_nested(spans):
    """Order (start, end, name) so a parent opens before a child that
    starts at the same instant."""
    return sorted(spans, key=lambda s: (s[0], -s[1]))


def reduce_profile(profile) -> Spans:
    """Reduce a ``jax.profiler.ProfileData``."""
    host, busy_by_device = None, []
    for plane in profile.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Modules":
                    busy_by_device.append(
                        [(e.start_ns, e.end_ns) for e in line.events])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                if any(e.name == WINDOW_SPAN for e in line.events):
                    if host is not None:
                        raise ValueError(f"more than one {WINDOW_SPAN!r} "
                                         "line")
                    host = [e for e in line.events
                            if e.name.startswith(HOST_PREFIXES)]
    if host is None:
        raise ValueError(f"no {WINDOW_SPAN!r} span")
    windows = [e for e in host if e.name == WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN!r} span, found "
                         f"{len(windows)}")
    lo, hi = windows[0].start_ns, windows[0].end_ns
    sim = [e for e in host if e.name.startswith(PREFIX)]

    count: dict[str, int] = collections.Counter()
    total: dict[str, float] = collections.defaultdict(float)
    for e in sim:
        for s, t in _clip([(e.start_ns, e.end_ns)], lo, hi):
            count[e.name] += 1
            total[e.name] += t - s
    self_ns: dict[str, float] = collections.defaultdict(float)
    for s, t, name in innermost_segments(
            _sort_nested((e.start_ns, e.end_ns, e.name) for e in sim),
            lo, hi):
        if name is not None:
            self_ns[name] += t - s

    inside = [e for e in sim if lo <= e.start_ns and e.end_ns <= hi]
    flushes = collections.defaultdict(list)
    for e in inside:
        if e.name == FLUSH:
            flushes[dict(e.stats).get("flush")].append(e)
    lags, unpaired = [], 0
    for e in inside:
        meta = dict(e.stats)
        if e.name != TAIL or meta.get("kind") != "lookup":
            continue
        match = flushes.get(meta.get("flush"), [])
        if len(match) == 1 and match[0].end_ns <= e.start_ns:
            lags.append(e.start_ns - match[0].end_ns)
        else:
            unpaired += 1

    segs = innermost_segments(
        _sort_nested((e.start_ns, e.end_ns, e.name) for e in host), lo, hi)
    idle: dict[str, float] = collections.defaultdict(float)
    used = 0
    for intervals in busy_by_device:
        busy = union(_clip(intervals, lo, hi))
        if not busy:
            continue
        used += 1
        gaps, t = [], lo
        for s, e in busy:
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        if t < hi:
            gaps.append((t, hi))
        for name, ns in _attribute(gaps, segs).items():
            idle[name] += ns
    return Spans(window_ns=(lo, hi), count=dict(count), total_ns=dict(total),
                 self_ns=dict(self_ns), drain_lag_ns=lags,
                 unpaired_tails=unpaired,
                 idle_by_span={k: v / used for k, v in idle.items()}
                 if used else {})


def us_per_op(run, name: str, which: str) -> float | None:
    """Span ``name``'s ``"self"`` or ``"total"`` time per window op, in
    microseconds, from ``run.spans``; None where the run has no spans or
    the window has no such span."""
    spans = getattr(run, "spans", None)
    if spans is None or not run.n_ops:
        return None
    ns = (spans.self_ns if which == "self" else spans.total_ns).get(name)
    return ns / run.n_ops / 1e3 if ns else None


def reduce_file(path: str) -> Spans:
    import jax
    return reduce_profile(jax.profiler.ProfileData.from_file(path))
