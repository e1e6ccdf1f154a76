"""Reduce a JAX profiler trace (``.xplane.pb``) to the benchmark's numbers.

What it reads:

* the device planes (``/device:TPU:<n>``): their ``XLA Modules`` line, one
  event per executed program, and their ``XLA Ops`` line, one event per
  operation inside a program (a Pallas kernel is a ``custom-call`` op);
* the host planes: the harness's own spans, written with
  ``jax.profiler.TraceAnnotation`` under names that start with ``bench.``.
  The span named ``bench.window`` marks the measured window.

What it gives, inside the window only:

* device busy time: the union of the program intervals, averaged over the
  devices that ran any;
* device time per program (module name without its ``(fingerprint)``) and
  per kernel (``custom-call`` ops, grouped by the program they ran in);
* idle time on the device attributed to the innermost harness span the
  host was in at that moment (``bench.window`` itself when the host was in
  the harness loop, outside every narrower span).

Host and device events share one clock in the trace, so no alignment is
done here.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import re

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
_FINGERPRINT = re.compile(r"\(\d+\)$")


@dataclasses.dataclass
class Reduced:
    window_ns: tuple[float, float]
    n_devices: int
    busy_ns: float                       # mean over devices
    module_ns: dict[str, float]          # program name -> device ns
    kernel_ns: dict[str, float]          # program name -> custom-call ns
    idle_by_span: dict[str, float]       # host span name -> idle ns

    @property
    def window_s(self) -> float:
        return (self.window_ns[1] - self.window_ns[0]) / 1e9

    @property
    def busy_s(self) -> float:
        return self.busy_ns / 1e9

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_ns / (self.window_ns[1] - self.window_ns[0])

    def module_seconds(self, prefix: str) -> float:
        return sum(v for k, v in self.module_ns.items()
                   if k.startswith(prefix)) / 1e9

    def kernel_seconds(self, prefix: str) -> float:
        return sum(v for k, v in self.kernel_ns.items()
                   if k.startswith(prefix)) / 1e9

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.module_ns.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.idle_by_span.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v / 1e9] for k, v in ops],
                "idle_gaps": [[k, v / 1e9] for k, v in gaps]}


def module_name(event_name: str) -> str:
    return _FINGERPRINT.sub("", event_name)


def _clip(intervals, lo, hi):
    for s, e in intervals:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            yield s, e


def union(intervals) -> list[tuple[float, float]]:
    """Merge intervals into disjoint sorted ones."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def innermost_segments(spans, lo, hi):
    """Cut ``[lo, hi)`` into segments labelled by the innermost open span.

    ``spans`` are (start, end, name) from one host thread, so they nest.
    Time outside every span is labelled ``None``."""
    bounds = []
    for s, e, name in spans:
        bounds.append((s, 1, e, name))
        bounds.append((e, 0, s, name))
    bounds.sort(key=lambda b: (b[0], b[1]))
    stack: list[tuple[float, str]] = []
    segs = []
    t = lo
    for when, opening, other, name in bounds:
        if when > t and when > lo:
            seg_lo, seg_hi = max(t, lo), min(when, hi)
            if seg_hi > seg_lo:
                segs.append((seg_lo, seg_hi, stack[-1][1] if stack else None))
            t = when
        if opening:
            stack.append((when, name))
        else:
            for i in range(len(stack) - 1, -1, -1):
                if stack[i] == (other, name):
                    del stack[i]
                    break
    if t < hi:
        segs.append((max(t, lo), hi, stack[-1][1] if stack else None))
    return segs


def _attribute(gaps, segs) -> dict[str, float]:
    out: dict[str, float] = collections.defaultdict(float)
    starts = [s for s, _, _ in segs]
    for g0, g1 in gaps:
        i = max(bisect.bisect_right(starts, g0) - 1, 0)
        while i < len(segs) and segs[i][0] < g1:
            s, e, name = segs[i]
            overlap = min(e, g1) - max(s, g0)
            if overlap > 0:
                out[name or "outside_spans"] += overlap
            i += 1
    return dict(out)


def reduce_profile(profile) -> Reduced:
    """Reduce a ``jax.profiler.ProfileData``."""
    spans_by_line: dict[tuple[str, str], list] = {}
    devices = []
    for plane in profile.planes:
        if plane.name.startswith("/device:TPU:"):
            modules, ops = [], []
            for line in plane.lines:
                if line.name == "XLA Modules":
                    modules = [(e.start_ns, e.end_ns, module_name(e.name))
                               for e in line.events]
                elif line.name == "XLA Ops":
                    ops = [(e.start_ns, e.end_ns, e.name)
                           for e in line.events if " custom-call(" in e.name]
            devices.append((modules, ops))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans = [(e.start_ns, e.end_ns, e.name) for e in line.events
                         if e.name.startswith(SPAN_PREFIX)]
                if spans:
                    spans_by_line[(plane.name, line.name)] = spans
    windows = [(s, e) for spans in spans_by_line.values()
               for s, e, n in spans if n == WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN!r} span, found "
                         f"{len(windows)}")
    lo, hi = windows[0]
    host_spans = next(spans for spans in spans_by_line.values()
                      if any(n == WINDOW_SPAN for _, _, n in spans))
    segs = innermost_segments(host_spans, lo, hi)

    module_ns: dict[str, float] = collections.defaultdict(float)
    kernel_ns: dict[str, float] = collections.defaultdict(float)
    idle: dict[str, float] = collections.defaultdict(float)
    busy_total, used = 0.0, 0
    for modules, ops in devices:
        inside = sorted((s, e, n) for s0, e0, n in modules
                        for s, e in _clip([(s0, e0)], lo, hi))
        if not inside:
            continue
        used += 1
        for s, e, n in inside:
            module_ns[n] += e - s
        busy = union((s, e) for s, e, _ in inside)
        busy_total += sum(e - s for s, e in busy)
        gaps, t = [], lo
        for s, e in busy:
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        if t < hi:
            gaps.append((t, hi))
        for name, ns in _attribute(gaps, segs).items():
            idle[name] += ns
        starts = [s for s, _, _ in inside]
        for s0, e0, _name in ops:
            for s, e in _clip([(s0, e0)], lo, hi):
                i = bisect.bisect_right(starts, s) - 1
                if i >= 0 and inside[i][1] >= e:
                    kernel_ns[inside[i][2]] += e - s
    if not used:
        raise ValueError("no device program ran inside the window")
    return Reduced(window_ns=(lo, hi), n_devices=used,
                   busy_ns=busy_total / used, module_ns=dict(module_ns),
                   kernel_ns=dict(kernel_ns),
                   idle_by_span={k: v / used for k, v in idle.items()})


def reduce_file(path: str) -> Reduced:
    import jax
    return reduce_profile(jax.profiler.ProfileData.from_file(path))
