"""Trace-time launch auditor: prove the one-launch-per-burst contract.

The kernel backends' performance story is a *shape* claim about the traced
program, not a style claim about the source: each flush phase must lower
to exactly ONE ``pallas_call`` (vmap over the chip axis included), with no
hidden host round trips (``pure_callback``/``io_callback``/explicit
transfers), stable retrace signatures across burst sizes (the pow2 padding
bounds distinct abstract signatures to O(log max_burst)), and byte
counters that reconcile against what the traced program actually moves.

The auditor enforces this dynamically: it wraps the sharded backend's
device entry points (the ``_stacked_search``/``_stacked_plan`` jits,
``sim_fused_lookup`` and ``sim_gather``) with a recorder that re-traces
every call via ``jax.make_jaxpr`` and summarizes the jaxpr, then drives a
scripted scenario, on one chip or on four, through every flush path —
search (cold + warm), plan, lookup, gather, and the zero-launch
program-group — checking after each phase:

  * SIM101 — exactly one recorded launch per flush phase, exactly one
    ``pallas_call`` primitive per launch (recursively, through pjit);
  * SIM102 — zero forbidden primitives (callbacks, infeed/outfeed,
    device_put) anywhere in the traced launch;
  * SIM103 — distinct input-signature count across a burst-size sweep is
    within the O(log max_burst) pow2-padding bound;
  * SIM104 — ``staged_bytes`` deltas equal PAGE_BYTES x newly-staged
    pages (and ZERO when warm), ``result_bytes`` deltas equal the exact
    64 B-granular payload the command mix implies, plane operands in the
    jaxpr are exactly padded_rows(unique pages) x PAGE_BYTES,
    ``launched_rows`` deltas equal the page rows of the recorded launches'
    plane operands, and ``kernel_launches`` equals the recorded launch
    count;
  * SIM105 — the unoptimized-HLO cross-check: parameter/ROOT bytes parsed
    from ``lower().compiler_ir('hlo')`` text (via launch/hlo_analysis)
    match the jaxpr operand/result bytes.

Failures surface as :class:`Finding` rows (path ``audit:sharded``) that
flow through the same baseline/check gate as the AST lint.
"""
from __future__ import annotations

import contextlib
import dataclasses
import math
from typing import Callable, Iterator

import jax

from repro.core.bits import PAGE_BYTES
from repro.core.commands import Command
from repro.core.engine import SimChipArray
from repro.core.range_query import exact_range
from repro.backend.planestore import next_pow2, padded_rows
from repro.backend import sharded
from repro.backend.sharded import ShardedSsdBackend
from repro.launch.hlo_analysis import _shape_bytes, parse_computations

from .findings import Finding

FORBIDDEN_PRIMITIVES = frozenset({
    "pure_callback", "io_callback", "callback", "debug_callback",
    "device_put", "infeed", "outfeed",
})

# The sharded backend's device entry points, looked up as module globals
# at call time.
_ENTRY_POINTS = ("_stacked_search", "_stacked_plan", "sim_fused_lookup",
                 "sim_gather")

#: chip counts the audit drives: a one-chip SSD and a four-chip one
CHIP_COUNTS = (1, 4)


# --------------------------------------------------------------- jaxpr walk
def _sub_jaxprs(value) -> Iterator:
    v = getattr(value, "jaxpr", value)      # ClosedJaxpr -> Jaxpr
    if hasattr(v, "eqns"):
        yield v
    elif isinstance(v, (list, tuple)):
        for item in v:
            yield from _sub_jaxprs(item)


def iter_eqns(jaxpr) -> Iterator:
    """All equations of a jaxpr, recursing through pjit/scan/cond bodies."""
    for eqn in jaxpr.eqns:
        yield eqn
        for param in eqn.params.values():
            for sub in _sub_jaxprs(param):
                yield from iter_eqns(sub)


def _aval_shape(v) -> tuple:
    a = v.aval
    return (tuple(a.shape), str(a.dtype))


def _aval_bytes(v) -> int:
    a = v.aval
    n = 1
    for d in a.shape:
        n *= int(d)
    return n * a.dtype.itemsize


@dataclasses.dataclass
class JaxprSummary:
    n_pallas: int
    primitives: tuple[str, ...]
    forbidden: tuple[str, ...]
    in_shapes: tuple[tuple, ...]
    out_shapes: tuple[tuple, ...]
    in_bytes: int
    out_bytes: int

    @property
    def signature(self) -> tuple:
        return self.in_shapes


def summarize_jaxpr(closed) -> JaxprSummary:
    prims = sorted({e.primitive.name for e in iter_eqns(closed.jaxpr)})
    n_pallas = sum(1 for e in iter_eqns(closed.jaxpr)
                   if e.primitive.name == "pallas_call")
    forbidden = tuple(p for p in prims if p in FORBIDDEN_PRIMITIVES)
    invars = closed.jaxpr.invars
    outvars = closed.jaxpr.outvars
    return JaxprSummary(
        n_pallas=n_pallas, primitives=tuple(prims), forbidden=forbidden,
        in_shapes=tuple(_aval_shape(v) for v in invars),
        out_shapes=tuple(_aval_shape(v) for v in outvars),
        in_bytes=sum(_aval_bytes(v) for v in invars),
        out_bytes=sum(_aval_bytes(v) for v in outvars))


def _plane_operands(summary: JaxprSummary) -> list[tuple]:
    """(shape, dtype) of a launch's page-plane operands: (..., 512)
    uint32, one lo and one hi plane per page row."""
    return [s for s in summary.in_shapes
            if s[0] and s[0][-1] == 512 and s[1] == "uint32"]


def _page_rows(summary: JaxprSummary) -> int:
    """Page rows a launch hands its kernel: its plane operands' rows over
    the two planes (lo, hi) of each row."""
    return sum(math.prod(dims[:-1])
               for dims, _ in _plane_operands(summary)) // 2


# ----------------------------------------------------------------- recorder
@dataclasses.dataclass
class LaunchRecord:
    entry: str                       # patched entry point name
    summary: JaxprSummary
    pure: Callable                   # array-only closure (for HLO lowering)
    args: tuple                      # the concrete array operands


def _is_arraylike(v) -> bool:
    return hasattr(v, "shape") and hasattr(v, "dtype")


def _record_wrapper(orig, entry_name: str, records: list):
    def wrapped(*args, **kwargs):
        arr_pos = [i for i, a in enumerate(args) if _is_arraylike(a)]
        arr_kw = [k for k, v in kwargs.items() if _is_arraylike(v)]
        arrays = [args[i] for i in arr_pos] + [kwargs[k] for k in arr_kw]

        def pure(*vals):
            new_args = list(args)
            for i, v in zip(arr_pos, vals[:len(arr_pos)]):
                new_args[i] = v
            new_kw = dict(kwargs)
            for k, v in zip(arr_kw, vals[len(arr_pos):]):
                new_kw[k] = v
            return orig(*new_args, **new_kw)

        closed = jax.make_jaxpr(pure)(*arrays)
        records.append(LaunchRecord(entry=entry_name,
                                    summary=summarize_jaxpr(closed),
                                    pure=pure, args=tuple(arrays)))
        return orig(*args, **kwargs)
    return wrapped


@contextlib.contextmanager
def record_launches():
    """Patch the sharded backend's device entry points; yields the record
    list."""
    records: list[LaunchRecord] = []
    saved = {n: getattr(sharded, n) for n in _ENTRY_POINTS}
    try:
        for n, f in saved.items():
            setattr(sharded, n, _record_wrapper(f, n, records))
        yield records
    finally:
        for n, f in saved.items():
            setattr(sharded, n, f)


# ------------------------------------------------------------ HLO cross-check
def hlo_cross_check(record: LaunchRecord) -> list[str]:
    """Parse the lowered (unoptimized) HLO and reconcile entry parameter /
    ROOT bytes against the jaxpr summary.  Returns mismatch messages."""
    text = jax.jit(record.pure).lower(*record.args) \
        .compiler_ir(dialect="hlo").as_hlo_text()
    comps = parse_computations(text)
    entry = next((c for c in comps.values() if c.is_entry), None)
    if entry is None or not entry.instrs:
        return [f"{record.entry}: no ENTRY computation parsed from HLO"]
    msgs = []
    param_bytes = sum(_shape_bytes(i.type_str) for i in entry.instrs
                      if i.op == "parameter")
    root_bytes = _shape_bytes(entry.instrs[-1].type_str)
    if param_bytes != record.summary.in_bytes:
        msgs.append(f"{record.entry}: HLO parameter bytes {param_bytes} != "
                    f"jaxpr operand bytes {record.summary.in_bytes}")
    if root_bytes != record.summary.out_bytes:
        msgs.append(f"{record.entry}: HLO ROOT bytes {root_bytes} != "
                    f"jaxpr result bytes {record.summary.out_bytes}")
    return msgs


# ------------------------------------------------------------------- driver
def _key(page: int, i: int) -> int:
    """Distinct programmed u64 keys, high nibble tagged to dodge headers."""
    return (0xA << 60) | (page << 16) | i


N_KEY_PAGES = 6
VAL_BASE = 6
N_ENTRIES = 12


class _Auditor:
    def __init__(self, n_chips: int, *, use_kernel: bool = True,
                 hlo: bool = True):
        self.n_chips = n_chips
        self.hlo = hlo
        self.findings: list[Finding] = []
        self.chips = SimChipArray(n_chips=n_chips, pages_per_chip=64,
                                  device_seed=11)
        self.backend = ShardedSsdBackend(
            self.chips, page_block=8, lookup_block=8, use_kernel=use_kernel)
        for p in range(N_KEY_PAGES):
            self.backend.program_entries(
                p, [_key(p, i) for i in range(N_ENTRIES)])
            self.backend.program_entries(
                VAL_BASE + p,
                [(0xB << 60) | (p << 16) | i for i in range(N_ENTRIES)])

    def check(self, cond: bool, rule: str, symbol: str, slug: str,
              msg: str) -> None:
        if not cond:
            self.findings.append(Finding(
                rule, "audit:sharded", symbol, slug, message=msg))

    # ------------------------------------------------------------ one phase
    def run_phase(self, records: list, phase: str, submit, *,
                  expect_result_bytes: int, expect_staged_bytes: int,
                  expect_pages: int | None = None,
                  expect_launches: int = 1):
        r0 = len(records)
        stats = self.backend.stats
        staged0, result0 = stats.staged_bytes, stats.result_bytes
        launches0, rows0 = stats.kernel_launches, stats.launched_rows
        tickets = submit()
        self.backend.flush()
        recs = records[r0:]

        self.check(len(recs) == expect_launches, "SIM101", phase,
                   "launch-count",
                   f"flush dispatched {len(recs)} launches, expected "
                   f"{expect_launches}")
        for rec in recs:
            s = rec.summary
            self.check(s.n_pallas == 1, "SIM101", phase,
                       f"pallas-count:{rec.entry}",
                       f"{rec.entry} traced to {s.n_pallas} pallas_call "
                       "primitives, expected exactly 1")
            self.check(not s.forbidden, "SIM102", phase,
                       f"forbidden:{rec.entry}",
                       f"{rec.entry} jaxpr contains forbidden primitives "
                       f"{list(s.forbidden)}")
            if expect_pages is not None:
                self.check_plane_operands(rec, phase, expect_pages)
            if self.hlo:
                for msg in hlo_cross_check(rec):
                    self.check(False, "SIM105", phase,
                               f"hlo-bytes:{rec.entry}", msg)

        self.check(
            stats.staged_bytes - staged0 == expect_staged_bytes, "SIM104",
            phase, "staged-bytes",
            f"staged_bytes moved {stats.staged_bytes - staged0}, expected "
            f"{expect_staged_bytes} (PAGE_BYTES x newly staged pages)")
        launched = sum(_page_rows(rec.summary) for rec in recs)
        self.check(
            stats.launched_rows - rows0 == launched, "SIM104", phase,
            "counter:launched_rows",
            f"launched_rows counted {stats.launched_rows - rows0}, the "
            f"recorded launches' plane operands hold {launched} page rows")
        self.check(
            stats.kernel_launches - launches0 == expect_launches, "SIM104",
            phase, "counter:kernel_launches",
            f"kernel_launches counted "
            f"{stats.kernel_launches - launches0} for {len(recs)} "
            "recorded launches")

        for t in tickets:
            t.result()
        got = stats.result_bytes - result0
        self.check(got == expect_result_bytes, "SIM104", phase,
                   "result-bytes",
                   f"result_bytes moved {got}, expected "
                   f"{expect_result_bytes} from the submitted command mix")
        if recs and expect_result_bytes:
            out_bytes = sum(r.summary.out_bytes for r in recs)
            self.check(got <= out_bytes, "SIM104", phase,
                       "result-within-launch",
                       f"result_bytes {got} exceeds traced launch output "
                       f"{out_bytes}")
        return recs

    def check_plane_operands(self, rec: LaunchRecord, phase: str,
                             expect_pages: int) -> None:
        """The (padded) page-plane operands must be exactly
        padded_rows(unique pages) rows — PAGE_BYTES per padded row."""
        planes = _plane_operands(rec.summary)
        self.check(len(planes) >= 2, "SIM104", phase,
                   f"plane-operands:{rec.entry}",
                   f"{rec.entry} jaxpr has {len(planes)} plane-shaped "
                   "operands, expected lo+hi")
        for dims, _ in planes[:2]:
            rows = 1
            for d in dims[:-1]:
                rows *= d
            self.check(rows == expect_pages, "SIM104", phase,
                       f"plane-rows:{rec.entry}",
                       f"{rec.entry} plane operand has {rows} padded rows "
                       f"({dims}), expected {expect_pages}")

    # ------------------------------------------------------------ scenario
    def expected_search_rows(self, addr_lists: list[list[int]]) -> int:
        """Padded plane rows for per-chip unique page lists."""
        block = self.backend.page_block
        n_pad = max(padded_rows(len(a), block) for a in addr_lists if a)
        c_pad = next_pow2(sum(1 for a in addr_lists if a))
        return c_pad * n_pad

    def per_chip(self, addrs: list[int]) -> list[list[int]]:
        n = self.n_chips
        out: list[list[int]] = [[] for _ in range(n)]
        for a in addrs:
            if a not in out[a % n]:
                out[a % n].append(a)
        return out

    def run(self) -> list[Finding]:
        with record_launches() as records:
            self._scenario(records)
        self._retrace_sweep()
        return self.findings

    def _scenario(self, records: list) -> None:
        b = self.backend

        # --- search, cold: 13 commands, 12 unique (query, page) cells ----
        search_cmds = [Command.search(p, _key(p, i))
                       for p in range(N_KEY_PAGES) for i in (0, 1)]
        search_cmds.append(Command.search(0, _key(0, 0)))    # dedup'd twin
        pages = [c.page_addr for c in search_cmds]
        self.run_phase(
            records, "search-cold",
            lambda: [b.submit_search(c) for c in search_cmds],
            expect_result_bytes=64 * 12,
            expect_staged_bytes=PAGE_BYTES * N_KEY_PAGES,
            expect_pages=self.expected_search_rows(self.per_chip(pages)))

        # --- search, warm: same pages, new queries -> ZERO page restage --
        warm_cmds = [Command.search(p, _key(p, 2))
                     for p in range(N_KEY_PAGES)]
        self.run_phase(
            records, "search-warm",
            lambda: [b.submit_search(c) for c in warm_cmds],
            expect_result_bytes=64 * N_KEY_PAGES,
            expect_staged_bytes=0,
            expect_pages=self.expected_search_rows(self.per_chip(
                [c.page_addr for c in warm_cmds])))

        # --- fused plans: 2 distinct plans, 7 commands, 6 unique cells ---
        plan_a = exact_range(_key(0, 0), _key(0, 8))
        plan_b = exact_range(_key(1, 0), _key(1, 4))
        plan_cmds = [Command.plan(p, plan_a.include, plan_a.exclude)
                     for p in range(4)]
        plan_cmds += [Command.plan(p, plan_b.include, plan_b.exclude)
                      for p in range(2)]
        plan_cmds.append(Command.plan(0, plan_a.include, plan_a.exclude))
        self.run_phase(
            records, "plan",
            lambda: [b.submit_plan(c) for c in plan_cmds],
            expect_result_bytes=64 * 6,
            expect_staged_bytes=0)

        # --- fused lookups: 4 hits + 1 miss; value pages stage cold ------
        lookup_cmds = [Command.lookup(i, VAL_BASE + i, _key(i, 1))
                       for i in range(4)]
        lookup_cmds.append(Command.lookup(0, VAL_BASE, _key(5, 999)))
        self.run_phase(
            records, "lookup",
            lambda: [b.submit_lookup(c) for c in lookup_cmds],
            expect_result_bytes=64 * 5 + 64 * 4,
            expect_staged_bytes=PAGE_BYTES * 4)      # value pages 6..9

        # --- gathers: explicit chunk bitmaps, 64 B per selected chunk ----
        bitmaps = [0b1011, 0b1, 0b1110001]
        gather_cmds = [Command.gather(p, bm)
                       for p, bm in enumerate(bitmaps)]
        n_chunks = sum(bin(bm).count("1") for bm in bitmaps)
        self.run_phase(
            records, "gather",
            lambda: [b.submit_gather(c) for c in gather_cmds],
            expect_result_bytes=64 * n_chunks,
            expect_staged_bytes=0)

        # --- program group: ZERO launches, coalescing + grouped restage --
        def submit_programs():
            new = [_key(2, 100 + i) for i in range(N_ENTRIES)]
            newer = [_key(2, 200 + i) for i in range(N_ENTRIES)]
            other = [_key(3, 300 + i) for i in range(N_ENTRIES)]
            return [b.submit_program(2, new), b.submit_program(2, newer),
                    b.submit_program(3, other)]

        stats = b.stats
        programs0, coalesced0 = stats.programs, stats.programs_coalesced
        self.run_phase(
            records, "program-group", submit_programs,
            expect_result_bytes=0,
            expect_staged_bytes=PAGE_BYTES * 2,      # pages 2+3, one scatter
            expect_launches=0)
        self.check(stats.programs - programs0 == 2, "SIM104",
                   "program-group", "counter:programs",
                   f"programs counted {stats.programs - programs0}, "
                   "expected 2 (page 2 coalesced last-wins + page 3)")
        self.check(stats.programs_coalesced - coalesced0 == 1, "SIM104",
                   "program-group", "counter:programs_coalesced",
                   f"programs_coalesced counted "
                   f"{stats.programs_coalesced - coalesced0}, expected 1")

        # --- post-program search: group restage means NO further staging -
        post_cmds = [Command.search(2, _key(2, 200)),
                     Command.search(3, _key(3, 300))]
        self.run_phase(
            records, "search-after-program",
            lambda: [b.submit_search(c) for c in post_cmds],
            expect_result_bytes=64 * 2,
            expect_staged_bytes=0)

    # -------------------------------------------------------- retrace sweep
    def _retrace_sweep(self, burst_sizes=(1, 2, 3, 4, 5, 6, 8, 12, 16)):
        """Distinct abstract signatures across a burst sweep must stay
        within the pow2-padding bound: O(log max_burst), not O(bursts)."""
        chips = SimChipArray(n_chips=self.n_chips, pages_per_chip=64,
                             device_seed=11)
        backend = ShardedSsdBackend(chips, page_block=8, lookup_block=8,
                                    use_kernel=True)
        for p in range(4):
            backend.program_entries(p, [_key(p, i) for i in range(32)])
        with record_launches() as records:
            q = 0
            for size in burst_sizes:
                tickets = []
                for _ in range(size):
                    tickets.append(backend.submit_search(
                        Command.search(q % 4, _key(q % 4, q % 32))))
                    q += 1
                backend.flush()
                for t in tickets:
                    t.result()
        sigs = {r.summary.signature for r in records
                if r.entry == "_stacked_search"}
        bound = int(math.log2(next_pow2(max(burst_sizes)))) + 1
        self.check(0 < len(sigs) <= bound, "SIM103", "retrace-sweep",
                   "distinct-signatures",
                   f"{len(sigs)} distinct launch signatures across burst "
                   f"sizes {list(burst_sizes)}; pow2 padding bounds this "
                   f"by log2(max)+1 = {bound}")

        # Pure-arithmetic half of the same invariant, over the full range.
        for block in (8, 32):
            distinct = {padded_rows(n, block) for n in range(1, 1025)}
            bound = int(math.log2(next_pow2(-(-1024 // block)))) + 1
            self.check(len(distinct) <= bound, "SIM103", "retrace-sweep",
                       f"padded-rows-bound:block{block}",
                       f"padded_rows yields {len(distinct)} distinct row "
                       f"counts for n in 1..1024 at block {block} "
                       f"(bound {bound})")


def audit_backend(n_chips: int = 4, *, use_kernel: bool = True,
                  hlo: bool = True) -> list[Finding]:
    """Run the full launch audit on an ``n_chips`` sharded backend."""
    return _Auditor(n_chips, use_kernel=use_kernel, hlo=hlo).run()


def run_audit(*, hlo: bool = True) -> list[Finding]:
    findings: list[Finding] = []
    for n_chips in CHIP_COUNTS:
        findings.extend(audit_backend(n_chips, hlo=hlo))
    return findings
