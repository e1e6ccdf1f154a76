"""MatchBackend: the batched search/gather contract, defined once.

core/match.py specifies *what* a search and a gather compute; this module
specifies *how* callers drive them at scale.  Index structures and workload
runners never talk to a chip directly — they enqueue commands against a
backend and flush, which is what turns a B+Tree range scan or a YCSB read
burst into one device operation instead of a per-page command storm
(paper §IV-E batch matching).

Two implementations ship today:

  * ``ScalarBackend`` (scalar.py) — the numpy ``SimChip``/``SimChipArray``
    functional model, executing queued commands one page at a time.  This is
    the bit-exact reference, with the full latch/ECC machinery.
  * ``ShardedSsdBackend`` (sharded.py) — the kernel backend: a whole SSD of
    ``channels x dies_per_channel`` chips whose stored pages stay *device
    resident* in a ``PlaneStore`` arena (planestore.py).  Each flush phase
    drains every chip's queue in ONE launch — searches and plans stacked
    over the chip axis (vmap), lookups and gathers row-stacked — with the
    per-page randomization stream regenerated in-kernel, and optional
    coupling to the flash/ssd.py resource timelines for per-burst
    latency/energy accounting.  After warm-up only the query operands cross
    host->device per flush; ``program_entries`` invalidates exactly the
    rewritten page's arena row through the engine's write observers.

Besides search/gather, backends implement ``submit_lookup`` — the fused
point-lookup primitive (key-page search + first-matching-slot value gather,
the §V-A paired-page pattern) that a YCSB read burst or a B+Tree
``lookup_batch`` resolves in ONE device launch instead of a search launch,
a Python bitmap decode, and a gather launch — and ``submit_plan``, the
fused multi-pass range-plan primitive (Op.PLAN): every include/exclude
pass of a §V-C range decomposition evaluates on-device and the OR/AND-NOT
combine happens in-latch (paper Fig 10), so ONE 64 B bitmap per page comes
back instead of one per pass (``BackendStats.result_bytes`` counts the
difference).

The write path is deferred too: ``submit_program`` queues an ``Op.PROGRAM``
(a full-page entry image) instead of reprogramming the chip inline.
Repeated programs of one page within a burst coalesce last-wins — every
ticket of the page resolves to the final image's ``BuiltPage`` and only ONE
chip program executes (``BackendStats.programs`` /
``programs_coalesced``).  At ``flush()`` the queued programs run *first*
(so commands flushed alongside them see the new images), and the kernel
backend re-stages every programmed page's device-resident plane row in ONE
grouped scatter (``PlaneStore.stage_group``) instead of the per-page
invalidate-then-restage round trip the eager ``program_entries`` path
causes.  This is the backend half of the §VI "whole cache acts as a write
buffer" configuration; the host half (coalescing across bursts, overlay
reads) lives in ``repro.buffer.writebuffer``.

Result delivery is *lazy* on the kernel backend: ``flush()`` dispatches
the launches and attaches a ``LazyResultBatch`` to each ticket; the
device->host transfer and host tail run at the first ``result()`` call of
a burst, so JAX async dispatch overlaps staging of burst k+1 with device
compute of burst k.

Future backends the ROADMAP names (async, replicated) implement the same
six methods: ``submit_search``, ``submit_gather``, ``submit_lookup``,
``submit_plan``, ``submit_program`` (inherited), ``flush``.

Protocol invariants (statically enforced by ``repro.analysis``; rule IDs
in brackets — see README "Static gates"):

  I1 [SIM001, SIM009]  Ticket discipline.  Every ``submit_*`` return
      value is kept (SIM001), and a ``.result()`` on tickets submitted in
      the same function is dominated by a ``flush()`` when more than one
      command is pending (SIM009, interprocedural: helper submits and
      flushes are summarized through the call graph).  Violations
      silently degrade to the eager one-command-per-launch path (§IV-E
      anti-pattern) or lean on a *later* burst's flush.  The eager
      ``search``/``gather``/``lookup``/``plan`` wrappers above are the
      documented immediate mode — a single straight-line submit whose
      ``Ticket.result()`` auto-flushes by contract — which the dataflow
      analysis proves clean (no baseline pin needed).

  I2 [SIM002]  Observer completeness.  Every mutation of a stored page
      image (``SimChip.pages``/``raw``) notifies the write observers, and
      every arena-plane mutation (``PlaneStore._lo``/``_hi``/...) updates
      the dirty/staging bookkeeping — otherwise a kernel backend matches
      against a stale device-resident row.

  I3 [SIM003]  No host sync in the hot path.  ``flush``/``_flush_*``/
      ``_dispatch*``/``_stacked*`` bodies and the kernel ``ops.py``
      wrappers never force a device->host transfer (``np.asarray``,
      ``int()``, ``.block_until_ready()`` on launch outputs); the host
      tail lives in the deferred closures ``LazyResultBatch`` runs.

  I4 [SIM004]  Counter integrity.  ``BackendStats`` fields move only
      inside the accounting helpers (flush phases, submit/resolve paths,
      deferred tails) — the staged/result byte exactness the launch audit
      (SIM101..SIM105) reconciles against the traced jaxpr depends on it.

  I5 [SIM007]  Unit-suffix convention.  Every name that carries a
      physical quantity declares its dimension by suffix: ``_ns`` for
      time, ``_pj`` for energy, ``_bytes`` for payload sizes, ``_prob``
      (or ``_probs``) for probabilities — and a value only flows between
      names of the same dimension.  Adding, subtracting or comparing two
      different declared dimensions (a latency landing in an energy
      field two calls away) is a lint finding; products and ratios are
      deliberately unconstrained so unit conversions (``ms * MS_NS``)
      and rates (``bytes / ns``) stay idiomatic.

  I6 [SIM008]  Seed provenance.  Every RNG construction
      (``default_rng``, ``SeedSequence``, ``PRNGKey``, ...) traces to a
      literal or an explicitly seed-named value (``seed``, ``*_seed``,
      ``entropy``) — through assignments, entropy-list mixing, helper
      returns, and every call site when the seed arrives as a parameter.
      Wall-clock or OS entropy anywhere in the chain breaks replay
      determinism and the seeded fault-injection tier with it.
"""
from __future__ import annotations

import abc
import dataclasses

import numpy as np

from repro.core.commands import (Command, GatherResponse, LookupResponse,
                                 ReadFullResponse, SearchResponse)
from repro.core.engine import SimChipArray
from repro.trace import TAIL, span


@dataclasses.dataclass
class BackendStats:
    searches: int = 0          # search commands resolved
    gathers: int = 0           # gather commands resolved
    lookups: int = 0           # fused lookup commands resolved
    plans: int = 0             # fused multi-pass plan commands resolved
    flushes: int = 0           # non-empty flush() calls
    kernel_launches: int = 0   # device launches
    operand_programs: int = 0  # compiled arena operand gathers: one per
                               # launch (``PlaneStore.take``/``take2d``/
                               # ``take_lookup``)
    staged_pages: int = 0      # page rows referenced across launches
    staged_queries: int = 0    # query rows staged across launches
    launched_rows: int = 0     # page-plane rows handed to search, plan and
                               # lookup launches, padding and duplicates
                               # included (gathers take chunk words)
    staged_bytes: int = 0      # page-plane bytes shipped host->device; with
                               # the device-resident store this stops growing
                               # once the working set is warm (only new or
                               # reprogrammed pages ever re-ship)
    batched_searches: int = 0  # searches that shared a launch with >= 1 peer
    programs: int = 0          # deferred Op.PROGRAM commands executed
    programs_coalesced: int = 0  # queued programs absorbed by a later
                               # program of the same page before the flush
                               # (last-wins; the page is programmed once)
    gathered_chunks: int = 0   # chunks the gather commands select
    gather_fetched_bytes: int = 0  # bytes the gather tails copy device->host,
                               # padding included (the whole (rows, 64, 16)
                               # launch output)
    result_bytes: int = 0      # exact device->host result payload: 64 B per
                               # search/plan bitmap (per unique launch cell
                               # on the kernel backend; dedup'd commands share
                               # one transfer), 64 B per gathered chunk,
                               # 64 B bitmap + 64 B value chunk (on hit) per
                               # lookup.  A fused PLAN pays 64 B/page where
                               # the per-pass path pays 64 B/pass/page.


class LazyResultBatch:
    """Deferred host tail of one flushed launch.

    The kernel backend resolves tickets *lazily*: ``flush()`` dispatches
    the launch and keeps its outputs as device arrays, attaching one of
    these to every ticket of the burst; the first ``result()`` call runs
    the host tail (device->host transfer, de-randomize/verify, ticket
    resolution) for the whole burst at once.  Until then JAX's async
    dispatch lets host staging of burst k+1 overlap device compute of
    burst k.  ``run()`` is idempotent — later tickets find themselves
    already resolved.  The tail runs inside a ``sim.tail`` span that
    carries ``meta`` (the launch's ``kind``, and the ``flush`` that
    launched it where the backend numbers its flushes).
    """

    __slots__ = ("_fn", "_exc", "_meta")

    def __init__(self, fn, **meta):
        self._fn = fn
        self._exc = None
        self._meta = meta

    def run(self) -> None:
        if self._exc is not None:
            # A previous drain attempt failed: re-raise the ROOT cause on
            # every later ticket of the burst instead of degenerating into
            # the misleading "ticket unresolved" bookkeeping error.
            raise self._exc
        fn, self._fn = self._fn, None
        if fn is not None:
            try:
                with span(TAIL, **self._meta):
                    fn()
            except BaseException as e:
                self._exc = e
                raise


class Ticket:
    """Deferred response handle returned by ``submit_*``.

    ``result()`` on an unresolved ticket flushes the owning backend first,
    so eager callers never deadlock; batch-aware callers submit many
    tickets and flush once.  On the kernel backend a flush attaches a
    :class:`LazyResultBatch` instead of a value — the launch output stays
    on-device until the first ``result()`` of the burst triggers the host
    transfer (``done`` reads True either way: the result is available
    without another flush).
    """

    __slots__ = ("_backend", "_value", "_batch", "_exc")

    def __init__(self, backend: "MatchBackend"):
        self._backend = backend
        self._value = None
        self._batch = None
        self._exc = None

    def _resolve(self, value) -> None:
        self._value = value
        self._batch = None

    def _fail(self, exc: BaseException) -> None:
        """Resolve the ticket to a typed per-command error (e.g. an
        UncorrectableReadError from the reliability tier): ``result()``
        raises it instead of returning a wrong response."""
        self._exc = exc
        self._batch = None

    def _defer(self, batch: LazyResultBatch) -> None:
        self._batch = batch

    @property
    def done(self) -> bool:
        return (self._value is not None or self._batch is not None
                or self._exc is not None)

    def result(self):
        if self._value is None and self._exc is None and self._batch is None:
            self._backend.flush()
        if self._value is None and self._exc is None \
                and self._batch is not None:
            self._batch.run()
        if self._exc is not None:
            raise self._exc
        if self._value is None:
            raise RuntimeError("flush() left a submitted ticket unresolved")
        return self._value


class MatchBackend(abc.ABC):
    """Batched search/gather execution over a SimChipArray's stored pages."""

    def __init__(self, chips: SimChipArray):
        self.chips = chips
        self.stats = BackendStats()
        # Reliability tier (repro.reliability.ReliabilityState) or None.
        # When attached, flush() runs an optimistic open burst over every
        # touched page and routes responses through the vote/verify/
        # fallback finalize paths; uncorrectable pages fail their tickets
        # with a typed error instead of resolving a wrong bitmap.
        self.reliability = None
        # Deferred Op.PROGRAM queue: page addr -> [entries, kwargs, tickets].
        # A dict so repeated programs of one page coalesce last-wins before
        # anything touches the chip (insertion order = program order).
        self._program_queue: dict[int, list] = {}

    def enable_reliability(self, state) -> None:
        """Attach a reliability tier to this backend's flush path.  Usually
        called through ``ReliabilityState.install`` /
        ``replay(..., RunConfig.reliable(...))``."""
        self.reliability = state

    def _open_reliability(self, page_addrs) -> dict:
        """Flush-time ECC-aware open burst over the flush's unique pages;
        {} when no reliability tier is attached.  Must run before kernel
        backends stage plane rows so open-time repairs ship corrected
        rows in the same flush."""
        if self.reliability is None:
            return {}
        return self.reliability.open_burst(self.chips, page_addrs)

    # ------------------------------------------------------------- storage
    # Programming and full-page reads are storage-mode operations; both
    # backends route them through the functional chip model so the stored
    # (randomized) images — the ground truth searches run against — are
    # identical regardless of backend choice.
    def program_entries(self, page_addr: int, entries, **kw):
        return self._program_page(page_addr, entries, kw)

    def _program_page(self, page_addr: int, entries, kw):
        """Program one page on the chip model.  Fault-aware backends
        (sharded) override this to fan writes out to replicas and remap
        grown bad blocks; the page keeps its *logical* address — callers
        and counters never see the physical placement."""
        return self.chips.program_entries(page_addr, entries, **kw)

    def submit_program(self, page_addr: int, entries, **kw) -> Ticket:
        """Queue a deferred page program (Op.PROGRAM).

        The entry image is copied at submit time (callers keep mutating
        their host mirrors).  Programs of the same page coalesce last-wins:
        one chip program executes at flush and every ticket of the page
        resolves to the final image's ``BuiltPage``.  Backends run queued
        programs *before* the burst's other commands and re-stage the
        programmed pages' plane rows in one grouped update.
        """
        t = Ticket(self)
        arr = np.array(entries, dtype=np.uint64, copy=True)
        entry = self._program_queue.get(int(page_addr))
        if entry is None:
            self._program_queue[int(page_addr)] = [arr, kw, [t]]
        else:
            entry[0], entry[1] = arr, kw
            entry[2].append(t)
            self.stats.programs_coalesced += 1
        return t

    @property
    def pending_programs(self) -> int:
        """Queued (post-coalescing) deferred programs."""
        return len(self._program_queue)

    def _execute_programs(self) -> list[int]:
        """Run the queued programs against the chip model, in submit order.

        Resolves every ticket and returns the programmed page addresses so
        the kernel backend can re-stage them as ONE group (and report the
        program group to its timeline).  Called by ``flush()``
        before any queued command executes — commands flushed in the same
        burst match against the new images, exactly like the eager path.
        """
        if not self._program_queue:
            return []
        queue, self._program_queue = self._program_queue, {}
        addrs: list[int] = []
        for page_addr, (entries, kw, tickets) in queue.items():
            built = self._program_page(page_addr, entries, kw)
            self.stats.programs += 1
            for t in tickets:
                t._resolve(built)
            addrs.append(page_addr)
        return addrs

    def read_full(self, page_addr: int) -> ReadFullResponse:
        return self.chips.read_full(page_addr)

    # ----------------------------------------------------------- immediate
    def search(self, cmd: Command) -> SearchResponse:
        return self.submit_search(cmd).result()

    def gather(self, cmd: Command) -> GatherResponse:
        return self.submit_gather(cmd).result()

    def lookup(self, cmd: Command) -> LookupResponse:
        return self.submit_lookup(cmd).result()

    def plan(self, cmd: Command) -> SearchResponse:
        return self.submit_plan(cmd).result()

    def _defer_all(self, tickets, tail, **meta) -> None:
        """Attach one lazy host tail to a burst's (cmd, ticket) pairs: the
        launch outputs stay device-resident until the first result().
        ``meta`` labels the tail's span."""
        batch = LazyResultBatch(tail, **meta)
        for _, t in tickets:
            t._defer(batch)

    # ------------------------------------------------------------ deferred
    @abc.abstractmethod
    def submit_search(self, cmd: Command) -> Ticket:
        """Queue a search; the ticket resolves at the next flush()."""

    @abc.abstractmethod
    def submit_gather(self, cmd: Command) -> Ticket:
        """Queue a gather; the ticket resolves at the next flush()."""

    @abc.abstractmethod
    def submit_lookup(self, cmd: Command) -> Ticket:
        """Queue a fused point lookup (Op.LOOKUP): search the key page,
        select the first matching user slot, gather that slot's chunk from
        the paired value page.  Resolves to a LookupResponse at flush()."""

    @abc.abstractmethod
    def submit_plan(self, cmd: Command) -> Ticket:
        """Queue a fused multi-pass range plan (Op.PLAN): evaluate every
        include/exclude pass against the page and accumulate OR / AND-NOT
        in-latch (paper Fig 10).  Resolves to a SearchResponse holding the
        ONE combined bitmap — 64 B crosses per page, not per pass."""

    @abc.abstractmethod
    def flush(self) -> None:
        """Execute every queued command and resolve its ticket."""

    @property
    @abc.abstractmethod
    def pending(self) -> int:
        """Number of queued, unresolved commands."""


def as_backend(chips_or_backend) -> MatchBackend:
    """Adapt a raw SimChipArray to the reference backend (API compat)."""
    if isinstance(chips_or_backend, MatchBackend):
        return chips_or_backend
    from .scalar import ScalarBackend
    return ScalarBackend(chips_or_backend)


def make_backend(name: str, chips: SimChipArray, **kw) -> MatchBackend:
    """Factory: ``scalar`` (reference) or ``sharded`` (channels x dies
    multi-chip SSD, the kernel backend)."""
    from .scalar import ScalarBackend
    from .sharded import ShardedSsdBackend
    backends = {"scalar": ScalarBackend, "sharded": ShardedSsdBackend}
    if name not in backends:
        raise ValueError(f"unknown backend {name!r}; pick from "
                         f"{sorted(backends)}")
    return backends[name](chips, **kw)
