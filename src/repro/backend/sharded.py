"""Sharded multi-chip SSD backend: channels x dies chips, one launch/burst.

This is the kernel backend: the one that turns "a chip model with fast
kernels" into "an SSD".  It owns ``channels x dies_per_channel`` chips
behind the same four-method ``MatchBackend`` contract and exploits their
parallelism the way the paper's controller does (§VI-A, TCAM-SSD's
channel-level framework).  ``ScalarBackend`` (scalar.py) is the reference
it is held to.

Address space.  A global page address stripes across chips exactly like
``SimChipArray.route`` — ``chip = addr % n_chips``, ``local = addr // n_chips``
(:func:`decompose` / :func:`compose`) — so stored images, and therefore
every response, are bit-identical to the scalar reference over the same
array, at every geometry down to 1x1.

Per-chip state.  Every chip gets its own pending command queue and its own
plane-arena namespace — per-chip row maps, dirty tracking and staged-byte
accounting — carved out of ONE block-aligned backing ``PlaneStore``
allocation, so that draining all chips stages with a single (chips, rows)
device gather instead of a per-chip gather+stack cascade (device dispatch,
not compute, dominates the interpret path).  ``flush()`` drains every chip
in a single device dispatch per phase:

  * searches — each chip's unique local pages and unique (query, mask)
    rows pad to the common pow2-of-block geometry and stack into
    (chips, rows, ...) operands for ONE ``jax.vmap``-ed ``sim_search``
    launch over the chip axis.  Sharding also shrinks the work: a chip's
    queries match only its own resident pages, so the cross product is
    ~1/chips of a one-chip launch — the kernel analogue of
    per-channel match engines, and where the >= 2x-at-16-chips throughput
    gate in benchmarks/kernel_micro.py comes from.
  * lookups — the paired ``sim_fused_lookup`` kernel is row-parallel
    (row i searches key page i, gathers value page i), so rows from every
    chip ride one row-stacked launch; the key and value page of one lookup
    may live on different chips (the §V-A cross-die pairing).
  * gathers — same row stacking through one ``sim_gather`` launch.
  * plans (Op.PLAN) — each chip's unique pages and unique
    (include, exclude) pass tuples dedup per chip (plan dedup, mirroring
    the query dedup) and stack into ONE vmapped ``sim_plan`` launch; the
    OR/AND-NOT combine happens in-kernel (the in-latch Fig 10 dataflow),
    so the timeline charges ``n_passes`` match ops but only 64 B of
    match-mode bus payload per page — not 64 B per pass per page.

Ticket resolution is lazy (see base.py): every flush phase keeps its
launch outputs device-resident and the host tail runs at the first
``result()`` of the burst, overlapping staging of the next burst with
device compute of this one.  Timeline accounting stays at flush time
— simulated SSD time is independent of when the host drains results.

Timeline coupling.  Pass ``timeline=`` (or ``timeline=True``) to attach a
``flash.timeline.BurstTimeline``: every flush reports per-chip batch sizes
and restaged bytes as ``ChipBurst`` records, which the adapter replays on
flash/ssd.py's die/channel/PCIe timelines — ``frontend.replay`` then
returns
measured-bit-exact results plus a simulated latency/energy distribution
(fig14/15-style) from the functional backend itself.
"""
from __future__ import annotations

import dataclasses
import functools
import operator

import jax
import jax.numpy as jnp
import numpy as np

from repro import kernels, trace
from repro.core.bits import (CHUNKS_PER_PAGE, SLOTS_PER_CHUNK,
                             popcount_words, unpack_bitmap)
from repro.core.commands import Command, LookupResponse, Op, SearchResponse
from repro.core.ecc import OpenVerdict
from repro.core.page import mask_header_slots
from repro.core.engine import SimChipArray
from repro.flash.params import (BITMAP_BYTES, CHUNK_BYTES, FlashParams,
                                OPEN_OVERHEAD_BYTES, PAGE_BYTES)
from repro.flash.timeline import BurstTimeline, ChipBurst
from repro.kernels.layout import planes_to_chunk_words_xp
from repro.kernels.sim_fused.ops import sim_fused_lookup
from repro.kernels.sim_gather.ops import sim_gather
from repro.kernels.sim_plan.ops import plan_pass_rows
from repro.kernels.sim_plan.ref import sim_plan_ref
from repro.kernels.sim_plan.sim_plan import sim_plan_kernel
from repro.kernels.sim_search.ref import sim_search_ref
from repro.kernels.sim_search.sim_search import sim_search_kernel
from repro.trace import span

from .base import MatchBackend, Ticket
from .results import (resolve_gather_responses, resolve_lookup_responses,
                      resolve_plan_responses, resolve_search_responses,
                      snapshot_parities)
from .planestore import PlaneStore, next_pow2, padded_rows

QUERY_BYTES = 16               # (query, mask) uint32 pairs shipped per search


def decompose(page_addr: int, n_chips: int) -> tuple[int, int]:
    """Global page -> (chip, local page), striped across the chip array."""
    return page_addr % n_chips, page_addr // n_chips


def compose(chip: int, local: int, n_chips: int) -> int:
    """(chip, local page) -> global page; inverse of :func:`decompose`."""
    return local * n_chips + chip


@functools.partial(jax.jit, static_argnames=("page_block", "use_kernel",
                                             "interpret"))
def _stacked_search(lo, hi, q, m, ids, seeds, *, page_block: int,
                    use_kernel: bool, interpret: bool):
    """One vmapped launch over the chip axis: (C, N, 512) planes x
    (C, Q, 2) queries -> (C, Q, N, 16) packed bitmaps."""
    if use_kernel:
        def one_chip(lo, hi, q, m, ids, seeds):
            return sim_search_kernel(lo, hi, q, m, 0, page_block=page_block,
                                     randomized=True, interpret=interpret,
                                     page_ids=ids, page_seeds=seeds)
    else:
        def one_chip(lo, hi, q, m, ids, seeds):
            return sim_search_ref(lo, hi, q, m, randomized=True,
                                  page_ids=ids, page_seeds=seeds)
    return jax.vmap(one_chip)(lo, hi, q, m, ids, seeds)


@functools.partial(jax.jit, static_argnames=("page_block", "use_kernel",
                                             "interpret"))
def _stacked_plan(lo, hi, q, m, f, ids, seeds, *, page_block: int,
                  use_kernel: bool, interpret: bool):
    """One vmapped fused-plan launch over the chip axis: (C, N, 512)
    planes x (C, G, P, 2) pass rows -> (C, G, N, 16) combined bitmaps."""
    if use_kernel:
        def one_chip(lo, hi, q, m, f, ids, seeds):
            return sim_plan_kernel(lo, hi, q, m, f, page_block=page_block,
                                   randomized=True, interpret=interpret,
                                   page_ids=ids, page_seeds=seeds)
    else:
        def one_chip(lo, hi, q, m, f, ids, seeds):
            return sim_plan_ref(lo, hi, q, m, f, randomized=True,
                                page_ids=ids, page_seeds=seeds)
    return jax.vmap(one_chip)(lo, hi, q, m, f, ids, seeds)


# The operand keys searches and plans dedup by, per chip.
_SEARCH_KEY = operator.attrgetter("query", "mask")
_PLAN_KEY = operator.attrgetter("plan_include", "plan_exclude")


def _search_operands(keys, c_pad):
    """(C, Q, 2) query and mask rows from each chip's unique (query, mask)
    keys; Q pads to a power of two."""
    q_pad = max(next_pow2(len(k)) for k in keys)
    q = np.zeros((c_pad, q_pad, 2), dtype=np.uint32)
    m = np.zeros_like(q)
    for i, k in enumerate(keys):
        pairs = np.asarray(k, np.uint32)           # (Q, [query, mask], 2)
        q[i, :len(k)] = pairs[:, 0]
        m[i, :len(k)] = pairs[:, 1]
    return q, m


def _plan_operands(keys, c_pad):
    """(C, G, P, 2) pass rows and (C, G, P) pass flags from each chip's
    unique (include, exclude) groups; G and P pad to powers of two."""
    g_pad = max(next_pow2(len(k)) for k in keys)
    p_pad = next_pow2(max(max((len(i) + len(e) for i, e in k), default=1)
                          for k in keys))
    q = np.zeros((c_pad, g_pad, p_pad, 2), dtype=np.uint32)
    m = np.zeros_like(q)
    f = np.zeros((c_pad, g_pad, p_pad), dtype=np.uint32)
    for i, k in enumerate(keys):
        for gi, (inc, exc) in enumerate(k):
            q[i, gi], m[i, gi], f[i, gi] = plan_pass_rows(inc, exc, p_pad)
    return q, m, f


@dataclasses.dataclass
class _Placement:
    """One stacked search or plan launch, laid out by
    ``ShardedSsdBackend._flush_place``.  Lists run over the active chips."""
    active: list[int]                   # chips with commands, slot order
    addrs: list[list[int]]              # unique pages, in page-row order
    keys: list[list[tuple]]             # unique operand keys, key-row order
    stacked: list[tuple[int, int, int]]  # per command: (slot, ki, pi)
    rows: int                           # page rows launched: C x N, padded
    args: tuple                         # lo, hi, key operands..., ids, seeds


class ShardedSsdBackend(MatchBackend):
    """channels x dies chips, per-chip queues, one stacked launch per burst.

    ``chips`` must hold ``channels * dies_per_channel`` chips (geometry
    defaults to one channel per chip).  Results are bit-identical to the
    scalar backend over the same array.  It reports ``open_verdict``
    CLEAN unless a reliability tier is attached (``enable_reliability``),
    in which case the flush runs the full optimistic open burst and
    charges read-retries and full-page ECC fallback reads on the flash
    timelines.
    """

    # Bounded program retry budget: a seeded program-failure draw relocates
    # the page to a spare and retries at most this many times (the SIM006
    # discipline — no unbounded, unseeded retry loops in the backend).
    MAX_PROGRAM_ATTEMPTS = 8

    def __init__(self, chips: SimChipArray, *, channels: int | None = None,
                 dies_per_channel: int | None = None, page_block: int = 8,
                 lookup_block: int = 8, use_kernel: bool = True,
                 interpret: bool | None = None,
                 timeline: BurstTimeline | bool | None = None,
                 replicas: int = 1):
        super().__init__(chips)
        n_chips = len(chips.chips)
        if channels is None:
            channels = n_chips if dies_per_channel is None else \
                n_chips // dies_per_channel
        if dies_per_channel is None:
            dies_per_channel = n_chips // channels
        if channels * dies_per_channel != n_chips:
            raise ValueError(
                f"geometry {channels}x{dies_per_channel} != {n_chips} chips")
        self.channels = channels
        self.dies_per_channel = dies_per_channel
        self.page_block = page_block
        self.lookup_block = lookup_block
        self.use_kernel = use_kernel
        self.interpret = (kernels.default_interpret() if interpret is None
                          else interpret)
        if timeline is True:
            timeline = BurstTimeline(FlashParams(
                channels=channels, dies_per_channel=dies_per_channel))
        if timeline is not None and timeline is not False \
                and timeline.n_chips != n_chips:
            raise ValueError(f"timeline models {timeline.n_chips} dies, "
                             f"backend has {n_chips} chips")
        self.timeline: BurstTimeline | None = timeline or None
        # One backing arena, addressed by global page; per-chip rows are
        # grouped at flush time (see module docstring).
        self.store = PlaneStore(chips, block=page_block, log_staging=True)
        # Per-chip pending queues — the sharded command namespace.
        self._pending: list[list[tuple[str, Command, Ticket]]] = [
            [] for _ in chips.chips]
        # Fault tolerance: k-replica page striping plus bad-block remap.
        # replicas=1 keeps exactly today's single-copy behaviour; with
        # replicas=k every program fans out to k-1 extra copies on the
        # next chips round-robin, allocated from the TOP of each chip's
        # local address space (primary data grows from the bottom).
        if not 1 <= replicas <= len(chips.chips):
            raise ValueError(f"replicas={replicas} needs 1..{len(chips.chips)}")
        self.replicas = replicas
        self._replica_of: dict[int, tuple[int, ...]] = {}
        self._spare_next: list[int] = [chips.pages_per_chip - 1
                                       for _ in chips.chips]
        # DeviceFaultState (repro.reliability.device_faults) or None.
        self.faults = None
        # flush() calls so far: the ``flush`` id on the flush's span and on
        # the spans of the result tails it launched.
        self.flush_seq = 0

    # ------------------------------------------------------------ geometry
    @classmethod
    def from_geometry(cls, *, channels: int, dies_per_channel: int = 1,
                      pages_per_chip: int = 512, device_seed: int = 0,
                      **kw) -> "ShardedSsdBackend":
        """Build the chip array from SSD geometry (FlashParams convention:
        ``channels x dies_per_channel`` chips)."""
        arr = SimChipArray(n_chips=channels * dies_per_channel,
                           pages_per_chip=pages_per_chip,
                           device_seed=device_seed)
        return cls(arr, channels=channels,
                   dies_per_channel=dies_per_channel, **kw)

    @property
    def n_chips(self) -> int:
        return len(self.chips.chips)

    def decompose(self, page_addr: int) -> tuple[int, int]:
        return decompose(page_addr, self.n_chips)

    # ------------------------------------------------------------- storage
    def program_entries(self, page_addr: int, entries, **kw):
        built = self._program_page(page_addr, entries, kw)
        if self.timeline is not None:
            for c in self._program_chips(page_addr):
                self.timeline.observe_program(c)
        return built

    def _program_chips(self, page_addr: int) -> list[int]:
        """Chips a logical program lands on: the (possibly remapped)
        primary plus every replica — replica fan-out is charged on the
        timelines like any other program."""
        chips = [self._mapped(page_addr) % self.n_chips]
        chips += [self._mapped(r) % self.n_chips
                  for r in self._replica_of.get(page_addr, ())]
        return chips

    # --------------------------------------------------- fault-aware placing
    def enable_device_faults(self, state) -> None:
        """Attach a DeviceFaultState: programs draw seeded failures (grown
        bad blocks remap to spares), reads consult the outage set at flush
        and fail over to replicas, and the attached timeline schedules
        stall windows onto its resource lines."""
        self.faults = state
        if self.timeline is not None:
            self.timeline.attach_faults(state)

    def _alloc_spare(self, chip: int) -> int:
        """Carve one spare page off the top of a chip's local space."""
        local = self._spare_next[chip]
        programmed = self.chips.chips[chip].pages
        while local >= 0 and local in programmed:
            local -= 1
        if local < 0:
            raise RuntimeError(
                f"chip {chip}: out of spare pages (replicas/bad-block "
                "remap exhausted the local address space)")
        self._spare_next[chip] = local - 1
        return compose(chip, local, self.n_chips)

    def _next_live_chip(self, chip: int) -> int:
        """First chip after ``chip`` (round-robin) not in the outage set."""
        for off in range(1, self.n_chips + 1):
            c = (chip + off) % self.n_chips
            if not self.faults.chip_dead(c):
                return c
        return chip                        # whole array dead: nowhere left

    def _mapped(self, addr: int) -> int:
        """Follow the bad-block remap chain to the live physical page."""
        if self.faults is None:
            return addr
        remap = self.faults.remap
        for _ in range(len(remap)):
            nxt = remap.get(addr)
            if nxt is None:
                break
            addr = nxt
        return addr

    def _replica_addrs(self, addr: int) -> tuple[int, ...]:
        """The k-1 replica pages of a primary (allocated at first program,
        striped across the next chips round-robin)."""
        if self.replicas <= 1:
            return ()
        reps = self._replica_of.get(addr)
        if reps is None:
            chip = addr % self.n_chips
            reps = tuple(self._alloc_spare((chip + r) % self.n_chips)
                         for r in range(1, self.replicas))
            self._replica_of[addr] = reps
        return reps

    def _program_page(self, page_addr: int, entries, kw):
        """Fault-aware program: primary (with bad-block remap and bounded
        seeded retry) plus every replica.  The logical address never
        changes — only the physical placement does."""
        built = self._program_physical(page_addr, entries, kw)
        if self.faults is not None:
            for rep in self._replica_addrs(page_addr):
                self._program_physical(rep, entries, kw)
                self.faults.stats.replica_programs += 1
        else:
            for rep in self._replica_addrs(page_addr):
                self._program_physical(rep, entries, kw)
        return built

    def _program_physical(self, addr: int, entries, kw):
        """Program one physical page, relocating off dead chips and around
        seeded program failures (grown bad blocks) with a bounded retry."""
        target = self._mapped(addr)
        if self.faults is not None:
            chip = target % self.n_chips
            if self.faults.chip_dead(chip):
                # The owning chip is offline: relocate to a spare on the
                # next live chip so writes survive the outage.
                spare = self._alloc_spare(self._next_live_chip(chip))
                self.faults.mark_bad(target, spare)
                target = spare
            for attempt in range(self.MAX_PROGRAM_ATTEMPTS):
                if not self.faults.program_fails(target, attempt):
                    break
                spare = self._alloc_spare(target % self.n_chips)
                self.faults.mark_bad(target, spare)
                target = spare
        return self.chips.program_entries(target, entries, **kw)

    # ------------------------------------------------------------ deferred
    def _submit(self, kind: str, cmd: Command) -> Ticket:
        t = Ticket(self)
        chip, _ = self.decompose(cmd.page_addr)
        self._pending[chip].append((kind, cmd, t))
        return t

    def submit_search(self, cmd: Command) -> Ticket:
        if cmd.op is not Op.SEARCH or cmd.query is None or cmd.mask is None:
            raise ValueError(f"not a search command: {cmd}")
        return self._submit("search", cmd)

    def submit_gather(self, cmd: Command) -> Ticket:
        if cmd.op is not Op.GATHER or cmd.chunk_bitmap is None:
            raise ValueError(f"not a gather command: {cmd}")
        return self._submit("gather", cmd)

    def submit_lookup(self, cmd: Command) -> Ticket:
        if cmd.op is not Op.LOOKUP or cmd.value_page is None:
            raise ValueError(f"not a lookup command: {cmd}")
        return self._submit("lookup", cmd)

    def submit_plan(self, cmd: Command) -> Ticket:
        if cmd.op is not Op.PLAN or cmd.plan_include is None:
            raise ValueError(f"not a plan command: {cmd}")
        return self._submit("plan", cmd)

    @property
    def pending(self) -> int:
        return sum(len(q) for q in self._pending) + self.pending_programs

    # --------------------------------------------------------------- flush
    def flush(self) -> None:
        # Each phase runs under its own span (repro.trace); the phases are
        # siblings that together cover the flush body.
        self.flush_seq += 1
        with span(trace.FLUSH, flush=self.flush_seq):
            self._flush()

    def _flush(self) -> None:
        # Deferred write path first: one grouped chip-program pass, ONE
        # plane-store scatter for every programmed row, and one program-
        # group report to the timeline (programs queue async on each die's
        # program line; restaged dirty planes charge the storage-mode
        # channel bus — the client clock does not advance).
        programs = []
        if self._program_queue:
            with span(trace.FLUSH_PROGRAM):
                programs = self._execute_programs()
                self.store.stage_group(programs)
                if self.timeline is not None:
                    staged, self.store.staged_log = self.store.staged_log, []
                    self.timeline.observe_program_group(
                        [c for a in programs for c in self._program_chips(a)],
                        restage_chips=[self.decompose(a)[0] for a in staged])
                self.stats.staged_bytes = self.store.staged_bytes
        if not any(self._pending):
            if programs:
                self.stats.flushes += 1
            return
        self.stats.flushes += 1
        with span(trace.FLUSH_PLACE):
            searches, lookups, gathers, plans = [], [], [], []
            for queue in self._pending:
                for kind, cmd, t in queue:
                    if self.faults is not None and self.faults.remap:
                        cmd = self._remap_cmd(cmd)
                    {"search": searches, "lookup": lookups,
                     "gather": gathers, "plan": plans}[kind].append((cmd, t))
                queue.clear()
            bursts: dict[int, ChipBurst] = {}
            # Device-fault failover: commands whose chip is offline at the
            # fault clock leave the kernel path here and are served
            # host-side from a replica (or fail typed) — see
            # _serve_degraded.
            if self.faults is not None:
                dead = self.faults.dead_chips()
                if dead:
                    searches = self._failover("search", searches, dead,
                                              bursts)
                    lookups = self._failover("lookup", lookups, dead, bursts)
                    gathers = self._failover("gather", gathers, dead, bursts)
                    plans = self._failover("plan", plans, dead, bursts)
            # Reliability open burst before staging (open-time ECC repairs
            # restage corrected rows in this flush); retries and full-page
            # fallback reads charge the owning die's timeline record.
            opens = self._open_reliability(
                {c.page_addr for c, _ in searches}
                | {c.page_addr for c, _ in plans}
                | {c.page_addr for c, _ in gathers}
                | {c.page_addr for c, _ in lookups}
                | {c.value_page for c, _ in lookups})
            if opens and self.timeline is not None:
                for a, po in opens.items():
                    c, _ = self.decompose(a)
                    b = self._burst(bursts, c)
                    b.retry_senses += po.result.retries_used
                    if po.verdict is OpenVerdict.FALLBACK_ECC:
                        b.fallback_reads += 1
        if searches:
            self._flush_searches(searches, bursts, opens)
        if plans:
            self._flush_plans(plans, bursts, opens)
        if lookups:
            self._flush_lookups(lookups, bursts, opens)
        if gathers:
            self._flush_gathers(gathers, bursts, opens)
        with span(trace.FLUSH_ACCOUNT):
            self.stats.staged_bytes = self.store.staged_bytes
            staged, self.store.staged_log = self.store.staged_log, []
            if self.timeline is not None:
                for a in staged:   # dirty/new planes restage in storage mode
                    c, _ = self.decompose(a)
                    self._burst(bursts, c).bus_storage_bytes += PAGE_BYTES
                self.timeline.observe_flush(
                    [bursts[c] for c in sorted(bursts)])

    def _burst(self, bursts: dict[int, ChipBurst], chip: int) -> ChipBurst:
        return bursts.setdefault(chip, ChipBurst(chip))

    # ---------------------------------------------------- degraded failover
    def _remap_cmd(self, cmd: Command) -> Command:
        """Follow grown-bad-block remaps; spares hold the same entries and
        responses are derandomized (address-independent), so the remapped
        read is bit-identical to the original."""
        mapped = self._mapped(cmd.page_addr)
        vmapped = (self._mapped(cmd.value_page)
                   if cmd.value_page is not None else None)
        if mapped == cmd.page_addr and vmapped == cmd.value_page:
            return cmd
        return dataclasses.replace(cmd, page_addr=mapped,
                                   value_page=vmapped)

    def _failover(self, kind: str, items, dead: set[int], bursts):
        """Split one flush list: commands touching a dead chip are served
        host-side (degraded) right now; the rest stay on the kernel path."""
        if not items:
            return items
        keep = []
        for cmd, ticket in items:
            touched = [cmd.page_addr]
            if cmd.value_page is not None:
                touched.append(cmd.value_page)
            if any(a % self.n_chips in dead for a in touched):
                self._serve_degraded(kind, cmd, ticket, dead, bursts)
            else:
                keep.append((cmd, ticket))
        return keep

    def _live_addr(self, addr: int, dead: set[int], bursts) -> int:
        """A live physical address for ``addr``: the page itself when its
        chip is up, else the first replica on a live chip (charged as one
        degraded full-page read).  Raises DegradedReadError when neither
        survives."""
        from repro.reliability import DegradedReadError
        if addr % self.n_chips not in dead:
            return self._mapped(addr)
        for rep in self._replica_of.get(addr, ()):
            rep = self._mapped(rep)
            chip = rep % self.n_chips
            if chip not in dead:
                self.faults.stats.failovers += 1
                b = self._burst(bursts, chip)
                b.degraded_reads += 1
                b.pcie_bytes += PAGE_BYTES
                return rep
        raise DegradedReadError(addr)

    def _serve_degraded(self, kind: str, cmd: Command, ticket: Ticket,
                        dead: set[int], bursts) -> None:
        """Graceful degradation: execute one command host-side against the
        scalar reference path on a surviving replica.  The replica holds
        the same entries, and search/gather responses are derandomized, so
        the result is bit-identical to the healthy read — faults surface
        only as latency (the degraded full-page reads charged in
        ``bursts``) or as a typed DegradedReadError, never as wrong data.
        """
        from repro.reliability import DegradedReadError
        try:
            addr = self._live_addr(cmd.page_addr, dead, bursts)
            vaddr = (self._live_addr(cmd.value_page, dead, bursts)
                     if cmd.value_page is not None else None)
        except DegradedReadError as e:
            ticket._fail(e)
            return
        self.faults.stats.degraded_ops += 1
        if kind == "search":
            ticket._resolve(self.chips.search(
                dataclasses.replace(cmd, page_addr=addr)))
        elif kind == "gather":
            ticket._resolve(self.chips.gather(
                dataclasses.replace(cmd, page_addr=addr)))
        elif kind == "plan":
            ticket._resolve(self._plan_host(
                dataclasses.replace(cmd, page_addr=addr)))
        else:                              # lookup: the §V-A command pair
            resp = self.chips.search(Command(
                Op.SEARCH, addr, query=cmd.query, mask=cmd.mask))
            bitmap = mask_header_slots(resp.bitmap_words)
            slots = np.nonzero(unpack_bitmap(bitmap, 512))[0]
            if slots.size == 0:
                ticket._resolve(LookupResponse(search=resp,
                                               value_slot=None, value=None))
                return
            slot = int(slots[0])
            g = self.chips.gather(Command.gather(
                vaddr, 1 << (slot // SLOTS_PER_CHUNK)))
            off = (slot % SLOTS_PER_CHUNK) * 8
            ticket._resolve(LookupResponse(
                search=resp, value_slot=slot,
                value=bytes(g.chunks[0][off:off + 8]),
                parity_ok=bool(g.parity_ok[0])))

    # Open-verdict severity, worst-wins across a degraded plan's passes
    # (mirrors ScalarBackend._VERDICT_RANK).
    _VERDICT_RANK = {v.value: i for i, v in enumerate((
        OpenVerdict.CLEAN, OpenVerdict.CLEAN_NEEDS_REFRESH,
        OpenVerdict.FALLBACK_ECC, OpenVerdict.UNCORRECTABLE))}

    def _plan_host(self, cmd: Command) -> SearchResponse:
        """Per-pass split reference for a degraded Op.PLAN (scalar recipe)."""
        acc = np.zeros(16, dtype=np.uint32)
        verdict = OpenVerdict.CLEAN.value
        for q, mk in cmd.plan_include:
            r = self.chips.search(Command(Op.SEARCH, cmd.page_addr,
                                          query=q, mask=mk))
            acc |= r.bitmap_words
            verdict = max(verdict, r.open_verdict,
                          key=self._VERDICT_RANK.__getitem__)
        for q, mk in cmd.plan_exclude:
            r = self.chips.search(Command(Op.SEARCH, cmd.page_addr,
                                          query=q, mask=mk))
            acc &= ~r.bitmap_words
            verdict = max(verdict, r.open_verdict,
                          key=self._VERDICT_RANK.__getitem__)
        return SearchResponse(bitmap_words=acc,
                              match_count=int(popcount_words(acc).sum()),
                              open_verdict=verdict)

    # ---------------------------------------------------- searches and plans
    def _flush_place(self, cmds, key_of, operands) -> _Placement:
        """Lay out one burst of searches or plans as ONE stacked launch.

        Per chip: unique pages -> arena rows, unique operand keys
        (``key_of(cmd)``) -> key rows; every command lands at one
        (chip slot, key row, page row) cell.  Chips with commands take the
        slots in chip order, each slot's page rows pad to the common
        pow2-of-block count, and the slots pad to a power of two.  One
        staging pass over every chip's pages and one (C, N) gather fetch
        the planes; ``operands(keys, c_pad)`` builds the key operands that
        sit between the planes and the page ids in the launch's arguments.
        """
        n = self.n_chips
        with span(trace.FLUSH_PLACE):
            addrs: list[list[int]] = [[] for _ in range(n)]
            page_rows: list[dict[int, int]] = [{} for _ in range(n)]
            key_rows: list[dict[tuple, int]] = [{} for _ in range(n)]
            keys: list[list[tuple]] = [[] for _ in range(n)]
            placements = []                        # (chip, ki, pi)
            for cmd, _ in cmds:
                c, _local = self.decompose(cmd.page_addr)
                if cmd.page_addr not in page_rows[c]:
                    page_rows[c][cmd.page_addr] = len(addrs[c])
                    addrs[c].append(cmd.page_addr)
                key = key_of(cmd)
                if key not in key_rows[c]:
                    key_rows[c][key] = len(keys[c])
                    keys[c].append(key)
                placements.append((c, key_rows[c][key],
                                   page_rows[c][cmd.page_addr]))

            active = [c for c in range(n) if addrs[c]]
            slot_of = {c: i for i, c in enumerate(active)}
            n_pad = max(padded_rows(len(addrs[c]), self.page_block)
                        for c in active)
            c_pad = next_pow2(len(active))
            stacked = [(slot_of[c], ki, pi) for c, ki, pi in placements]
            addrs = [addrs[c] for c in active]
            keys = [keys[c] for c in active]

        with span(trace.FLUSH_OPERANDS):
            rows = self.store.rows_for([a for chip in addrs for a in chip])
            idx2d = np.zeros((c_pad, n_pad), np.int32)
            off = 0
            for i, chip in enumerate(addrs):
                idx2d[i, :len(chip)] = rows[off:off + len(chip)]
                off += len(chip)
            lo, hi, ids, seeds = self.store.take2d(idx2d)
            self.stats.operand_programs += 1
            return _Placement(active, addrs, keys, stacked, c_pad * n_pad,
                              (lo, hi, *operands(keys, c_pad), ids, seeds))

    def _flush_account_senses(self, p: _Placement, bursts, vf: int) -> None:
        """One sense per unique page (``vf`` under approximate-match
        voting) and its open overhead on the bus, per chip, plus the
        launch's counters."""
        for c, addrs in zip(p.active, p.addrs):
            k = len(addrs)
            self.chips.chips[c].counters.array_reads += k  # one sense/page
            b = self._burst(bursts, c)
            b.senses += k * vf
            b.bus_match_bytes += OPEN_OVERHEAD_BYTES * k
        self.stats.kernel_launches += 1
        self.stats.launched_rows += p.rows
        self.stats.staged_pages += sum(len(a) for a in p.addrs)

    def _flush_searches(self, searches, bursts, opens=None) -> None:
        # Approximate-match voting re-senses each page vote_k times; the
        # majority accumulates in-latch so still ONE bitmap crosses per
        # command (mirrors the plan path's in-latch accumulation).
        vf = self.reliability.vote_factor if self.reliability is not None \
            else 1
        p = self._flush_place(searches, _SEARCH_KEY, _search_operands)
        with span(trace.FLUSH_LAUNCH, kind="search", rows=p.rows):
            out = _stacked_search(
                *p.args, page_block=self.page_block,
                use_kernel=self.use_kernel, interpret=self.interpret)

        with span(trace.FLUSH_ACCOUNT):
            self._flush_account_senses(p, bursts, vf)
            self.stats.staged_queries += sum(len(k) for k in p.keys)
            self.stats.searches += len(searches)
            if len(searches) > 1:
                self.stats.batched_searches += len(searches)
            for cmd, _ in searches:
                c, _local = self.decompose(cmd.page_addr)
                b = self._burst(bursts, c)
                b.matches += vf
                b.bus_match_bytes += BITMAP_BYTES
                b.pcie_bytes += BITMAP_BYTES + QUERY_BYTES

            def tail(out=out, searches=searches, stacked=p.stacked,
                     rel=self.reliability, opens=opens):
                with span(trace.TAIL_FETCH):
                    out = np.asarray(out)
                self.stats.result_bytes += resolve_search_responses(
                    self.chips, searches, stacked, out,
                    reliability=rel, opens=opens)
            self._defer_all(searches, tail, kind="search",
                            flush=self.flush_seq)

    def _flush_plans(self, plans, bursts, opens=None) -> None:
        """Fused range plans, stacked across chips like searches.

        Unique (include, exclude) pass tuples are the plan's operand keys:
        plan groups, the per-chip plan dedup mirroring the query dedup.
        ONE vmapped ``sim_plan`` launch evaluates every chip's groups
        against its own resident pages.  On the simulated bus a plan costs
        ``n_passes`` match ops but only ONE 64 B bitmap per page — the
        in-latch accumulation (Fig 10) — where the per-pass split path
        would cross 64 B per pass per page.
        """
        vf = self.reliability.vote_factor if self.reliability is not None \
            else 1
        p = self._flush_place(plans, _PLAN_KEY, _plan_operands)
        with span(trace.FLUSH_LAUNCH, kind="plan", rows=p.rows):
            out = _stacked_plan(
                *p.args, page_block=self.page_block,
                use_kernel=self.use_kernel, interpret=self.interpret)

        with span(trace.FLUSH_ACCOUNT):
            self._flush_account_senses(p, bursts, vf)
            self.stats.staged_queries += sum(len(i) + len(e)
                                             for k in p.keys for i, e in k)
            self.stats.plans += len(plans)
            for cmd, _ in plans:
                c, _local = self.decompose(cmd.page_addr)
                b = self._burst(bursts, c)
                b.matches += cmd.n_passes * vf  # every pass matches on-die
                b.bus_match_bytes += BITMAP_BYTES  # ...but ONE bitmap crosses
                b.pcie_bytes += BITMAP_BYTES + QUERY_BYTES * cmd.n_passes

            def tail(out=out, plans=plans, stacked=p.stacked,
                     rel=self.reliability, opens=opens):
                with span(trace.TAIL_FETCH):
                    out = np.asarray(out)
                self.stats.result_bytes += resolve_plan_responses(
                    self.chips, plans, stacked, out,
                    reliability=rel, opens=opens)
            self._defer_all(plans, tail, kind="plan", flush=self.flush_seq)

    # -------------------------------------------------------------- lookups
    def _flush_lookups(self, lookups, bursts, opens=None) -> None:
        """Row-stacked fused burst across every chip: ONE launch."""
        vf = self.reliability.vote_factor if self.reliability is not None \
            else 1
        with span(trace.FLUSH_PLACE):
            key_addrs = [cmd.page_addr for cmd, _ in lookups]
            val_addrs = [cmd.value_page for cmd, _ in lookups]
            n = len(lookups)
            n_pad = padded_rows(n, self.lookup_block)
        with span(trace.FLUSH_OPERANDS):
            k_rows = self.store.rows_for(key_addrs)
            v_rows = self.store.rows_for(val_addrs)
            klo, khi, kids, kseeds, vlo, vhi = self.store.take_lookup(
                k_rows, v_rows, n_pad)
            self.stats.operand_programs += 1
            q = np.zeros((n_pad, 2), dtype=np.uint32)
            m = np.full((n_pad, 2), 0xFFFFFFFF, dtype=np.uint32)  # pads miss
            q[:n] = np.asarray([cmd.query for cmd, _ in lookups], np.uint32)
            m[:n] = np.asarray([cmd.mask for cmd, _ in lookups], np.uint32)

        with span(trace.FLUSH_LAUNCH, kind="lookup", rows=2 * n_pad):
            bm, val, slots = sim_fused_lookup(
                klo, khi, vlo, vhi, q, m, randomized=True,
                key_ids=kids, key_seeds=kseeds, row_block=self.lookup_block,
                use_kernel=self.use_kernel, interpret=self.interpret)
        with span(trace.FLUSH_ACCOUNT):
            self.stats.kernel_launches += 1
            self.stats.launched_rows += 2 * n_pad
            self.stats.lookups += n
            self.stats.staged_pages += len(set(key_addrs) | set(val_addrs))
            self.stats.staged_queries += n
            # Key pages re-sense vote_k times for majority voting; value
            # pages sense once (the chunk read is verified by parity, not
            # by vote).
            for addrs, senses in ((set(key_addrs), vf), (set(val_addrs), 1)):
                for a in addrs:                    # one open per unique page
                    c, _ = self.decompose(a)
                    b = self._burst(bursts, c)
                    b.senses += senses
                    b.bus_match_bytes += OPEN_OVERHEAD_BYTES
            for cmd, _ in lookups:
                kc, _ = self.decompose(cmd.page_addr)
                vc, _ = self.decompose(cmd.value_page)
                kb = self._burst(bursts, kc)
                kb.matches += vf
                kb.bus_match_bytes += BITMAP_BYTES
                kb.pcie_bytes += BITMAP_BYTES + QUERY_BYTES
                vb = self._burst(bursts, vc)
                vb.bus_match_bytes += CHUNK_BYTES
                vb.pcie_bytes += CHUNK_BYTES

            snap = snapshot_parities(self.chips, val_addrs)

            def tail(bm=bm, val=val, slots=slots, lookups=lookups, n=n,
                     snap=snap, rel=self.reliability, opens=opens):
                with span(trace.TAIL_FETCH):
                    bm, val, slots = (np.asarray(bm)[:n], np.asarray(val)[:n],
                                      np.asarray(slots)[:n])
                self.stats.result_bytes += resolve_lookup_responses(
                    self.chips, lookups, bm, val, slots, snap,
                    reliability=rel, opens=opens)
            self._defer_all(lookups, tail, kind="lookup",
                            flush=self.flush_seq)

    # -------------------------------------------------------------- gathers
    def _flush_gathers(self, gathers, bursts, opens=None) -> None:
        with span(trace.FLUSH_PLACE):
            addrs = [cmd.page_addr for cmd, _ in gathers]
            n = len(gathers)
            n_pad = padded_rows(n, self.page_block)
        with span(trace.FLUSH_OPERANDS):
            rows = self.store.rows_for(addrs)
            lo, hi, _, _ = self.store.take(rows, n_pad)
            self.stats.operand_programs += 1
            chunk_words = planes_to_chunk_words_xp(lo, hi, jnp)
            bm = np.zeros((n_pad, 2), dtype=np.uint32)
            bm[:n] = np.asarray([cmd.chunk_bitmap for cmd, _ in gathers],
                                np.uint32)
        with span(trace.FLUSH_LAUNCH, kind="gather", rows=n_pad):
            out, _counts = sim_gather(chunk_words, bm,
                                      max_out=CHUNKS_PER_PAGE,
                                      page_block=self.page_block,
                                      interpret=self.interpret,
                                      use_kernel=self.use_kernel)
        with span(trace.FLUSH_ACCOUNT):
            self.stats.kernel_launches += 1
            self.stats.gathers += n
            snap = snapshot_parities(self.chips, addrs)

            def tail(out=out, gathers=gathers, n=n, snap=snap,
                     rel=self.reliability, opens=opens):
                with span(trace.TAIL_FETCH):
                    out = np.asarray(out)
                self.stats.gather_fetched_bytes += out.nbytes
                out = out[:n]
                self.stats.result_bytes += resolve_gather_responses(
                    self.chips, gathers, out, snap,
                    reliability=rel, opens=opens)
            self._defer_all(gathers, tail, kind="gather",
                            flush=self.flush_seq)
            for cmd, _ in gathers:
                c, _local = self.decompose(cmd.page_addr)
                k = int(popcount_words(
                    np.asarray(cmd.chunk_bitmap, np.uint32)).sum())
                self.stats.gathered_chunks += k
                b = self._burst(bursts, c)
                b.senses += 1
                b.bus_match_bytes += CHUNK_BYTES * k
                b.pcie_bytes += CHUNK_BYTES * k
