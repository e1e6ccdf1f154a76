"""The TPC-H family: query 6 over a BitWeaving-packed ``lineitem``.

A run generates the four columns Q6 reads from the seed (``bench/tpch.py``,
dbgen's rules), packs them into one 8-byte slot per row with the
configuration's ``RowCodec``, loads them into the program's
``SimSecondaryIndex`` over a ``ShardedSsdBackend``, stages every page into
the device arena, warms up, and runs Q6 back to back: one closed-loop
client, no think time.  Each query is one ``select_where`` (one
``Op.PLAN`` per page, then one gather per page with a match) and the
revenue summed from the rows it returns.  After the window every query's
revenue and row count is held to the numpy reference.

A query's latency runs from the call into ``select_where`` until its
revenue is summed.
"""
from __future__ import annotations

import contextlib
import time

import numpy as np

from bench import roofline, tpch

KIND_LABELS = ((0, "q6"),)
PLAN_PROGRAM = "jit__stacked_plan"
GATHER_PROGRAM = "jit_sim_gather_kernel"
CHUNK_BITMAP_BYTES = 8        # a gather command's 64-bit chunk bitmap


class Deployment:
    """One run of a TPC-H cell, for ``harness.run_cell``.

    The constructor generates the table, builds and stages the index and
    calls ``prepare(deployment, backend)`` if given; then ``warm_up``,
    ``window``, and after it ``check``, ``window_ops`` and
    ``kernel_bytes``.  ``backend`` and ``host`` (host seconds inside the
    query path, ``replay``, and inside the backend's ``flush``) are what
    the harness reads around the window.
    """

    def __init__(self, cell, *, seed: int, seconds: float, interpret: bool,
                 trace: bool, marks: dict, prepare=None):
        import jax
        from repro.backend.sharded import ShardedSsdBackend
        from repro.core.bitweaving import Column, RowCodec
        from repro.index.secondary import IncompleteGatherError, \
            SimSecondaryIndex
        from repro.reliability import (DegradedReadError, DeviceFaultState,
                                       FaultSchedule, UncorrectableReadError)
        self.read_errors = (IncompleteGatherError, DegradedReadError,
                            UncorrectableReadError)
        cfg = cell.config
        self.traffic = cell.traffic
        self.cols = tpch.generate(cfg["records"], seed=seed,
                                  scale_factor=cfg["scale_factor"])
        self.codec = RowCodec([Column(name, bits) for name, bits
                               in cfg["packing"]["columns"]])
        self.seed = seed
        self.params = tpch.rng_for(seed, 1)
        marks["generated"] = time.perf_counter()

        geo = cfg["geometry"]
        n_chips = geo["channels"] * geo["dies_per_channel"]
        n_pages = -(-cfg["records"] // tpch.ROWS_PER_PAGE)
        self.backend = backend = ShardedSsdBackend.from_geometry(
            channels=geo["channels"], dies_per_channel=geo["dies_per_channel"],
            pages_per_chip=-(-n_pages // n_chips), timeline=True,
            use_kernel=True, interpret=interpret)
        if cfg["faults"] != "healthy":
            raise ValueError(f"fault schedule {cfg['faults']!r} is not built")
        self.fault_state = DeviceFaultState(
            FaultSchedule.healthy(seed=seed % (1 << 31)))
        backend.enable_device_faults(self.fault_state)
        self.index = SimSecondaryIndex(backend, self.codec)
        self.index.load_rows(self.cols)
        marks["loaded"] = time.perf_counter()
        store = backend.store
        store.stage_group(range(n_pages))
        jax.block_until_ready(store.take(np.zeros(1, np.int32), 1))
        if store.resident_rows != n_pages:
            raise RuntimeError(f"{store.resident_rows} resident rows, table "
                               f"has {n_pages} pages")
        marks["staged"] = time.perf_counter()
        if prepare is not None:
            prepare(self, backend)

        self.host = {"replay": 0.0, "flush": 0.0}
        self.span = (jax.profiler.TraceAnnotation if trace
                     else contextlib.nullcontext)
        clock, host, span = time.perf_counter, self.host, self.span
        inner_flush = backend.flush

        def flush():
            t = clock()
            with span("bench.flush"):
                inner_flush()
            host["flush"] += clock() - t
        backend.flush = flush
        # One record per query run: its parameters, revenue and row count
        # (None after a typed read error), and its clocks.
        self.queries: list[tuple[int, int, int]] = []
        self.revenue: list[int | None] = []
        self.rows: list[int | None] = []
        self.errors = 0
        self.t_in: list[float] = []
        self.t_out: list[float] = []
        self.expected: dict[tuple, tuple[int, int, np.ndarray]] = {}

    # ------------------------------------------------------------ queries
    def _query(self, params: tuple[int, int, int]) -> float:
        """Run one Q6; returns the host clock when its revenue is summed."""
        clock = time.perf_counter
        self.queries.append(params)
        t = clock()
        self.t_in.append(t)
        with self.span("bench.replay"):
            try:
                rows = self.index.select_where(tpch.predicates(*params))
                price = self.codec.decode_rows(rows, "extendedprice")
                discount = self.codec.decode_rows(rows, "discount")
                revenue = int((price.astype(np.int64)
                               * discount.astype(np.int64)).sum())
                n_rows = len(rows)
            except self.read_errors:
                revenue = n_rows = None
                self.errors += 1
        now = clock()
        self.host["replay"] += now - t
        self.t_out.append(now)
        self.revenue.append(revenue)
        self.rows.append(n_rows)
        return now

    def _reference(self, params: tuple[int, int, int]):
        """(revenue, rows, selected-row mask) of ``params``, cached."""
        if params not in self.expected:
            keep = tpch.q6_mask(self.cols, *params)
            revenue, n_rows = tpch.q6(self.cols, *params)
            self.expected[params] = (revenue, n_rows, keep)
        return self.expected[params]

    def warm_up(self, log) -> int:
        """Compile every launch shape a query can produce, so that the
        window compiles nothing; returns the queries run.

        A query launches one plan over every page, padded to a power of
        two passes, and one gather over the pages with a match, padded by
        ``padded_rows`` to any count up to the table's.  One query runs
        per pass padding the traffic's parameter sets give through the
        codec, its set drawn from the seed; then one gather launch of
        each padded row count those queries did not launch."""
        from repro.backend.planestore import next_pow2, padded_rows
        from repro.core.commands import Command
        sets = tpch.parameter_sets(self.traffic)
        by_padding = {}
        for i in tpch.rng_for(self.seed, 2).permutation(len(sets)):
            passes = self.codec.where(tpch.predicates(*sets[i])).n_passes
            by_padding.setdefault(next_pow2(passes), sets[i])
        backend, block = self.backend, self.backend.page_block
        launched = set()
        for padding in sorted(by_padding):
            gathers = backend.stats.gathers
            self._query(by_padding[padding])
            if backend.stats.gathers > gathers:
                launched.add(padded_rows(backend.stats.gathers - gathers,
                                         block))
        first, n_pages = self.index.first_page, self.index.n_pages
        rows = block
        while rows <= padded_rows(n_pages, block):
            if rows not in launched:
                tickets = [backend.submit_gather(Command.gather(page, 0b10))
                           for page in range(first,
                                             first + min(rows, n_pages))]
                backend.flush()
                for ticket in tickets:
                    ticket.result()
            rows *= 2
        self.w0 = len(self.queries)
        return self.w0

    def window(self, deadline: float) -> float:
        """Queries back to back, each drawn from the seed, until one ends
        at or after ``deadline``; returns the host clock at which the last
        one's revenue was summed."""
        while True:
            now = self._query(tpch.draw_parameters(self.params,
                                                   self.traffic))
            if now >= deadline:
                self.end = len(self.queries)
                return now

    # --------------------------------------------------------------- after
    def check(self) -> tuple[dict, int]:
        """Every query's revenue and row count (warm-up and window)
        against ``bench/tpch.py``: ({name: (value, limit)}, failed window
        queries).  The numbers are exact counts, so each limit is 0."""
        expected = [self._reference(p)[:2] for p in self.queries]
        wrong_revenue = np.array([got != want[0] for got, want
                                  in zip(self.revenue, expected)])
        wrong_rows = np.array([got != want[1] for got, want
                               in zip(self.rows, expected)])
        fs = self.fault_state.stats
        checks = {
            "wrong_revenue": (int(wrong_revenue.sum()), 0),
            "wrong_rows": (int(wrong_rows.sum()), 0),
            "host_served": (self.errors + fs.degraded_ops + fs.failovers, 0),
        }
        bad = wrong_revenue | wrong_rows
        return checks, int(bad[self.w0:self.end].sum())

    def window_ops(self) -> tuple[np.ndarray, np.ndarray]:
        """(op kind, call to summed revenue in seconds) of each window
        query."""
        window = slice(self.w0, self.end)
        return (np.zeros(self.end - self.w0, np.uint8),
                np.array(self.t_out[window]) - np.array(self.t_in[window]))

    def kernel_bytes(self) -> dict:
        """{device program: (launches, required bytes)} of the window's
        queries: per query one plan launch over every page, and one gather
        launch reading each page with a selected row once at 4 KiB, its
        8 B chunk bitmap in and 64 B per selected chunk out."""
        n_pages = self.index.n_pages
        plan = gather = gathers = 0
        for params in self.queries[self.w0:self.end]:
            passes = self.codec.where(tpch.predicates(*params)).n_passes
            plan += roofline.plan_bytes(n_pages, passes)
            pages, chunks = tpch.hit_pages_and_chunks(
                self._reference(params)[2])
            if pages:
                gathers += 1
                gather += (pages * (roofline.PAGE_BYTES + CHUNK_BITMAP_BYTES)
                           + chunks * roofline.RESULT_BYTES)
        return {PLAN_PROGRAM: (self.end - self.w0, plan),
                GATHER_PROGRAM: (gathers, gather)}
