"""Profiler spans of the hot path, by name.

``span(name, **meta)`` is ``jax.profiler.TraceAnnotation``: it writes a
host span into the profiler's trace, on the same clock as the device's
events, and costs under a microsecond when no profiler session runs.
The session is the switch; there is no other.  Spans sit at the layer
boundaries of the read, scan, write and selection paths, never inside a
jitted function and never on a per-op path that does no device work.

The flush phases are siblings that together cover ``ShardedSsdBackend.flush``;
what they leave out is the flush span's self time.  ``flush`` metadata is
the backend's ``flush_seq`` and links a flush to the result tail, and to
the replay drain, of the burst it launched.
"""
from __future__ import annotations

from jax.profiler import TraceAnnotation as span

# Backend (backend/sharded.py, backend/base.py, backend/planestore.py).
FLUSH = "sim.flush"                    # flush=<flush_seq>
FLUSH_PROGRAM = "sim.flush.program"    # grouped programs + arena restage
FLUSH_PLACE = "sim.flush.place"        # queues, failover, command placement
FLUSH_OPERANDS = "sim.flush.operands"  # rows_for, compiled gather, operands
FLUSH_LAUNCH = "sim.flush.launch"      # kind=<lookup|plan|search|gather>, rows
FLUSH_ACCOUNT = "sim.flush.account"    # ChipBurst records, parities, stats
STAGE = "sim.stage"                    # PlaneStore._stage, rows=<pages>
TAIL = "sim.tail"                      # LazyResultBatch.run, flush=, kind=
TAIL_FETCH = "sim.tail.fetch"          # device->host copy of launch outputs

# Secondary index (index/secondary.py).
SELECT = "sim.select"                  # one selection, pages=, passes=
SELECT_COLLECT = "sim.select.collect"  # plan bitmaps -> gather commands
SELECT_DECODE = "sim.select.decode"    # gathered chunks -> encoded rows

# Replay frontend (frontend/replay.py).
REPLAY_BURST = "sim.replay.burst"      # ReplayCore.resolve_burst
REPLAY_DRAIN = "sim.replay.drain"      # one burst's drain, flush=<its flush>
REPLAY_SCAN = "sim.replay.scan"        # ReplayCore.scan
REPLAY_WB_DRAIN = "sim.replay.wb_drain"  # ReplayCore.flush_write_buffer

