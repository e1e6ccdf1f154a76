"""Interchangeable execution backends for the SiM search/gather/lookup
contract.

See base.py for the contract, scalar.py for the per-page reference path,
sharded.py for the channels x dies multi-chip SSD backend (per-chip
arenas, one stacked launch per burst, optional flash/ssd.py timeline
coupling), planestore.py for the device-resident page-plane arena behind
it, and results.py for the host tails that turn its launch outputs into
resolved tickets.
"""
from .base import (BackendStats, MatchBackend, Ticket, as_backend,
                   make_backend)
from .planestore import PlaneStore
from .scalar import ScalarBackend
from .sharded import ShardedSsdBackend

__all__ = ["BackendStats", "MatchBackend", "PlaneStore", "Ticket",
           "as_backend", "make_backend", "ScalarBackend",
           "ShardedSsdBackend"]
