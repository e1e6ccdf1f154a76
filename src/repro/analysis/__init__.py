"""Static gates for the backend protocol: AST contract lint + launch audit.

Two prongs, one CLI (``python -m repro.analysis``):

  * ``contracts`` — AST-based lint rules (SIM001..SIM004) over ``src/repro``
    that enforce the MatchBackend invariants documented in
    ``repro.backend.base`` (ticket discipline, observer completeness,
    host-sync-free hot paths, counter integrity);
  * ``launch_audit`` — a trace-time auditor (SIM101..SIM105) that drives the
    sharded backend, on one chip and on four, through every flush path,
    captures each device entry point's jaxpr, and proves
    one-``pallas_call``-per-burst, zero hidden callbacks, retrace-signature
    stability, and byte-exact counter reconciliation.

Accepted pre-existing findings are pinned in ``baseline.toml`` next to this
file; ``--check`` fails on any finding not in the baseline.
"""
from .findings import Finding

__all__ = ["Finding"]
