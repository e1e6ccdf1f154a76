"""Launch auditor (repro.analysis.launch_audit): jaxpr gate behavior."""
import jax
import jax.numpy as jnp
import pytest

import repro.backend.sharded as sharded_mod
from repro.analysis.launch_audit import (CHIP_COUNTS, FORBIDDEN_PRIMITIVES,
                                         audit_backend, iter_eqns,
                                         record_launches, summarize_jaxpr)


# ------------------------------------------------------------ jaxpr walking
def test_iter_eqns_recurses_through_pjit():
    @jax.jit
    def inner(x):
        return jnp.sin(x) + 1.0

    def outer(x):
        return inner(x) * 2.0

    closed = jax.make_jaxpr(outer)(jnp.ones(4))
    prims = {e.primitive.name for e in iter_eqns(closed.jaxpr)}
    assert "sin" in prims          # only visible through the pjit body


def test_forbidden_primitive_detected():
    def f(x):
        return jax.pure_callback(
            lambda v: v, jax.ShapeDtypeStruct(x.shape, x.dtype), x)

    s = summarize_jaxpr(jax.make_jaxpr(f)(jnp.ones(4)))
    assert "pure_callback" in s.forbidden
    assert set(s.forbidden) <= FORBIDDEN_PRIMITIVES


def test_summary_bytes_and_signature():
    def f(x, y):
        return x @ y

    closed = jax.make_jaxpr(f)(jnp.ones((2, 3), jnp.float32),
                               jnp.ones((3, 4), jnp.float32))
    s = summarize_jaxpr(closed)
    assert s.in_bytes == 4 * (6 + 12)
    assert s.out_bytes == 4 * 8
    assert s.signature == (((2, 3), "float32"), ((3, 4), "float32"))
    assert s.n_pallas == 0 and s.forbidden == ()


# -------------------------------------------------------------- the recorder
def test_recorder_restores_entry_points():
    orig = sharded_mod._stacked_search
    with record_launches() as records:
        assert sharded_mod._stacked_search is not orig
    assert sharded_mod._stacked_search is orig
    assert records == []


# ----------------------------------------------------------- the full audits
@pytest.mark.parametrize("n_chips", CHIP_COUNTS)
def test_sharded_audit_is_clean(n_chips):
    findings = audit_backend(n_chips, hlo=True)
    assert findings == [], "\n".join(f.format() for f in findings)


# ------------------------------------------------------------- gate tripping
def test_second_pallas_call_trips_sim101(monkeypatch):
    """Doctor the search launch to run twice; the audit must flag every
    search phase (value-identical, so only the launch *shape* differs)."""
    orig = sharded_mod._stacked_search

    def doubled(*args, **kwargs):
        first = orig(*args, **kwargs)
        again = orig(*args, **kwargs)
        return first | (again & 0)     # second launch contributes nothing

    monkeypatch.setattr(sharded_mod, "_stacked_search", doubled)
    findings = audit_backend(hlo=False)
    bad = [f for f in findings
           if f.rule == "SIM101" and f.slug == "pallas-count:_stacked_search"]
    assert bad, "doctored double-launch _stacked_search was not flagged"
    assert {f.symbol for f in bad} >= {"search-cold", "search-warm"}


def test_callback_in_flush_trips_sim102(monkeypatch):
    """Doctor the search launch with a host callback; the audit must flag
    it."""
    orig = sharded_mod._stacked_search

    def with_callback(lo, hi, q, m, *args, **kwargs):
        probe = jax.pure_callback(
            lambda v: v, jax.ShapeDtypeStruct(q.shape, q.dtype), q)
        return orig(lo, hi, probe, m, *args, **kwargs)

    monkeypatch.setattr(sharded_mod, "_stacked_search", with_callback)
    findings = audit_backend(hlo=False)
    assert any(f.rule == "SIM102" and "pure_callback" in f.message
               for f in findings)

