"""Fault: one answer of every launch is altered where the kernel produces
it: the lowest bit of row 0's value words in a lookup launch, the match
bit of slot 256 of group 0, page 0 in a plan launch."""
import contextlib

from bench.control import patched

KIND = "fault"


def applies(cell) -> bool:
    if cell.config["family"] != "ycsb":     # whose traffic keys it reads
        return False
    t = cell.traffic
    return t["read_proportion"] + t["scan_proportion"] > 0


@contextlib.contextmanager
def apply():
    import jax.numpy as jnp
    from repro.backend import sharded
    lookup, plan = sharded.sim_fused_lookup, sharded._stacked_plan

    def altered_lookup(*args, **kw):
        bm, val, slots = lookup(*args, **kw)
        return bm, val.at[0].set(val[0] ^ jnp.uint32(1)), slots

    def altered_plan(*args, **kw):
        out = plan(*args, **kw)
        return out.at[0, 0, 0, 8].set(out[0, 0, 0, 8] ^ jnp.uint32(1))
    with patched(sharded, "sim_fused_lookup", altered_lookup), \
            patched(sharded, "_stacked_plan", altered_plan):
        yield None
