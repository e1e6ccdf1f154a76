"""Pure-jnp oracle for the sim_search kernel."""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.core.bits import mix2_32, pack_bitmap
from repro.core.randomize import _HI_SALT, _LO_SALT


def stream_planes(page_base: int, n_pages: int, device_seed: int, xp=jnp,
                  page_ids=None, page_seeds=None):
    """Randomization stream for pages [page_base, page_base+n) as planes.

    ``page_ids``/``page_seeds`` (each (N,) uint32) override the contiguous
    single-seed default — the per-page addressing the sharded backend uses.
    """
    if page_ids is None:
        page = (xp.arange(n_pages, dtype=xp.uint32)[:, None]
                + xp.uint32(page_base))
    else:
        page = xp.asarray(page_ids, dtype=xp.uint32)[:, None]
    if page_seeds is None:
        seed = xp.uint32(device_seed & 0xFFFFFFFF)
    else:
        seed = xp.asarray(page_seeds, dtype=xp.uint32)[:, None]
    slot = xp.arange(512, dtype=xp.uint32)[None, :]
    ctr = (page * xp.uint32(512) + slot).astype(xp.uint32)
    ctr = ctr ^ seed
    return mix2_32(ctr, _LO_SALT, xp), mix2_32(ctr, _HI_SALT, xp)


def sim_search_ref(lo, hi, queries, masks, *, randomized: bool = False,
                   page_base: int = 0, device_seed: int = 0,
                   page_ids=None, page_seeds=None) -> jnp.ndarray:
    """Reference masked multi-query search.

    lo, hi:   (N, 512) uint32 slot-word planes (possibly randomized)
    queries:  (Q, 2) uint32
    masks:    (Q, 2) uint32
    returns:  (Q, N, 16) uint32 packed match bitmaps
    """
    lo = jnp.asarray(lo, dtype=jnp.uint32)
    hi = jnp.asarray(hi, dtype=jnp.uint32)
    q = jnp.asarray(queries, dtype=jnp.uint32)
    m = jnp.asarray(masks, dtype=jnp.uint32)
    if randomized:
        s_lo, s_hi = stream_planes(page_base, lo.shape[0], device_seed,
                                   page_ids=page_ids, page_seeds=page_seeds)
        q_lo = q[:, None, None, 0] ^ s_lo[None]      # (Q, N, 512)
        q_hi = q[:, None, None, 1] ^ s_hi[None]
    else:
        q_lo = q[:, None, None, 0]
        q_hi = q[:, None, None, 1]
    mm = ((lo[None] ^ q_lo) & m[:, None, None, 0]) | (
        (hi[None] ^ q_hi) & m[:, None, None, 1])
    bits = (mm == 0).astype(jnp.uint32)              # (Q, N, 512)
    return pack_bitmap(bits, xp=jnp)                 # (Q, N, 16)


def sim_search_ref_np(lo, hi, queries, masks, **kw) -> np.ndarray:
    return np.asarray(sim_search_ref(lo, hi, queries, masks, **kw))
