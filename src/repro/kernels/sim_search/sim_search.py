"""Pallas TPU kernel: SiM search — masked multi-query match -> packed bitmap.

Hardware mapping (DESIGN.md §2):
  * one grid step stages a tile of ``page_block`` pages (two (PB, 512) uint32
    word planes, 4 KiB/page) from HBM into VMEM — the analogue of the NAND
    array sense into the page buffers;
  * the VPU evaluates the masked XOR match for *all Q queries* against the
    resident tile — the analogue of §IV-E batch matching, amortizing the
    page sense across queries and raising arithmetic intensity by Q;
  * when ``randomized=True`` the kernel regenerates the per-slot
    randomization stream *in-kernel* (two fmix32 rounds on a slot-address
    counter) and XORs it into the broadcast query — the deserializer of
    §IV-C1; stored pages never need de-randomizing for a search;
  * the 512 match bits per page are packed to 16 uint32 words before leaving
    VMEM, so HBM write traffic is 64 B/page — the same 64:1 reduction the
    chip achieves on its bus.

Page addressing: each staged page carries its own 32-bit flash address and
device seed as (N, 1) uint32 operands riding the sublane axis next to the
planes.  The stream counter for slot ``s`` of page ``p`` is
``(addr[p] * 512 + s) ^ seed[p]`` — identical to core/randomize.py — so a
single launch can batch pages from *different* chips (different local
addresses and device seeds), which is what the MatchBackend's deferred
submission queue relies on (§IV-E cross-page multi-query batching).

Block geometry: the trailing axis of both planes is 512 = 4 x 128 lanes;
``page_block`` rides the sublane axis (multiples of 8 keep the uint32 tile
(8, 128)-aligned).  VMEM per step ~= 2 * PB * 2 KiB + Q * PB * 2 KiB
(match-bit intermediate), e.g. PB=32, Q=16 -> ~1.3 MiB, well under the
~16 MiB v5e VMEM budget.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.bits import mix2_32
from repro.core.randomize import _HI_SALT, _LO_SALT
from repro.kernels.lanes import pack_bits

SLOTS = 512
BITMAP_WORDS = 16


def _search_kernel(lo_ref, hi_ref, q_ref, m_ref, page_ref, seed_ref, out_ref,
                   *, page_block: int, n_queries: int, randomized: bool):
    lo = lo_ref[...]                       # (PB, 512) uint32
    hi = hi_ref[...]
    q = q_ref[...]                         # (Q, 2) uint32
    m = m_ref[...]
    q_lo = q[:, 0][:, None, None]          # (Q, 1, 1)
    q_hi = q[:, 1][:, None, None]
    m_lo = m[:, 0][:, None, None]
    m_hi = m[:, 1][:, None, None]

    if randomized:
        # Deserializer: regenerate the slot-address-counter stream in VMEM
        # from each staged page's own flash address and device seed.
        page = page_ref[...]               # (PB, 1) uint32
        seed = seed_ref[...]               # (PB, 1) uint32
        slot = jax.lax.broadcasted_iota(
            jnp.uint32, (page_block, SLOTS), 1)
        ctr = (page * jnp.uint32(SLOTS) + slot) ^ seed
        s_lo = mix2_32(ctr, _LO_SALT, jnp)         # (PB, 512)
        s_hi = mix2_32(ctr, _HI_SALT, jnp)
        q_lo = q_lo ^ s_lo[None]
        q_hi = q_hi ^ s_hi[None]

    mismatch = ((lo[None] ^ q_lo) & m_lo) | ((hi[None] ^ q_hi) & m_hi)
    bits = mismatch == 0                           # (Q, PB, 512) bool

    # In-VMEM bitmap packing: 512 bits -> 16 uint32 (the 64 B bus payload).
    packed = pack_bits(bits.reshape(n_queries * page_block, SLOTS))
    out_ref[...] = packed.reshape(n_queries, page_block, BITMAP_WORDS)


@functools.partial(
    jax.jit,
    static_argnames=("page_block", "randomized", "interpret"))
def _sim_search_call(lo, hi, queries, masks, page_ids, page_seeds, *,
                     page_block: int, randomized: bool, interpret: bool):
    n_pages = lo.shape[0]
    n_queries = queries.shape[0]
    assert n_pages % page_block == 0, (n_pages, page_block)
    grid = (n_pages // page_block,)

    kernel = functools.partial(
        _search_kernel, page_block=page_block, n_queries=n_queries,
        randomized=randomized)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((page_block, SLOTS), lambda i: (i, 0)),
            pl.BlockSpec((page_block, SLOTS), lambda i: (i, 0)),
            pl.BlockSpec((n_queries, 2), lambda i: (0, 0)),
            pl.BlockSpec((n_queries, 2), lambda i: (0, 0)),
            pl.BlockSpec((page_block, 1), lambda i: (i, 0)),
            pl.BlockSpec((page_block, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((n_queries, page_block, BITMAP_WORDS),
                               lambda i: (0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_queries, n_pages, BITMAP_WORDS),
                                       jnp.uint32),
        interpret=interpret,
        name="sim_search",
    )(jnp.asarray(lo, jnp.uint32), jnp.asarray(hi, jnp.uint32),
      jnp.asarray(queries, jnp.uint32), jnp.asarray(masks, jnp.uint32),
      jnp.asarray(page_ids, jnp.uint32).reshape(-1, 1),
      jnp.asarray(page_seeds, jnp.uint32).reshape(-1, 1))


def sim_search_kernel(lo, hi, queries, masks, page_base, *,
                      page_block: int = 32, randomized: bool = False,
                      device_seed: int = 0, interpret: bool = True,
                      page_ids=None, page_seeds=None):
    """Run the search kernel.

    lo, hi:     (N, 512) uint32 planes, N a multiple of ``page_block``
                (ops.py pads)
    queries:    (Q, 2) uint32;  masks: (Q, 2) uint32
    page_base:  scalar — global index of page 0 (randomization seed) when
                ``page_ids`` is not given
    page_ids:   optional (N,) uint32 per-page flash addresses (overrides the
                contiguous ``page_base + arange(N)`` default)
    page_seeds: optional (N,) uint32 per-page device seeds (default: the
                scalar ``device_seed`` for every page)
    returns:    (Q, N, 16) uint32 packed match bitmaps
    """
    n_pages = lo.shape[0]
    if page_ids is None:
        page_ids = jnp.uint32(page_base) + jnp.arange(n_pages,
                                                      dtype=jnp.uint32)
    if page_seeds is None:
        page_seeds = jnp.full(n_pages, device_seed & 0xFFFFFFFF, jnp.uint32)
    return _sim_search_call(lo, hi, queries, masks, page_ids, page_seeds,
                            page_block=page_block, randomized=randomized,
                            interpret=interpret)
