"""Pallas TPU kernel: fused multi-pass range-plan evaluation (paper Fig 10).

A SiM range plan decomposes ``lo <= k < hi`` into P masked-equality passes
(core/range_query.py).  The chip evaluates them *in-latch*: each pass's
match bits are OR-accumulated (include passes) or AND-NOT-accumulated
(exclude passes) into the SDC latch, and only the final combined 512-bit
bitmap — 64 B — crosses the bus.  Per-pass bitmaps never leave the chip.

This kernel is the TPU analogue of that dataflow.  One grid step stages a
tile of ``page_block`` pages into VMEM and sweeps ALL P pass rows of one
plan group against the resident tile: per-pass match bits are reduced with
a masked OR into an include accumulator and an exclude accumulator while
still in VMEM, the AND-NOT combine happens in-register, and only the packed
(PB, 16) combined bitmap is written back to HBM.  Device->host result
traffic therefore shrinks by the pass count versus the per-pass
``sim_search`` path (exact 64-bit plans reach >100 passes), exactly like
the chip's bus.

Operand scheme matches ``sim_search``: each staged page carries its own
flash address and device seed on the sublane axis, so the §IV-C1
randomization stream regenerates in-kernel and one launch batches pages
from different chips.  Plans ride a *group* axis: the grid is
(page tiles, plan groups), each group owning (P, 2) query/mask rows plus a
(P, 1) flags column marking every pass include / exclude / padding (a
(1, P) block of the (G, P) flags would break the TPU's (8, 128) tiling).

VMEM per step ~= 2 * PB * 2 KiB (planes) + P * PB * 2 KiB (pass-match
intermediate); the default PB=8 keeps a 128-pass plan at ~2 MiB, the
most passes one plan may hold (``MAX_PASSES``).
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

# Pass flags: how a pass row enters the in-latch accumulation.
PASS_PAD = 0        # padding row — contributes to neither accumulator
PASS_INCLUDE = 1    # OR into the include accumulator
PASS_EXCLUDE = 2    # OR into the exclude accumulator (AND-NOT at the end)
MAX_PASSES = 128    # passes one plan may hold (VMEM at the default PB)


def _plan_kernel(lo_ref, hi_ref, q_ref, m_ref, f_ref, page_ref, seed_ref,
                 out_ref, *, page_block: int, randomized: bool):
    lo = lo_ref[...]                       # (PB, 512) uint32
    hi = hi_ref[...]
    q = q_ref[...][0]                      # (P, 2): this group's pass rows
    m = m_ref[...][0]
    f = f_ref[...][0]                      # (P, 1) uint32 pass flags

    q_lo = q[:, 0:1][:, :, None]           # (P, 1, 1)
    q_hi = q[:, 1:2][:, :, None]
    m_lo = m[:, 0:1][:, :, None]
    m_hi = m[:, 1:2][:, :, None]
    if randomized:
        # Deserializer: regenerate the slot-address-counter stream in VMEM
        # from each staged page's own flash address and device seed.
        page = page_ref[...]               # (PB, 1) uint32
        seed = seed_ref[...]
        slot = jax.lax.broadcasted_iota(
            jnp.uint32, (page_block, SLOTS), 1)
        ctr = (page * jnp.uint32(SLOTS) + slot) ^ seed
        q_lo = q_lo ^ mix2_32(ctr, _LO_SALT, jnp)[None]
        q_hi = q_hi ^ mix2_32(ctr, _HI_SALT, jnp)[None]

    mismatch = ((lo[None] ^ q_lo) & m_lo) | ((hi[None] ^ q_hi) & m_hi)
    bits = (mismatch == 0).astype(jnp.int32)       # (P, PB, 512) 0/1

    # In-latch accumulation (Fig 10): masked OR over the include passes,
    # masked OR over the exclude passes, one AND-NOT combine — all while
    # the per-pass bits are still resident in VMEM.
    is_inc = (f == jnp.uint32(PASS_INCLUDE)).astype(jnp.int32)[:, :, None]
    is_exc = (f == jnp.uint32(PASS_EXCLUDE)).astype(jnp.int32)[:, :, None]
    inc = (bits & is_inc).max(axis=0)              # (PB, 512) 0/1
    exc = (bits & is_exc).max(axis=0)
    acc = (inc & (1 - exc)) == 1

    # Only the combined bitmap leaves VMEM: 512 bits -> 16 uint32 (64 B).
    out_ref[...] = pack_bits(acc)[None]


@functools.partial(
    jax.jit,
    static_argnames=("page_block", "randomized", "interpret"))
def _sim_plan_call(lo, hi, queries, masks, flags, page_ids, page_seeds, *,
                   page_block: int, randomized: bool, interpret: bool):
    n_pages = lo.shape[0]
    n_groups, n_passes, _ = queries.shape
    assert n_pages % page_block == 0, (n_pages, page_block)
    grid = (n_pages // page_block, n_groups)

    kernel = functools.partial(
        _plan_kernel, page_block=page_block, randomized=randomized)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((page_block, SLOTS), lambda i, j: (i, 0)),
            pl.BlockSpec((page_block, SLOTS), lambda i, j: (i, 0)),
            pl.BlockSpec((1, n_passes, 2), lambda i, j: (j, 0, 0)),
            pl.BlockSpec((1, n_passes, 2), lambda i, j: (j, 0, 0)),
            pl.BlockSpec((1, n_passes, 1), lambda i, j: (j, 0, 0)),
            pl.BlockSpec((page_block, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((page_block, 1), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((1, page_block, BITMAP_WORDS),
                               lambda i, j: (j, i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_groups, n_pages, BITMAP_WORDS),
                                       jnp.uint32),
        interpret=interpret,
        name="sim_plan",
    )(jnp.asarray(lo, jnp.uint32), jnp.asarray(hi, jnp.uint32),
      jnp.asarray(queries, jnp.uint32), jnp.asarray(masks, jnp.uint32),
      jnp.asarray(flags, jnp.uint32)[..., None],
      jnp.asarray(page_ids, jnp.uint32).reshape(-1, 1),
      jnp.asarray(page_seeds, jnp.uint32).reshape(-1, 1))


def sim_plan_kernel(lo, hi, queries, masks, flags, *, page_block: int = 8,
                    randomized: bool = False, device_seed: int = 0,
                    page_base: int = 0, interpret: bool = True,
                    page_ids=None, page_seeds=None):
    """Run the fused plan kernel.

    lo, hi:     (N, 512) uint32 planes, N a multiple of ``page_block``
                (ops.py pads)
    queries:    (G, P, 2) uint32 pass rows;  masks: (G, P, 2) uint32
    flags:      (G, P) uint32 — PASS_INCLUDE / PASS_EXCLUDE / PASS_PAD
    page_ids:   optional (N,) uint32 per-page flash addresses (defaults to
                the contiguous ``page_base + arange(N)``)
    page_seeds: optional (N,) uint32 per-page device seeds (default: the
                scalar ``device_seed`` for every page)
    returns:    (G, N, 16) uint32 combined match bitmaps — ONE per
                (plan group, page), not one per pass
    """
    n_pages = lo.shape[0]
    if page_ids is None:
        page_ids = jnp.uint32(page_base) + jnp.arange(n_pages,
                                                      dtype=jnp.uint32)
    if page_seeds is None:
        page_seeds = jnp.full(n_pages, device_seed & 0xFFFFFFFF, jnp.uint32)
    return _sim_plan_call(lo, hi, queries, masks, flags, page_ids,
                          page_seeds, page_block=page_block,
                          randomized=randomized, interpret=interpret)
