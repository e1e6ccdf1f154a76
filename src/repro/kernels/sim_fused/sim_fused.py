"""Pallas TPU kernels: fused SiM search + gather.

The paper notes a search is commonly followed immediately by a gather on the
same page, and the chip pipelines them because the page already sits in the
page buffers (§III-B, §V-A).  The TPU analogue is fusion: one VMEM residency
of the page tile feeds both the match and the compaction matmul, halving HBM
page reads for the search->gather pattern that dominates B+Tree lookups.

Two kernels live here:

  * ``sim_fused_kernel`` — the cross-product form: Q queries against N
    pages, each (query, page) cell returning its packed bitmap plus the
    matching chunks compacted from the *same* page.  Pages carry per-row
    flash addresses and device seeds (same operand scheme as ``sim_search``)
    so one launch batches pages from different chips.
  * ``sim_lookup_kernel`` — the paired form the index/workload read burst
    produces: row i matches query i against *key* page i, selects the first
    matching user slot in-kernel (header chunk masked), and gathers the
    slot's 64 B chunk from the paired *value* page i — search + slot select
    + value gather in ONE launch, no bitmap round trip through the host.

Gathered chunks come back *randomized* when the store is randomized (the
gather bus payload is the raw latch content); the controller/host
de-randomizes per chunk — tests cover the round trip.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.bits import mix2_32
from repro.core.randomize import _HI_SALT, _LO_SALT
from repro.kernels.lanes import (exclusive_prefix_count, f32_to_u32,
                                 pack_bits, u32_to_f32)

HIGHEST = jax.lax.Precision.HIGHEST   # f32 operands up to 2**16 - 1
SLOTS = 512
CHUNKS = 64
WORDS = 16
BITMAP_WORDS = 16
SLOTS_PER_CHUNK = 8
NO_SLOT = SLOTS          # first-match sentinel: no user slot matched


def _match_bits(lo, hi, q_lo, q_hi, m_lo, m_hi, page, seed, *,
                shape, randomized: bool):
    """Masked XOR match with in-VMEM stream regeneration (§IV-C1).

    ``page``/``seed`` are (PB, 1) uint32 per-page operands; the stream
    counter for slot s of page p is ``(page[p] * 512 + s) ^ seed[p]`` —
    identical to core/randomize.py, so one launch spans chips.
    """
    if randomized:
        slot = jax.lax.broadcasted_iota(jnp.uint32, shape, len(shape) - 1)
        ctr = (page * jnp.uint32(SLOTS) + slot) ^ seed
        q_lo = q_lo ^ mix2_32(ctr, _LO_SALT, jnp)
        q_hi = q_hi ^ mix2_32(ctr, _HI_SALT, jnp)
    mismatch = ((lo ^ q_lo) & m_lo) | ((hi ^ q_hi) & m_hi)
    return mismatch == 0


def _onehot(shape, pred):
    """f32 0/1 matrix of ``shape``, one where ``pred(row, col)`` holds."""
    row = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    col = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    return pred(row, col).astype(jnp.float32)


def _select_chunk(lane_on, lo, hi):
    """Compact one chunk's 8 slots out of the planes, on the MXU.

    lane_on: (R, 512) bool, set on the 8 lanes of the selected chunk (or
    on none); lo/hi: (R, 512) uint32 planes.  Returns (R, 16) uint32 chunk
    words with lo/hi interleaved per slot, zeros where nothing was
    selected.  Each output sums a single selected word, split into 16-bit
    halves so f32 holds it exactly.
    """
    to_even = _onehot((SLOTS, WORDS), lambda s, w: w == 2 * (s % 8))
    to_odd = _onehot((SLOTS, WORDS), lambda s, w: w == 2 * (s % 8) + 1)

    def pick(plane, spread):
        p = jnp.where(lane_on, plane, jnp.uint32(0))
        w_lo = jnp.dot(u32_to_f32(p & jnp.uint32(0xFFFF)), spread,
                       precision=HIGHEST, preferred_element_type=jnp.float32)
        w_hi = jnp.dot(u32_to_f32(p >> jnp.uint32(16)), spread,
                       precision=HIGHEST, preferred_element_type=jnp.float32)
        return f32_to_u32(w_lo) | (f32_to_u32(w_hi) << jnp.uint32(16))
    return pick(lo, to_even) | pick(hi, to_odd)


def _chunk_lanes(chunk_sel):
    """(R, 64) bool chunk selection -> (R, 512) bool lane selection."""
    spread = _onehot((CHUNKS, SLOTS),
                     lambda c, s: s // SLOTS_PER_CHUNK == c)
    return jnp.dot(chunk_sel.astype(jnp.bfloat16),
                   spread.astype(jnp.bfloat16),
                   preferred_element_type=jnp.float32) > 0


# ---------------------------------------------------------------------------
# Cross-product fused kernel: Q queries x N pages, same-page chunk gather.
# ---------------------------------------------------------------------------

def _fused_kernel(lo_ref, hi_ref, q_ref, m_ref, page_ref, seed_ref, bm_ref,
                  out_ref, cnt_ref, *, page_block: int, max_out: int,
                  randomized: bool):
    lo = lo_ref[...]                                   # (PB, 512)
    hi = hi_ref[...]
    q = q_ref[...]                                     # (1, 2): query j
    m = m_ref[...]
    bits = _match_bits(lo, hi, q[0, 0], q[0, 1], m[0, 0], m[0, 1],
                       page_ref[...], seed_ref[...],
                       shape=(page_block, SLOTS), randomized=randomized)

    # --- search output: packed 64 B bitmap per page
    bm_ref[...] = pack_bits(bits)[None]

    # --- gather phase, reusing the resident planes
    gather = _onehot((SLOTS, CHUNKS), lambda s, c: s // SLOTS_PER_CHUNK == c)
    chunk_bits = jnp.dot(bits.astype(jnp.bfloat16),
                         gather.astype(jnp.bfloat16),
                         preferred_element_type=jnp.float32) > 0
    pos = exclusive_prefix_count(chunk_bits)           # (PB, 64) int32
    m_ids = jax.lax.broadcasted_iota(jnp.int32,
                                     (page_block, max_out, CHUNKS), 1)
    sel = (pos[:, None, :] == m_ids) & chunk_bits[:, None, :]
    rows = page_block * max_out
    lanes = _chunk_lanes(sel.reshape(rows, CHUNKS))
    planes = [jnp.broadcast_to(p[:, None, :], (page_block, max_out, SLOTS)
                               ).reshape(rows, SLOTS) for p in (lo, hi)]
    out_ref[...] = _select_chunk(lanes, *planes).reshape(
        1, page_block, max_out, WORDS)
    cnt_ref[...] = chunk_bits.astype(jnp.int32).sum(axis=1)[None]


@functools.partial(jax.jit, static_argnames=("page_block", "max_out",
                                             "randomized", "interpret"))
def sim_fused_kernel(lo, hi, queries, masks, page_ids, page_seeds, *,
                     page_block: int = 16, max_out: int = 16,
                     randomized: bool = False, interpret: bool = True):
    """Fused multi-query search+gather.

    lo, hi:      (N, 512) uint32 planes, N a multiple of ``page_block``
    queries:     (Q, 2) uint32;  masks: (Q, 2) uint32
    page_ids:    (N,) uint32 per-page flash addresses
    page_seeds:  (N,) uint32 per-page device seeds
    returns:     (bitmaps (Q, N, 16) uint32,
                  gathered (Q, N, max_out, 16) uint32,
                  counts (Q, N) int32)
    """
    n = lo.shape[0]
    n_q = queries.shape[0]
    assert n % page_block == 0, (n, page_block)
    kernel = functools.partial(_fused_kernel, page_block=page_block,
                               max_out=max_out, randomized=randomized)
    return pl.pallas_call(
        kernel,
        grid=(n // page_block, n_q),
        in_specs=[
            pl.BlockSpec((page_block, SLOTS), lambda i, j: (i, 0)),
            pl.BlockSpec((page_block, SLOTS), lambda i, j: (i, 0)),
            pl.BlockSpec((1, 2), lambda i, j: (j, 0)),
            pl.BlockSpec((1, 2), lambda i, j: (j, 0)),
            pl.BlockSpec((page_block, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((page_block, 1), lambda i, j: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, page_block, BITMAP_WORDS),
                         lambda i, j: (j, i, 0)),
            pl.BlockSpec((1, page_block, max_out, WORDS),
                         lambda i, j: (j, i, 0, 0)),
            pl.BlockSpec((1, page_block), lambda i, j: (j, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_q, n, BITMAP_WORDS), jnp.uint32),
            jax.ShapeDtypeStruct((n_q, n, max_out, WORDS), jnp.uint32),
            jax.ShapeDtypeStruct((n_q, n), jnp.int32),
        ],
        interpret=interpret,
    )(jnp.asarray(lo, jnp.uint32), jnp.asarray(hi, jnp.uint32),
      jnp.asarray(queries, jnp.uint32), jnp.asarray(masks, jnp.uint32),
      jnp.asarray(page_ids, jnp.uint32).reshape(-1, 1),
      jnp.asarray(page_seeds, jnp.uint32).reshape(-1, 1))


# ---------------------------------------------------------------------------
# Paired lookup kernel: query i -> key page i -> value page i, one launch.
# ---------------------------------------------------------------------------

def _lookup_kernel(klo_ref, khi_ref, vlo_ref, vhi_ref, q_ref, m_ref,
                   kid_ref, kseed_ref, bm_ref, val_ref, slot_ref, *,
                   row_block: int, randomized: bool):
    klo = klo_ref[...]                                 # (RB, 512) key planes
    khi = khi_ref[...]
    q = q_ref[...]                                     # (RB, 2) per-row query
    m = m_ref[...]
    bits = _match_bits(klo, khi, q[:, 0:1], q[:, 1:2], m[:, 0:1], m[:, 1:2],
                       kid_ref[...], kseed_ref[...],
                       shape=(row_block, SLOTS), randomized=randomized)

    # Raw packed bitmap (bit-identical to a search command's bus payload).
    bm_ref[...] = pack_bits(bits)

    # First matching *user* slot: the header chunk (slots 0..7) never holds
    # entries — index software strips it host-side; here the strip happens
    # in-VMEM so the whole match->gather hop needs no host round trip.
    slot = jax.lax.broadcasted_iota(jnp.int32, (row_block, SLOTS), 1)
    user = bits & (slot >= SLOTS_PER_CHUNK)
    first = jnp.where(user, slot, NO_SLOT).min(axis=1, keepdims=True)
    slot_ref[...] = first                              # (RB, 1) int32

    # Gather the matched slot's chunk from the paired VALUE page row; a
    # row with no match (first == NO_SLOT) selects no lane.
    lanes = slot // SLOTS_PER_CHUNK == first // SLOTS_PER_CHUNK
    val_ref[...] = _select_chunk(lanes, vlo_ref[...], vhi_ref[...])


@functools.partial(jax.jit, static_argnames=("row_block", "randomized",
                                             "interpret"))
def sim_lookup_kernel(klo, khi, vlo, vhi, queries, masks, key_ids, key_seeds,
                      *, row_block: int = 8, randomized: bool = False,
                      interpret: bool = True):
    """Paired search->slot-select->value-gather, one launch for B lookups.

    klo, khi:   (B, 512) uint32 key-page planes (row i serves lookup i)
    vlo, vhi:   (B, 512) uint32 value-page planes, paired per row
    queries:    (B, 2) uint32 per-row queries;  masks: (B, 2) uint32
    key_ids:    (B,) uint32 key-page flash addresses (stream regeneration)
    key_seeds:  (B,) uint32 key-page device seeds
    returns:    (bitmaps (B, 16) uint32 — raw key-page match bitmaps,
                 value_words (B, 16) uint32 — the matched slot's 64 B value
                 chunk, randomized as stored,
                 slots (B,) int32 — first matching user slot, 512 if none)
    """
    b = klo.shape[0]
    assert b % row_block == 0, (b, row_block)
    kernel = functools.partial(_lookup_kernel, row_block=row_block,
                               randomized=randomized)
    bm, val, slot = pl.pallas_call(
        kernel,
        grid=(b // row_block,),
        in_specs=[
            pl.BlockSpec((row_block, SLOTS), lambda i: (i, 0)),
            pl.BlockSpec((row_block, SLOTS), lambda i: (i, 0)),
            pl.BlockSpec((row_block, SLOTS), lambda i: (i, 0)),
            pl.BlockSpec((row_block, SLOTS), lambda i: (i, 0)),
            pl.BlockSpec((row_block, 2), lambda i: (i, 0)),
            pl.BlockSpec((row_block, 2), lambda i: (i, 0)),
            pl.BlockSpec((row_block, 1), lambda i: (i, 0)),
            pl.BlockSpec((row_block, 1), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((row_block, BITMAP_WORDS), lambda i: (i, 0)),
            pl.BlockSpec((row_block, WORDS), lambda i: (i, 0)),
            pl.BlockSpec((row_block, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, BITMAP_WORDS), jnp.uint32),
            jax.ShapeDtypeStruct((b, WORDS), jnp.uint32),
            jax.ShapeDtypeStruct((b, 1), jnp.int32),
        ],
        interpret=interpret,
        name="sim_lookup",
    )(jnp.asarray(klo, jnp.uint32), jnp.asarray(khi, jnp.uint32),
      jnp.asarray(vlo, jnp.uint32), jnp.asarray(vhi, jnp.uint32),
      jnp.asarray(queries, jnp.uint32), jnp.asarray(masks, jnp.uint32),
      jnp.asarray(key_ids, jnp.uint32).reshape(-1, 1),
      jnp.asarray(key_seeds, jnp.uint32).reshape(-1, 1))
    return bm, val, slot[:, 0]
