"""Pallas TPU kernel: SiM gather — bitmap-selected chunk compaction.

Hardware mapping (DESIGN.md §2): the chip's column decoder walks the 64-bit
chunk-select bitmap and streams selected 64 B chunks onto the bus.  The TPU
analogue of a selection tree is a *one-hot matmul on the MXU*: the prefix sum
of the select bits defines a (max_out, 64) compaction permutation which,
multiplied against the page's (64, 16) chunk words, emits the selected chunks
front-packed and in order.

uint32 words cannot ride the MXU directly; each word is split into two
16-bit halves lifted to f32 (exact: one-hot rows sum at most one value
< 2^16), multiplied, and recombined — so the kernel is exact for arbitrary
bit patterns while the heavy lifting stays on the systolic array.

Block geometry: per grid step — chunks tile (PB, 64, 16) uint32 (PB pages,
4 KiB each), bitmap tile (PB, 2), output (PB, M, 16).  The one-hot tensor is
(PB, M, 64) f32 in VMEM; with PB=16, M=16 that is ~64 KiB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.lanes import exclusive_prefix_count, f32_to_u32, u32_to_f32

CHUNKS = 64
WORDS = 16
HIGHEST = jax.lax.Precision.HIGHEST   # f32 operands up to 2**16 - 1


def _gather_kernel(chunk_ref, bm_ref, out_ref, cnt_ref, *, page_block: int,
                   max_out: int):
    chunks = chunk_ref[...]                           # (PB, 64, 16) uint32
    bm = bm_ref[...]                                  # (PB, 2) uint32

    j = jax.lax.broadcasted_iota(jnp.uint32, (page_block, CHUNKS), 1)
    word = jnp.where(j < 32, bm[:, 0:1], bm[:, 1:2])  # (PB, 64)
    bit = ((word >> (j % 32)) & jnp.uint32(1)) == 1
    pos = exclusive_prefix_count(bit)                 # (PB, 64) int32

    m_ids = jax.lax.broadcasted_iota(jnp.int32,
                                     (page_block, max_out, CHUNKS), 1)
    sel = ((pos[:, None, :] == m_ids) & bit[:, None, :]
           ).astype(jnp.float32)                      # (PB, M, 64)

    # Split-16 exact integer matmul on the MXU.
    c_lo = u32_to_f32(chunks & jnp.uint32(0xFFFF))
    c_hi = u32_to_f32(chunks >> jnp.uint32(16))
    dn = (((2,), (1,)), ((0,), (0,)))                 # batch PB, contract 64
    out_lo = jax.lax.dot_general(sel, c_lo, dn, precision=HIGHEST,
                                 preferred_element_type=jnp.float32)
    out_hi = jax.lax.dot_general(sel, c_hi, dn, precision=HIGHEST,
                                 preferred_element_type=jnp.float32)
    out_ref[...] = f32_to_u32(out_lo) | (f32_to_u32(out_hi) << jnp.uint32(16))
    cnt_ref[...] = bit.astype(jnp.int32).sum(axis=1, keepdims=True)


@functools.partial(jax.jit,
                   static_argnames=("page_block", "max_out", "interpret"))
def sim_gather_kernel(chunks, bitmap_words, *, page_block: int = 16,
                      max_out: int = 16, interpret: bool = True):
    """chunks (N, 64, 16) uint32, bitmap (N, 2) uint32 ->
    (gathered (N, max_out, 16) uint32, counts (N, 1) int32)."""
    n = chunks.shape[0]
    assert n % page_block == 0, (n, page_block)
    grid = (n // page_block,)
    kernel = functools.partial(_gather_kernel, page_block=page_block,
                               max_out=max_out)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((page_block, CHUNKS, WORDS), lambda i: (i, 0, 0)),
            pl.BlockSpec((page_block, 2), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((page_block, max_out, WORDS), lambda i: (i, 0, 0)),
            pl.BlockSpec((page_block, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, max_out, WORDS), jnp.uint32),
            jax.ShapeDtypeStruct((n, 1), jnp.int32),
        ],
        interpret=interpret,
        name="sim_gather",
    )(jnp.asarray(chunks, jnp.uint32), jnp.asarray(bitmap_words, jnp.uint32))
