"""Per-chunk data randomization (paper §IV-C1).

Modern SSDs XOR stored data with a deterministic pseudo-random stream so the
cell charge distribution stays balanced.  SiM's twist: the stream seed is
derived from the *chunk* address (not the page), so non-contiguous chunks can
be de-randomized independently by the gather command, and the *query key* is
randomized in the deserializer with the same stream — the stream then cancels
out inside the XOR match and matching runs directly on randomized data.

We implement the stream as a counter-based PRNG (two decorrelated fmix32
lanes per slot word), which is exactly the kind of LFSR-equivalent circuit a
flash deserializer uses, and is reproducible under both numpy and jnp (the
Pallas kernel regenerates the same stream on the fly in-VMEM).
"""
from __future__ import annotations

import numpy as np

from .bits import (CHUNKS_PER_PAGE, SLOTS_PER_CHUNK, SLOTS_PER_PAGE, mix2_32)

_LO_SALT = 0x9E3779B9
_HI_SALT = 0x7F4A7C15


def stream_words(page_addr, device_seed: int = 0, xp=np):
    """Randomization stream for one page: (512, 2) uint32.

    The counter for slot ``s`` of chunk ``c`` of page ``p`` is the global slot
    address ``(p*64 + c)*8 + s`` mixed with a device seed.  Chunk-addressed
    seeding means a chunk's stream never depends on its page offset.
    """
    page_addr = int(page_addr)
    chunk_base = np.uint32((page_addr * CHUNKS_PER_PAGE) & 0xFFFFFFFF)
    slot_idx = xp.arange(SLOTS_PER_PAGE, dtype=xp.uint32)
    ctr = (chunk_base * xp.uint32(SLOTS_PER_CHUNK) + slot_idx).astype(xp.uint32)
    ctr = ctr ^ xp.uint32(device_seed & 0xFFFFFFFF)
    lo = mix2_32(ctr, _LO_SALT, xp)
    hi = mix2_32(ctr, _HI_SALT, xp)
    return xp.stack([lo, hi], axis=-1)


def chunk_stream_words(page_addr: int, chunk_idx: int, device_seed: int = 0,
                       xp=np):
    """Stream for a single chunk: (8, 2) uint32 — used by gather-side
    de-randomization of non-contiguous chunks."""
    page_addr = int(page_addr)
    chunk_addr = np.uint32((page_addr * CHUNKS_PER_PAGE + chunk_idx) & 0xFFFFFFFF)
    slot_idx = xp.arange(SLOTS_PER_CHUNK, dtype=xp.uint32)
    ctr = (chunk_addr * xp.uint32(SLOTS_PER_CHUNK) + slot_idx).astype(xp.uint32)
    ctr = ctr ^ xp.uint32(device_seed & 0xFFFFFFFF)
    lo = mix2_32(ctr, _LO_SALT, xp)
    hi = mix2_32(ctr, _HI_SALT, xp)
    return xp.stack([lo, hi], axis=-1)


def chunk_stream_words_batch(page_addrs, chunk_ids, device_seeds, xp=np):
    """Streams for K (page, chunk, seed) triples at once: (K, 8, 2) uint32.

    Vectorized form of ``chunk_stream_words`` — one call de-randomizes every
    chunk of a whole gather/lookup burst instead of K per-chunk calls (the
    host tail of the sharded backend's flush).  ``device_seeds`` may be a
    scalar (one chip) or a (K,) array (burst spanning chips).
    """
    pages = xp.asarray(page_addrs, dtype=xp.uint32)
    chunks = xp.asarray(chunk_ids, dtype=xp.uint32)
    seeds = xp.broadcast_to(xp.asarray(device_seeds).astype(xp.uint32),
                            pages.shape)
    chunk_addr = (pages * xp.uint32(CHUNKS_PER_PAGE) + chunks).astype(
        xp.uint32)
    slot_idx = xp.arange(SLOTS_PER_CHUNK, dtype=xp.uint32)
    ctr = (chunk_addr[:, None] * xp.uint32(SLOTS_PER_CHUNK)
           + slot_idx[None, :]).astype(xp.uint32)
    ctr = ctr ^ seeds[:, None]
    lo = mix2_32(ctr, _LO_SALT, xp)
    hi = mix2_32(ctr, _HI_SALT, xp)
    return xp.stack([lo, hi], axis=-1)


def randomize_page_words(words, page_addr, device_seed: int = 0, xp=np):
    """XOR a page of (512, 2) slot words with its stream (involution)."""
    return xp.asarray(words, dtype=xp.uint32) ^ stream_words(
        page_addr, device_seed, xp)


def randomize_query(query_pair, page_addr, device_seed: int = 0, xp=np):
    """Randomize an 8-byte query against every slot position of a page.

    Returns (512, 2) uint32: the per-slot randomized query the deserializer
    broadcasts down the bitlines.  XORing this with the randomized page data
    equals XORing the plain query with plain data — the cancellation property
    the whole scheme rests on (verified by tests/property).
    """
    q = xp.asarray(query_pair, dtype=xp.uint32)
    return q[None, :] ^ stream_words(page_addr, device_seed, xp)
