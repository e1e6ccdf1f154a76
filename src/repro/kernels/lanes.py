"""In-kernel building blocks shared by the SiM Pallas kernels.

Each is written in operations that Mosaic lowers for a TPU: no reduction
over unsigned integers, no cast from uint32 straight to float, no cumsum
and no reshape that splits the lane axis.  Sums that would run along the
lanes go through the MXU instead, with operands that make the product
exact.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def pack_bits(bits):
    """(M, 512) bool match bits -> (M, 16) uint32 packed bitmap.

    Bit i of word w is slot 32*w + i (core/bits.py:pack_bitmap).  Two
    (M, 512) x (512, 16) matmuls sum each word's low and high 16 bits:
    bf16 holds 0/1 and the powers of two up to 2**15 exactly, and f32
    accumulates sums below 2**16 exactly.  (One (512, 32) product whose
    halves were then sliced apart lost words on a v5e: those whose high
    half was 0xff80 or more, a float NaN pattern once shifted, came back
    as 0x7fc0xxxx.)
    """
    n = bits.shape[-1]
    lane = jax.lax.broadcasted_iota(jnp.int32, (n, 16), 0)
    word = jax.lax.broadcasted_iota(jnp.int32, (n, 16), 1)
    b = bits.astype(jnp.bfloat16)

    def half(h: int):
        on = (lane // 32 == word) & ((lane % 32) // 16 == h)
        weight = jnp.where(on, 1 << (lane % 16), 0).astype(jnp.float32)
        return f32_to_u32(jnp.dot(b, weight.astype(jnp.bfloat16),
                                  preferred_element_type=jnp.float32))
    return half(0) | (half(1) << jnp.uint32(16))


def exclusive_prefix_count(bits):
    """(M, K) bool -> (M, K) int32: set bits strictly before each lane.

    A matmul with the strict upper triangle of ones; exact in bf16/f32.
    """
    k = bits.shape[-1]
    i = jax.lax.broadcasted_iota(jnp.int32, (k, k), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (k, k), 1)
    tri = (i < j).astype(jnp.bfloat16)
    return jnp.dot(bits.astype(jnp.bfloat16), tri,
                   preferred_element_type=jnp.float32).astype(jnp.int32)


def u32_to_f32(x):
    """uint32 below 2**31 -> float32 (Mosaic casts only from int32)."""
    return jax.lax.bitcast_convert_type(x, jnp.int32).astype(jnp.float32)


def f32_to_u32(x):
    """Non-negative integral float32 below 2**31 -> uint32."""
    return jax.lax.bitcast_convert_type(x.astype(jnp.int32), jnp.uint32)
