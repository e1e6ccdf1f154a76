"""Pallas TPU kernels for the SiM hot paths.

Every kernel directory ships three files:
  <name>.py — the pl.pallas_call kernel with explicit BlockSpec tiling
  ops.py    — the jit'd public wrapper (padding, layout, interpret flag)
  ref.py    — the pure-jnp oracle the kernel is validated against

On a TPU the kernels lower to Mosaic.  Anywhere else — the CPU test runs —
they execute with ``interpret=True``: the kernel body runs step by step
under the Pallas interpreter.  ``default_interpret()`` picks by backend.
"""
import os

import jax

# <checkout>/.jax_cache: four levels up from src/repro/kernels/__init__.py.
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def enable_compile_cache() -> str:
    """Keep compiled programs in JAX's persistent cache; returns its path.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no
    other directory is set here.  Otherwise the cache lives at the fixed
    ``COMPILE_CACHE_DIR`` inside the checkout, so a later run finds what an
    earlier one wrote.  Every compile is kept, however short: a Mosaic
    kernel compiles in less than JAX's default one-second floor.  Call it
    before the first compile.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = COMPILE_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
