"""Compile the main-path kernels for a described TPU v5e, with no chip.

Interpret mode runs every kernel body on the CPU but never lowers it to
Mosaic, so a tiling, dtype or VMEM refusal only shows when compiling for
the chip.  These tests compile each launch that ``frontend.replay`` sends
through ``ShardedSsdBackend`` at the shapes ``chip_smoke.py`` launches,
for one device of a described ``v5e:2x2`` topology.  They need the TPU
compiler that ships with ``libtpu``; they skip where the topology cannot be
described.
"""
from __future__ import annotations

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
import chip_smoke  # noqa: E402


@pytest.fixture(scope="module")
def one_chip():
    """One device of a described v5e:2x2, with the persistent compile
    cache off: a compile for a described chip cannot be read back."""
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no libtpu, or it cannot describe the chip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("launch", ["sim_search", "sim_plan", "sim_lookup",
                                    "sim_gather"])
def test_main_path_launch_compiles_for_v5e(one_chip, launch):
    compiled = chip_smoke.main_path_launches(one_chip)[launch]().compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < 16 * 2**30          # one v5e holds 16 GB of HBM


@pytest.mark.parametrize("launch", ["sim_search", "sim_plan", "sim_lookup",
                                    "sim_gather"])
def test_main_path_kernel_carries_its_name(one_chip, launch):
    """Each kernel's ``pallas_call`` is named, so its custom-call op in the
    compiled program, and in a profiler trace, is ``%<launch>.<n>``."""
    text = chip_smoke.main_path_launches(one_chip)[launch]().compile() \
        .as_text()
    calls = [line.split("=", 1)[0].strip() for line in text.splitlines()
             if " custom-call(" in line and "tpu_custom_call" in line]
    assert calls and all(c.startswith(f"%{launch}.") for c in calls), calls


def test_plane_scatter_updates_the_arena_in_place_on_v5e(one_chip):
    """``scatter_planes`` at the YCSB cells' arena (65,536 rows) and a
    drain's 32 padded rows: the four donated planes alias the four outputs
    and no plane-sized temporary is made, so a restage writes its rows in
    place instead of copying the arena."""
    import jax
    import jax.numpy as jnp
    from repro.backend.planestore import scatter_planes

    def arg(*shape, dtype=jnp.uint32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    cap, rows = 65536, 32
    planes = (arg(cap, 512), arg(cap, 512), arg(cap, 1), arg(cap, 1))
    updates = (arg(rows, dtype=jnp.int32), arg(rows, 512), arg(rows, 512),
               arg(rows, 1), arg(rows, 1))
    mem = scatter_planes.lower(*planes, *updates).compile().memory_analysis()
    assert mem.alias_size_in_bytes >= 2 * cap * 512 * 4
    assert mem.temp_size_in_bytes < cap * 512 * 4
