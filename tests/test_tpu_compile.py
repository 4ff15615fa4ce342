"""The platform's device programs compile for a TPU v5e.

Interpret mode cannot see what Mosaic refuses (tile-misaligned slices,
VMEM overruns) or what does not fit the chip's 16 GB of HBM.  These tests
compile the main path's programs at the widths the published data sets
give them, for a described ``v5e:2x2`` topology: no chip is needed and
nothing runs.  The topology is described inside a fixture (never while a
module is imported), so every xdist worker collects the same tests and
only the worker that runs this file loads the TPU compiler.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro.core import subsample as ss
from repro.kernels import subsample_gather as sg
from repro.platform import compute as pc

HBM_BYTES = 16e9                       # one TPU v5e chip
T = 512                                # MOMENTS draws per task (8 × 64)
NETFLIX_W = 1 << 16                    # longest movie, padded to pow2
EAGLET_W = 1 << 21                     # the 15× family, padded to pow2


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:             # no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _fits(compiled) -> int:
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert total < HBM_BYTES, total
    return total


@pytest.mark.parametrize("b,n,d", [(32, 16, 1024), (32, 8, NETFLIX_W),
                                   (1, 8, EAGLET_W)])
def test_stats_wave_kernel_compiles(one_chip, b, n, d):
    data = jax.ShapeDtypeStruct((b, n, sg.lane_width(d) // sg.LANES,
                                 sg.LANES), jnp.float32, sharding=one_chip)
    slots = jax.ShapeDtypeStruct((b,), jnp.int32, sharding=one_chip)
    idx = jax.ShapeDtypeStruct((b, T), jnp.int32, sharding=one_chip)
    kernel = functools.partial(sg.subsample_stats_wave, interpret=False)
    compiled = jax.jit(kernel).lower(data, slots, idx).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("workload,width", [(ss.NETFLIX_HIGH, NETFLIX_W),
                                            (ss.EAGLET, EAGLET_W)])
def test_map_task_compiles_and_fits(one_chip, workload, width):
    block = (64, width)                # a task of the largest knee size
    data = jax.ShapeDtypeStruct(block, jnp.float32, sharding=one_chip)
    months = jax.ShapeDtypeStruct(block, jnp.int32, sharding=one_chip)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)
    compiled = ss.map_task.lower(
        data, months, key, draws=workload.draws,
        draw_size=workload.draw_size, grid=workload.grid,
        statistic=workload.statistic).compile()
    _fits(compiled)


@pytest.mark.parametrize("tasks,width,wave", [(278, NETFLIX_W, 32),
                                              (7, EAGLET_W, 4)])
def test_moments_wave_program_fits(one_chip, tasks, width, wave):
    """The jitted MOMENTS wave over a whole data set's arena: Netflix
    (17,770 movies in tasks of 64) and EAGLET (400 families).  The kernel
    reads the wave's blocks in place, so the program copies not even one
    task block of the arena."""
    arena = jax.ShapeDtypeStruct((tasks, 64, width // sg.LANES, sg.LANES),
                                 jnp.float32, sharding=one_chip)
    vec = jax.ShapeDtypeStruct((wave,), jnp.int32, sharding=one_chip)
    compiled = pc._wave_fn("moments").lower(arena, vec, vec, n=T).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _fits(compiled)
    assert compiled.memory_analysis().temp_size_in_bytes < 64 * width * 4


def test_sharded_moments_wave_compiles(topo):
    mesh = Mesh(np.asarray(topo.devices[:4]), ("wave",))
    wave = NamedSharding(mesh, P("wave"))
    arena = jax.ShapeDtypeStruct((4 * 70, 64, NETFLIX_W // sg.LANES,
                                  sg.LANES), jnp.float32, sharding=wave)
    vec = jax.ShapeDtypeStruct((4, 8), jnp.int32, sharding=wave)
    compiled = pc._moments_wave_sharded_jit(mesh).lower(
        arena, vec, vec, n=T).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _fits(compiled)


@pytest.mark.parametrize("tasks,width", [(171, 1 << 14), (105, 1 << 15),
                                         (2, NETFLIX_W)])
def test_grid_wave_program_fits(one_chip, tasks, width):
    """The jitted ``monthly_mean`` wave over each Netflix shape bucket's
    arena, in waves of 32: the grid kernel reads the rows in place, so
    the program's temporaries stay far below one task block."""
    shape = (tasks, 64, width // sg.LANES, sg.LANES)
    arena = jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    months = jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)
    vec = jax.ShapeDtypeStruct((32,), jnp.int32, sharding=one_chip)
    compiled = pc._wave_fn("grid").lower(
        arena, months, vec, vec,
        **pc._grid_static(ss.NETFLIX_LOW, width)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _fits(compiled)
    assert compiled.memory_analysis().temp_size_in_bytes < 64 * width * 4


@pytest.mark.parametrize("workload", [ss.NETFLIX_HIGH, ss.NETFLIX_LOW])
def test_grid_task_program_compiles(one_chip, workload):
    """A task as a wave of one (the per-task path), at the longest
    movie's width, for both Netflix draw counts."""
    shape = (1, 64, NETFLIX_W // sg.LANES, sg.LANES)
    arena = jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    months = jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)
    vec = jax.ShapeDtypeStruct((1,), jnp.int32, sharding=one_chip)
    compiled = pc._wave_fn("grid").lower(
        arena, months, vec, vec,
        **pc._grid_static(workload, NETFLIX_W)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_sharded_grid_wave_compiles(topo):
    mesh = Mesh(np.asarray(topo.devices[:4]), ("wave",))
    wave = NamedSharding(mesh, P("wave"))
    shape = (4 * 43, 64, (1 << 14) // sg.LANES, sg.LANES)
    arena = jax.ShapeDtypeStruct(shape, jnp.float32, sharding=wave)
    months = jax.ShapeDtypeStruct(shape, jnp.int32, sharding=wave)
    vec = jax.ShapeDtypeStruct((4, 8), jnp.int32, sharding=wave)
    compiled = pc._grid_wave_sharded_jit(mesh).lower(
        arena, months, vec, vec,
        **pc._grid_static(ss.NETFLIX_LOW, 1 << 14)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _fits(compiled)


def test_chip_smoke_refuses_cpu():
    import chip_smoke

    with pytest.raises(SystemExit, match="needs a TPU"):
        chip_smoke.require_tpu(1)
