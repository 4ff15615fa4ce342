"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (never a module-level constant) so
importing this module touches no jax device state.  The dry-run entry point
(``repro.launch.dryrun``) sets ``XLA_FLAGS=--xla_force_host_platform_
device_count=512`` *before* importing jax; everything else sees the real
device count.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax

from repro.config.base import MeshConfig


def _make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    """``jax.make_mesh`` with every axis Auto (GSPMD-partitioned)."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_mesh(cfg: MeshConfig):
    return _make_mesh(cfg.shape, cfg.axis_names)


def make_test_mesh(shape: Optional[Tuple[int, ...]] = None,
                   axes: Tuple[str, ...] = ("data", "model")):
    """Small mesh over however many (real or forced) devices exist."""
    n = jax.device_count()
    if shape is None:
        shape = (n // min(n, 2), min(n, 2)) if n > 1 else (1, 1)
    return _make_mesh(shape, axes)


def make_wave_mesh(n_devices: int):
    """1-D mesh for sharded wave execution (platform DESIGN.md §11).

    The single ``"wave"`` axis partitions the :class:`~repro.platform.
    compute.ShardedBlockArena` (and each wave's slot/seed matrices) over
    ``n_devices`` devices.  On CPU the mesh is emulated by launching
    pytest/benchmarks under ``XLA_FLAGS=--xla_force_host_platform_
    device_count=8`` (SNIPPETS olmax idiom); callers must therefore ask
    for at most ``jax.device_count()`` devices — failing loudly here
    beats a confusing GSPMD error at dispatch time.
    """
    if n_devices < 1:
        raise ValueError(f"mesh needs >=1 device, got {n_devices}")
    avail = jax.device_count()
    if n_devices > avail:
        raise ValueError(
            f"wave mesh wants {n_devices} devices but only {avail} "
            "exist — run under XLA_FLAGS="
            "--xla_force_host_platform_device_count=8 to emulate")
    return _make_mesh((n_devices,), ("wave",))
