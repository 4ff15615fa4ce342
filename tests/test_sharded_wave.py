"""Sharded wave execution (DESIGN.md §11): bit-identity vs single-device.

The multi-device matrix — mesh sizes {1, 2, 4, 8} × engines {pallas, jnp}
× workloads (EAGLET, Netflix, epsilon-bounded moments) — needs 8 emulated
devices, so those tests carry ``@pytest.mark.multidevice`` and skip unless
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` was exported before
jax import.  ``test_multidevice_suite_in_subprocess`` runs them
hermetically from the plain single-device suite by re-spawning pytest
with the flag set; the CI ``multidevice`` job exports the flag itself and
selects ``-m multidevice`` directly (which deselects the wrapper).

The slot→(device, local-slot) indirection and the multi-shard reduce
ordering are pure-host properties and run everywhere.
"""

from __future__ import annotations

import dataclasses
import os
import pathlib
import subprocess
import sys
import threading

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from repro.core.subsample import EAGLET, NETFLIX_HIGH  # noqa: E402
from repro.kernels.subsample_gather import to_lane_layout  # noqa: E402
from repro.platform import compute as pc  # noqa: E402
from repro.platform.compute import MomentsSpec  # noqa: E402
from repro.platform.driver import PlatformSpec  # noqa: E402
from repro.platform.facade import Platform  # noqa: E402
from repro.platform.reduce import StreamingReduceTree, tree_add  # noqa: E402
from tests._hypothesis_compat import given, settings, st  # noqa: E402

MESH_SIZES = (1, 2, 4, 8)

WL_MOMENTS = MomentsSpec(draws=4, draw_size=16)
WL_EAGLET = dataclasses.replace(EAGLET, draws=2, draw_size=8)
WL_NETFLIX = dataclasses.replace(NETFLIX_HIGH, draws=2, draw_size=8)


def _dataset(n, length=96, seed=0, ragged=True):
    rng = np.random.default_rng(seed)
    samples, months = {}, {}
    for i in range(n):
        m = int(rng.integers(length // 2, length)) if ragged else length
        samples[i] = rng.normal(size=m).astype(np.float32)
        months[i] = rng.integers(0, 12, m).astype(np.int32)
    return samples, months


def _run(samples, months, workload, **spec_kw):
    base = dict(platform="BTS", n_workers=2, backend="threaded",
                wave="on", knee_bytes=2048.0)
    base.update(spec_kw)
    return Platform(PlatformSpec(**base)).run(samples, months, workload)


def _assert_same_result(ref, rep):
    assert ref.result is not None and rep.result is not None
    assert set(ref.result) == set(rep.result)
    for k in ref.result:
        np.testing.assert_array_equal(ref.result[k], rep.result[k])


# ---------------------------------------------------------------------------
# Multi-device matrix (8 emulated devices)
# ---------------------------------------------------------------------------


@pytest.mark.multidevice
@pytest.mark.parametrize(
    "engine,workload",
    [("pallas", WL_MOMENTS), ("jnp", WL_EAGLET), ("jnp", WL_NETFLIX)],
    ids=["pallas-moments", "jnp-eaglet", "jnp-netflix"])
def test_sharded_wave_bit_identical(mesh_devices, engine, workload):
    """One single-device reference, then every mesh size must reproduce
    it to the last bit — and issue the SAME number of device dispatches
    (the scheduler's wave partition is mesh-invariant; sharding changes
    where lanes execute, never how waves are cut)."""
    samples, months = _dataset(24, seed=3)
    ref = _run(samples, months, workload, engine=engine)
    for mesh in MESH_SIZES:
        rep = _run(samples, months, workload, engine=engine,
                   mesh_devices=mesh)
        _assert_same_result(ref, rep)
        assert rep.device_dispatches == ref.device_dispatches, \
            f"mesh={mesh} changed the wave partition"


@pytest.mark.multidevice
def test_sharded_epsilon_same_task_set(mesh_devices):
    """The epsilon early-stop must settle on the same executed task set
    (and hence the same subset-reduce result) at every mesh size: the
    claim cap that cuts waves is mesh-invariant, so convergence is
    checked at identical settlement points.  n_workers=1 serializes
    wave settlement so the stop point is reproducible."""
    rng = np.random.default_rng(1)
    samples = {i: rng.normal(size=64).astype(np.float32)
               for i in range(48)}
    months = {i: rng.integers(0, 12, 64).astype(np.int32)
              for i in samples}
    kw = dict(engine="pallas", n_workers=1, knee_bytes=256.0,
              epsilon=5.0, min_tasks=4, max_wave=4)
    ref = _run(samples, months, WL_MOMENTS, **kw)
    assert ref.stop_reason is not None, "epsilon target never converged"
    assert ref.tasks_executed < 48
    for mesh in MESH_SIZES:
        rep = _run(samples, months, WL_MOMENTS, mesh_devices=mesh, **kw)
        assert rep.stop_reason is not None
        assert rep.tasks_executed == ref.tasks_executed, \
            f"mesh={mesh} early-stopped on a different task set"
        _assert_same_result(ref, rep)


@pytest.mark.multidevice
def test_service_sharded_waves_bit_identical(mesh_devices):
    """ServicePool claims route through the query class' sharded arena
    (mesh_devices is part of the class cache key), and the served result
    matches the unsharded service bit for bit."""
    from repro.platform.service import PlatformService

    samples, months = _dataset(16, seed=7, ragged=False)

    def serve(**extra):
        spec = PlatformSpec(platform="BTS", n_workers=2,
                            backend="threaded", wave="on",
                            engine="pallas", knee_bytes=2048.0, **extra)
        with PlatformService(spec) as svc:
            handle = svc.register_dataset(samples, months)
            return svc.submit(handle, WL_MOMENTS).result(timeout=120.0)

    ref = serve()
    rep = serve(mesh_devices=4)
    assert set(ref) == set(rep)
    for k in ref:
        np.testing.assert_array_equal(ref[k], rep[k])


@pytest.mark.multidevice
def test_sharded_arena_physical_layout(mesh_devices):
    """Each task's physical arena row is its (device, local) slot in the
    device-major layout, and the row's content is the task's own block
    (the permutation at pack time must not mix blocks up)."""
    from repro.launch.mesh import make_wave_mesh
    from repro.platform.driver import plan_job

    samples, months = _dataset(10, seed=11, ragged=False)
    plan = plan_job(samples, months, WL_MOMENTS, sizing="kneepoint",
                    engine="pallas", n_exec=2, knee_bytes=1024.0)
    mesh = make_wave_mesh(4)
    arena = pc.ShardedBlockArena.pack(plan.tasks, plan.task_shape,
                                      plan.build_block, mesh, lanes=True,
                                      months=False)
    for key in arena.keys():
        data = np.asarray(arena.bucket(key)[0])
        per_dev = arena._per_dev[key]
        assert data.shape[0] == 4 * per_dev
    for task in plan.tasks:
        key, dev, local = arena._dev_slot[task.task_id]
        per_dev = arena._per_dev[key]
        assert arena._slot[task.task_id] == (key, dev * per_dev + local)
        want = to_lane_layout(plan.build_block(task)[0])
        got = np.asarray(arena.bucket(key)[0])[dev * per_dev + local]
        np.testing.assert_array_equal(want, got)


@pytest.mark.multidevice
@pytest.mark.parametrize("workload,engaged", [(WL_NETFLIX, True),
                                              (WL_EAGLET, False)],
                         ids=["monthly_mean", "alod"])
def test_sharded_grid_kernel_tasks(mesh_devices, workload, engaged):
    """A sharded ``monthly_mean`` wave computes every task's partial in
    the grid kernel, as ``stats()["grid_kernel_tasks"]`` counts; an
    ``alod`` job's waves never do."""
    from repro.platform.service import PlatformService

    samples, months = _dataset(16, seed=5)
    spec = PlatformSpec(platform="BTS", n_workers=2, backend="threaded",
                        wave="on", knee_bytes=2048.0, mesh_devices=4)
    with PlatformService(spec) as svc:
        handle = svc.register_dataset(samples, months)
        ticket = svc.submit(handle, workload)
        ticket.result(timeout=120.0)
        stats = svc.stats()
    (qc,) = handle._classes.values()
    assert isinstance(qc.wave_ctx.arena, pc.ShardedBlockArena)
    assert ticket.n_tasks > 1
    assert stats["grid_kernel_tasks"] == (ticket.n_tasks if engaged else 0)


# ---------------------------------------------------------------------------
# Hermetic wrapper: run the marked matrix under an emulated 8-device mesh
# ---------------------------------------------------------------------------


def test_multidevice_suite_in_subprocess():
    """The single-device suite spawns a pytest child with the XLA flag
    exported, so the multi-device matrix runs on every plain
    ``python -m pytest`` without the developer hand-setting anything."""
    if jax.device_count() >= 8:
        pytest.skip("already on a multi-device mesh; the marked tests "
                    "ran in-process")
    repo = pathlib.Path(__file__).resolve().parents[1]
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-m", "multidevice",
         str(pathlib.Path(__file__).resolve())],
        cwd=repo, env=env, capture_output=True, text=True, timeout=1500)
    tail = "\n".join((proc.stdout + proc.stderr).splitlines()[-30:])
    assert proc.returncode == 0, \
        f"multidevice suite failed (rc={proc.returncode}):\n{tail}"
    assert " passed" in proc.stdout, \
        f"multidevice suite selected nothing:\n{tail}"


# ---------------------------------------------------------------------------
# Slot indirection properties (pure host, run everywhere)
# ---------------------------------------------------------------------------


@settings(max_examples=200, deadline=None)
@given(st.integers(0, 100_000), st.integers(1, 64))
def test_shard_slot_round_trip(index, n_dev):
    dev, local = pc.shard_slot(index, n_dev)
    assert 0 <= dev < n_dev
    assert pc.unshard_slot(dev, local, n_dev) == index


@settings(max_examples=100, deadline=None)
@given(st.integers(1, 512), st.integers(1, 16))
def test_shard_slot_no_cross_device_aliasing(bucket, n_dev):
    """Distinct logical slots map to distinct physical rows: locals stay
    under the per-device stride, so ``dev * per_dev + local`` never
    collides across devices."""
    per_dev = -(-bucket // n_dev)
    seen = set()
    for i in range(bucket):
        dev, local = pc.shard_slot(i, n_dev)
        assert local < per_dev
        phys = dev * per_dev + local
        assert phys not in seen
        seen.add(phys)
    assert len(seen) == bucket


@settings(max_examples=100, deadline=None)
@given(st.integers(1, 256), st.integers(1, 16))
def test_shard_slot_tail_bucket_padding(bucket, n_dev):
    """The device-major physical order: real positions hold their own
    logical slot; tail-pad positions wrap to a valid earlier block (the
    ``% bucket`` copy), so every physical row is well-defined data."""
    per_dev = -(-bucket // n_dev)
    order = [pc.unshard_slot(dev, local, n_dev) % bucket
             for dev in range(n_dev) for local in range(per_dev)]
    assert len(order) == n_dev * per_dev
    assert all(0 <= x < bucket for x in order)
    for i in range(bucket):
        dev, local = pc.shard_slot(i, n_dev)
        assert order[dev * per_dev + local] == i


@settings(max_examples=100, deadline=None)
@given(st.integers(1, 512), st.integers(1, 16), st.integers(1, 64),
       st.integers(0, 511))
def test_contiguous_claim_occupancy_bound(bucket, n_dev, width, start):
    """The recompile-safety invariant behind the warmup-pinned kernel
    width: a contiguous FIFO claim of ``width`` logical slots lands at
    most ``ceil(width / n_dev)`` lanes on any one device, so
    ``shard_wave_width`` of the claim cap is never exceeded."""
    start = start % bucket
    run = [pc.shard_slot(i, n_dev)[0]
           for i in range(start, min(start + width, bucket))]
    if not run:
        return
    occupancy = np.bincount(run, minlength=n_dev)
    assert occupancy.max() <= -(-width // n_dev)
    assert pc.pow2_ceil(int(occupancy.max())) <= \
        pc.shard_wave_width(max(width, 1), n_dev)


def test_mesh_devices_requires_wave_execution():
    samples, months = _dataset(4, seed=0, ragged=False)
    spec = PlatformSpec(platform="BTS", n_workers=1, backend="threaded",
                        wave="off", engine="pallas", knee_bytes=2048.0,
                        mesh_devices=2)
    with pytest.raises(ValueError, match="mesh_devices"):
        Platform(spec).run(samples, months, WL_MOMENTS)


def test_wave_mesh_rejects_oversubscription():
    from repro.launch.mesh import make_wave_mesh

    with pytest.raises(ValueError, match="device"):
        make_wave_mesh(jax.device_count() + 1)
    with pytest.raises(ValueError, match=">=1"):
        make_wave_mesh(0)


# ---------------------------------------------------------------------------
# Multi-shard reduce ordering (satellite: combine_subset regression)
# ---------------------------------------------------------------------------


def _leaf(i):
    return {"sum": np.float32(1.0 + 0.1 * i), "count": np.float32(1.0)}


def test_reduce_tree_multi_shard_out_of_order_arrivals():
    """Partials arriving interleaved from several shard producer threads
    — each offering its own slice in reversed order — must combine to
    the same root as the sorted single-producer stream: the tree is
    keyed by task id, never by arrival order."""
    n, n_shards = 37, 4
    ref_tree = StreamingReduceTree(n)
    for i in range(n):
        ref_tree.offer(i, _leaf(i))
    ref = ref_tree.result(timeout=30.0)

    tree = StreamingReduceTree(n)
    barrier = threading.Barrier(n_shards)

    def producer(shard):
        mine = [i for i in range(n) if i % n_shards == shard]
        barrier.wait()
        for i in reversed(mine):
            tree.offer(i, _leaf(i))

    threads = [threading.Thread(target=producer, args=(s,))
               for s in range(n_shards)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    got = tree.result(timeout=30.0)
    for k in ref:
        np.testing.assert_array_equal(ref[k], got[k])


def test_combine_subset_depends_only_on_task_set():
    """The early-stop finalize: the same executed subset handed over in
    scrambled per-shard dict orders yields one bitwise answer, equal to
    the same leaves flowing through a live tree."""
    n = 29
    executed = [i for i in range(n) if i % 3 != 0]
    orders = [executed,
              list(reversed(executed)),
              executed[1::2] + executed[0::2],
              [executed[(7 * k) % len(executed)]
               for k in range(len(executed))]]
    roots = []
    for order in orders:
        assert sorted(order) == sorted(executed)
        items = {i: _leaf(i) for i in order}
        roots.append(StreamingReduceTree.combine_subset(n, items,
                                                        tree_add))
    live = StreamingReduceTree(n)
    for i in executed:
        live.offer(i, _leaf(i))
    live.wait_leaves(len(executed), timeout=30.0)
    roots.append(live.snapshot())
    live.close()
    for other in roots[1:]:
        for k in roots[0]:
            np.testing.assert_array_equal(roots[0][k], other[k])
