#!/usr/bin/env python3
"""Bring-up check: the subsampling service's main path on a TPU, at the
published data scale.

    python chip_smoke.py              # one chip: every phase below
    python chip_smoke.py --chips 4    # four chips: the sharded-wave phase

One chip.  Netflix (17,770 movies, ~100.5 M ratings) and EAGLET (400
families, ~230 MB with the 15x and 7x outliers) are generated from
``--seed``, registered with one ``PlatformService`` and served through
``submit(...).result()``: ``NETFLIX_HIGH`` (jnp, one task per dispatch),
``NETFLIX_LOW`` (jnp waves over the resident arena), ``EAGLET`` (alod,
jnp) and ``MOMENTS`` (the Pallas wave kernel) on each data set.  Both
handles stay alive, and each keeps its query classes' wave arenas
resident, so the chip ends up holding both data sets' arenas at once.  Then one-shot ``Platform.run`` jobs
repeat ``NETFLIX_LOW`` and ``MOMENTS``.  Checked:

* eight tasks of every query against a float64 NumPy recomputation of
  the task's partial from the same block and the same drawn indices
  (threefry draws the same bits on every backend); wave engines run
  the eight together in full-width waves, each in its own lane;
* job invariants: the total draw count is exact, monthly means lie in
  [1, 5];
* ``MOMENTS`` from the service equals ``Platform.run`` bit for bit (the
  jnp comparison is printed, not asserted);
* the lowered ``MOMENTS`` wave holds ``tpu_custom_call``: the kernel is
  compiled by Mosaic, not interpreted.  The compiled size of every wave
  program is printed.

Four chips.  Netflix ``MOMENTS`` and ``NETFLIX_LOW`` served with
``WaveOptions(mesh_devices=4)`` must equal the same jobs without a mesh
bit for bit; the arena's bytes on each device are printed.

Everything runs in this one process.  Without a TPU the script exits
non-zero before doing any work.  Wall times, compile counts and memory
are printed as set-up facts, not benchmark numbers.  The last line of
standard output is the JSON result.
"""

from __future__ import annotations

import argparse
import collections
import gc
import json
import pathlib
import sys
import time

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

# published scale: the Netflix Prize data set and the thesis' EAGLET run
NETFLIX_SCALE = dict(n_movies=17770, mean_ratings=4990)
EAGLET_SCALE = dict(n_families=400, mean_markers=129_000)
# tolerance of a float32 task partial against its float64 recomputation
RTOL, ATOL = 1e-4, 1e-3
TASKS_CHECKED = 8
SHARDED_CHIPS = 4


def require_tpu(n_chips: int):
    """The devices, or exit non-zero when JAX finds no TPU (or too few)."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, JAX found "
                         f"{devices[0].platform!r}")
    if len(devices) < n_chips:
        raise SystemExit(f"chip_smoke: needs {n_chips} chips, JAX found "
                         f"{len(devices)}")
    return devices


def say(*parts) -> None:
    print("#", *parts, flush=True)


class CompileCounter:
    """Backend compiles (and their seconds) seen by this process."""

    def __init__(self):
        import jax

        self.count, self.seconds = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_kw) -> None:
        if event.endswith("backend_compile_duration"):
            self.count += 1
            self.seconds += duration


def memory_line(label: str) -> None:
    import jax

    for dev in jax.local_devices():
        stats = dev.memory_stats() or {}
        say(f"memory {label} device {dev.id}: peak_bytes_in_use="
            f"{stats.get('peak_bytes_in_use')} bytes_in_use="
            f"{stats.get('bytes_in_use')}")


# ---------------------------------------------------------------------------
# data
# ---------------------------------------------------------------------------


def netflix(seed: int):
    from repro.data.synthetic import NetflixSpec, netflix_dataset

    return netflix_dataset(NetflixSpec(seed=seed, **NETFLIX_SCALE))


def eaglet(seed: int):
    from repro.data.synthetic import EagletSpec, eaglet_dataset

    return eaglet_dataset(EagletSpec(seed=seed, **EAGLET_SCALE))


def describe(name: str, samples) -> None:
    n = sum(a.shape[0] for a in samples.values())
    longest = max(a.shape[0] for a in samples.values())
    say(f"data {name}: {len(samples)} samples, {n} values, longest "
        f"{longest}, {sum(a.nbytes for a in samples.values())} bytes")


# ---------------------------------------------------------------------------
# reference: one task's partial recomputed in float64
# ---------------------------------------------------------------------------


def reference_partial(block: np.ndarray, months: np.ndarray, seed: int,
                      workload) -> dict:
    """The map task's partial from the task's padded block and the
    indices ``jax.random`` draws for its seed, summed in float64."""
    import jax
    import jax.numpy as jnp

    key = jax.random.PRNGKey(seed)
    ns, sl = block.shape
    if workload.statistic == "moments":
        n = workload.draws * workload.draw_size
        idx = np.asarray(jax.random.randint(key, (n,), 0, ns,
                                            dtype=jnp.int32))
        hits = np.bincount(idx, minlength=ns)
        total = np.zeros(sl)
        total_sq = np.zeros(sl)
        for r in np.flatnonzero(hits):
            row = block[r].astype(np.float64)
            total += hits[r] * row
            total_sq += hits[r] * row * row
        return {"sum": total, "sumsq": total_sq, "count": float(n)}
    grid = workload.grid
    idx = np.asarray(jax.random.randint(
        key, (workload.draws, ns, workload.draw_size), 0, sl))
    rows = np.arange(ns)[None, :, None]
    drawn = block[rows, idx].astype(np.float64)   # [draws, ns, draw_size]
    if workload.statistic == "alod":
        pos = idx.astype(np.float32) / np.float32(sl)
        cell = np.clip((pos * np.float32(grid)).astype(np.int32), 0,
                       grid - 1).ravel()
        mean = drawn.mean(axis=2, keepdims=True)
        sd = drawn.std(axis=2, keepdims=True) + 1e-6
        z = np.abs((drawn - mean) / sd).ravel()
        return {"sum_curve": np.bincount(cell, weights=z, minlength=grid),
                "hits": np.bincount(cell, minlength=grid),
                "count": float(ns * workload.draws)}
    month = np.clip(months[rows, idx], 0, grid - 1).ravel()
    return {"sum": np.bincount(month, weights=drawn.ravel(),
                               minlength=grid),
            "count": np.bincount(month, minlength=grid)}


def engine_partials(qc, tasks, seed: int) -> dict:
    """``{task_id: partial}`` from the query class's own engine.  A wave
    engine runs the tasks together through its resident wave program,
    as many to a wave as its width holds, so each task sits in its own
    lane; a per-task engine dispatches them one by one."""
    from repro.platform import compute as pc

    ctx = qc.wave_ctx
    if ctx is None:
        return {t.task_id: pc.run_map_task(*qc.block(t), seed + t.task_id,
                                           qc.workload, qc.engine)
                for t in tasks}
    by_shape = collections.defaultdict(list)
    for t in tasks:
        by_shape[ctx.task_shape(t)].append(t)
    got = {}
    for group in by_shape.values():
        width = ctx.cap(group[0])
        for i in range(0, len(group), width):
            wave = group[i:i + width]
            seeds = np.asarray([seed + t.task_id for t in wave], np.int32)
            got.update((t.task_id, out)
                       for t, out in zip(wave, ctx.run(wave, seeds)))
    return got


def check_tasks(label: str, qc, seed: int) -> None:
    """Eight tasks of a query class, computed by the class's own engine,
    against :func:`reference_partial`."""
    tasks = qc.plan.tasks
    picks = np.unique(np.linspace(0, len(tasks) - 1, TASKS_CHECKED)
                      .astype(int))
    checked = [tasks[i] for i in picks]
    partials = engine_partials(qc, checked, seed)
    worst = 0.0
    for task in checked:
        task_seed = seed + task.task_id
        block, months = qc.plan.build_block(task)
        got = partials[task.task_id]
        want = reference_partial(block, months, task_seed, qc.workload)
        assert got.keys() == want.keys(), (label, got.keys(), want.keys())
        for name, ref in want.items():
            val = np.asarray(got[name], np.float64)
            ref = np.asarray(ref, np.float64)
            assert val.shape == ref.shape, (label, name, val.shape)
            assert np.all(np.isfinite(val)), (label, name, task.task_id)
            if name in ("count", "hits"):
                assert np.array_equal(val, ref), (label, name, task.task_id)
                continue
            assert np.allclose(val, ref, rtol=RTOL, atol=ATOL), (
                label, name, task.task_id,
                float(np.max(np.abs(val - ref))))
            scale = np.maximum(np.abs(ref), 1.0)
            worst = max(worst, float(np.max(np.abs(val - ref) / scale)))
    lanes = ("" if qc.wave_ctx is None else
             f" (waves of {qc.wave_ctx.cap(checked[0])} lanes)")
    say(f"reference {label}: tasks {picks.tolist()} of {len(tasks)}{lanes} "
        f"match float64 (rtol={RTOL}, atol={ATOL}); worst relative error "
        f"{worst:.3g}")


def check_job(label: str, result: dict, qc) -> None:
    """Job-level invariants of a finished query."""
    wl, plan = qc.workload, qc.plan
    n_tasks = len(plan.tasks)
    if wl.statistic == "moments":
        want = n_tasks * wl.draws * wl.draw_size
        assert result["count"] == want, (label, result["count"], want)
        assert np.all(np.isfinite(result["mean"])), label
        assert np.all(result["var"] >= 0), label
    elif wl.statistic == "monthly_mean":
        want = n_tasks * plan.max_count * wl.draws * wl.draw_size
        got = float(np.sum(np.asarray(result["count"], np.float64)))
        assert got == want, (label, got, want)
        assert np.all(np.asarray(result["count"]) > 0), label
        mean = np.asarray(result["monthly_mean"])
        assert np.all((mean >= 1.0) & (mean <= 5.0)), (
            label, float(mean.min()), float(mean.max()))
    else:
        want = n_tasks * plan.max_count * wl.draws
        assert result["n"] == want, (label, result["n"], want)
        assert np.all(np.isfinite(result["alod"])), label
    say(f"invariants {label}: {n_tasks} tasks of {plan.max_count} samples, "
        f"draw count exact ({want})")


def identical(a: dict, b: dict) -> bool:
    return a.keys() == b.keys() and all(
        np.array_equal(np.asarray(a[k]), np.asarray(b[k])) for k in a)


def inspect_wave_program(label: str, qc) -> None:
    """Lower the query class's wave program for its first bucket, print
    its compiled sizes, and for MOMENTS and ``monthly_mean`` assert that
    it holds the Mosaic kernel (``tpu_custom_call``): compiled, not
    interpreted."""
    from repro.platform import compute as pc

    ctx, wl = qc.wave_ctx, qc.workload
    key = ctx.arena.keys()[0]
    data, months = ctx.arena.bucket(key)
    slots = np.zeros(ctx.wave_pad[key], np.int32)
    if qc.engine == "pallas":
        lowered = pc._wave_fn("moments").lower(
            data, slots, slots, n=wl.draws * wl.draw_size)
        assert "tpu_custom_call" in lowered.as_text(), (
            f"{label}: MOMENTS wave is not a Mosaic kernel")
        kernel = "tpu_custom_call present; "
    else:
        kind, static = pc._jnp_wave_kind(wl, ctx.arena.width(key))
        lowered = pc._wave_fn(kind).lower(data, months, slots, slots,
                                          **static)
        kernel = ""
        if kind == "grid":
            assert "tpu_custom_call" in lowered.as_text(), (
                f"{label}: monthly_mean wave is not a Mosaic kernel")
            kernel = "tpu_custom_call present; "
    mem = lowered.compile().memory_analysis()
    say(f"wave program {label}: {kernel}compiled arguments "
        f"{mem.argument_size_in_bytes} B, temporaries "
        f"{mem.temp_size_in_bytes} B")


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def query_class(svc, handle, workload):
    """The service's cached query class for ``workload`` (planned,
    packed and warmed by the submit that ran it)."""
    from repro.platform import compute as pc
    from repro.platform.driver import wave_enabled

    engine = pc.resolve_engine(workload.statistic, svc.spec.engine)
    qc, built_now = handle.query_class(
        workload, spec=svc.spec, engine=engine,
        sizing=svc.plat.task_sizing, n_exec=svc.spec.n_workers,
        wave_on=wave_enabled(svc.spec, engine, workload))
    assert not built_now, "query class was not built by its submit"
    return qc


def serve(svc, handle, name: str, workloads, *, seed: int,
          compiles: CompileCounter):
    """Serve ``workloads`` on one registered data set; returns
    ``({workload name: result}, {workload name: knee bytes})``."""
    results, knees = {}, {}
    for wl in workloads:
        label = f"{name}/{wl.name}"
        c0, t0 = compiles.count, time.perf_counter()
        ticket = svc.submit(handle, wl)
        result = ticket.result()
        took = time.perf_counter() - t0
        qc = query_class(svc, handle, wl)
        knees[wl.name] = qc.plan.knee_bytes
        say(f"served {label}: engine {qc.engine}, "
            f"{'waves' if qc.wave_ctx is not None else 'per-task'}, "
            f"{ticket.n_tasks} tasks, {ticket.device_dispatches} "
            f"dispatches, knee {qc.plan.knee_bytes:.0f} B, "
            f"{qc.arena_bytes:.0f} arena bytes; set-up wall "
            f"{took:.3f} s with {compiles.count - c0} compiles")
        check_job(label, result, qc)
        check_tasks(label, qc, seed)
        if qc.wave_ctx is not None:
            inspect_wave_program(label, qc)
        results[wl.name] = result
    return results, knees


def one_shot(name: str, samples, months, workload, served: dict, *,
             knee: float, seed: int, must_match: bool) -> None:
    """``Platform.run`` of one query, compared with the service's answer
    for the same seed and knee."""
    from repro.platform import Platform, PlatformSpec

    spec = PlatformSpec(platform="BTS", backend="threaded", seed=seed,
                        knee_bytes=knee)
    t0 = time.perf_counter()
    report = Platform(spec).run(samples, months, workload)
    same = identical(report.result, served)
    say(f"Platform.run {name}/{workload.name}: {report.n_tasks} tasks, "
        f"{report.device_dispatches} dispatches, set-up wall "
        f"{time.perf_counter() - t0:.3f} s; bit-identical to the service: "
        f"{same}")
    if must_match:
        assert same, f"{name}/{workload.name}: service != Platform.run"


def run_one_chip(seed: int, compiles: CompileCounter) -> None:
    from repro.core import subsample as ss
    from repro.platform import PlatformService, PlatformSpec
    from repro.platform.compute import MOMENTS

    # NETFLIX_LOW runs after MOMENTS, so its waves' temporaries come on
    # top of both resident Netflix arenas in the peak
    phases = (("netflix", netflix, (ss.NETFLIX_HIGH, MOMENTS,
                                    ss.NETFLIX_LOW)),
              ("eaglet", eaglet, (ss.EAGLET, MOMENTS)))
    data, handles, served, knees = {}, {}, {}, {}
    spec = PlatformSpec(platform="BTS", backend="threaded", seed=seed)
    with PlatformService(spec) as svc:
        for name, make, workloads in phases:
            t0 = time.perf_counter()
            data[name] = make(seed)
            describe(name, data[name][0])
            say(f"generated {name} in {time.perf_counter() - t0:.3f} s")
            # a handle owns its query classes' arenas: holding both
            # handles keeps both data sets resident, as a user would
            handles[name] = svc.register_dataset(*data[name], name=name)
            served[name], knees[name] = serve(svc, handles[name], name,
                                              workloads, seed=seed,
                                              compiles=compiles)
            memory_line(f"service holding {' and '.join(handles)}")
    del handles
    gc.collect()
    for name, workload, must_match in (
            ("netflix", ss.NETFLIX_LOW, False), ("netflix", MOMENTS, True),
            ("eaglet", MOMENTS, True)):
        one_shot(name, *data[name], workload, served[name][workload.name],
                 knee=knees[name][workload.name], seed=seed,
                 must_match=must_match)
        gc.collect()
    memory_line("after Platform.run")


def arena_bytes_per_device(arena) -> dict:
    per = collections.Counter()
    for key in arena.keys():
        for arr in arena.bucket(key):
            if arr is not None:
                for shard in arr.addressable_shards:
                    per[shard.device.id] += shard.data.nbytes
    return dict(sorted(per.items()))


def run_sharded(seed: int, compiles: CompileCounter) -> None:
    """Netflix MOMENTS and NETFLIX_LOW with and without a wave mesh."""
    from repro.core import subsample as ss
    from repro.platform import PlatformService, PlatformSpec, WaveOptions
    from repro.platform.compute import MOMENTS

    n_dev = SHARDED_CHIPS
    samples, months = netflix(seed)
    describe("netflix", samples)
    workloads = (MOMENTS, ss.NETFLIX_LOW)
    results = {}
    knees = {}
    for mesh in (None, n_dev):
        spec = PlatformSpec(platform="BTS", backend="threaded", seed=seed,
                            waves=WaveOptions(mesh_devices=mesh))
        with PlatformService(spec) as svc:
            for wl in workloads:
                handle = svc.register_dataset(samples, months,
                                              name="netflix",
                                              knee_bytes=knees.get(wl.name))
                c0, t0 = compiles.count, time.perf_counter()
                results[mesh, wl.name] = svc.submit(handle, wl).result()
                qc = query_class(svc, handle, wl)
                knees[wl.name] = qc.plan.knee_bytes
                say(f"served netflix/{wl.name} mesh_devices={mesh}: "
                    f"{len(qc.plan.tasks)} tasks, set-up wall "
                    f"{time.perf_counter() - t0:.3f} s with "
                    f"{compiles.count - c0} compiles; arena bytes per "
                    f"device {arena_bytes_per_device(qc.wave_ctx.arena)}")
                check_job(f"netflix/{wl.name} mesh={mesh}",
                          results[mesh, wl.name], qc)
                del handle, qc
        gc.collect()
        memory_line(f"after mesh_devices={mesh}")
    for wl in workloads:
        same = identical(results[None, wl.name], results[n_dev, wl.name])
        say(f"sharded netflix/{wl.name}: mesh_devices={n_dev} bit-identical "
            f"to no mesh: {same}")
        assert same, f"netflix/{wl.name}: sharded result differs"


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, SHARDED_CHIPS),
                        default=1,
                        help="4 runs only the sharded-wave phase")
    parser.add_argument("--seed", type=int, default=0)
    args = parser.parse_args(argv)

    devices = require_tpu(args.chips)
    from repro.platform.compile_cache import enable_compile_cache

    say(f"compile cache: {enable_compile_cache(ROOT / '.jax_cache')}")
    compiles = CompileCounter()
    t0 = time.perf_counter()
    if args.chips == SHARDED_CHIPS:
        run_sharded(args.seed, compiles)
    else:
        run_one_chip(args.seed, compiles)
    say(f"all phases passed in {time.perf_counter() - t0:.3f} s wall, "
        f"{compiles.count} backend compiles taking {compiles.seconds:.3f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
