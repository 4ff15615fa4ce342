"""Quickstart: the paper's pipeline end to end in ~30 seconds on CPU.

1. Generate a Netflix-like subsampling workload.
2. Offline kneepoint phase: measure the task-size→cost curve, find the knee.
3. Run the job through ``repro.platform.Platform`` (kneepoint sizing →
   adaptive-replication datastore → two-phase scheduler → streaming
   reduce) and compare against large/tiniest tasks.
4. Replay the same job on the virtual-time simulated backend and check the
   statistics are bit-identical to the threaded run.

Run:  python examples/quickstart.py        (or PYTHONPATH=src python ...)
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np

from repro.core import subsample as ss
from repro.core.datastore import ReplicatedDataStore, ReplicationPolicy
from repro.data.synthetic import NetflixSpec, netflix_dataset
from repro.platform import (CacheOptions, Platform, PlatformSpec,
                            ScheduleOptions, measure_kneepoint)


def main():
    samples, months = netflix_dataset(NetflixSpec(n_movies=96,
                                                  mean_ratings=16384))
    total_mb = sum(s.nbytes for s in samples.values()) / 2**20
    print(f"dataset: {len(samples)} movies, {total_mb:.1f} MiB")

    knee_res, knee = measure_kneepoint(samples, months, ss.NETFLIX_HIGH,
                                       sizes=(1, 2, 4, 8, 16, 32, 64))
    print(f"\noffline kneepoint phase: knee at {knee / 2**10:.0f} KiB "
          f"({knee_res.reason})")

    store = ReplicatedDataStore(
        n_initial=2, policy=ReplicationPolicy(fetch_slo=2e-3))

    print(f"\n{'platform':8s} {'tasks':>6s} {'makespan':>9s} "
          f"{'throughput':>12s}")
    reports = {}
    for platform in ("BTS", "BLT", "BTT"):
        # options are grouped: scheduling policy under schedule=, the
        # worker-side block cache under cache= (see DESIGN.md §14)
        spec = PlatformSpec(
            platform=platform, n_workers=2, backend="threaded",
            knee_bytes=knee if platform == "BTS" else None,
            schedule=ScheduleOptions(balanced="auto", prefetch="auto"),
            cache=CacheOptions(capacity_bytes=64 << 20))
        rep = Platform(
            spec,
            datastore=store if platform == "BTS" else None,
        ).run(samples, months, ss.NETFLIX_HIGH)
        reports[platform] = rep
        print(f"{platform:8s} {rep.n_tasks:6d} {rep.makespan:8.2f}s "
              f"{rep.throughput_bps / 2**20:9.2f} MiB/s")

    bts = reports["BTS"]
    print(f"\nBTS vs BLT: {bts.throughput_bps / reports['BLT'].throughput_bps:.2f}x"
          f"   BTS vs BTT: "
          f"{bts.throughput_bps / reports['BTT'].throughput_bps:.2f}x")
    print(f"phase timings: "
          f"{ {k: round(v, 3) for k, v in bts.phases.items()} }")
    print(f"ready tasks at each settle: {bts.queue_depths[:8]} ... "
          f"stragglers: {bts.stragglers}")
    print(f"datastore: {store.stats()}")
    mean = bts.result["monthly_mean"]
    print(f"\nestimated monthly mean ratings (first 6 months): "
          f"{np.round(mean[:6], 2)}")

    # repeat the BTS query: the worker-side block cache filled on the
    # first run, so this one fetches ~nothing from the data nodes
    before = sum(store.fetch_counts().values())
    spec2 = PlatformSpec(
        platform="BTS", n_workers=2, backend="threaded", knee_bytes=knee,
        schedule=ScheduleOptions(balanced="auto", prefetch="auto"),
        cache=CacheOptions(capacity_bytes=64 << 20))
    rep2 = Platform(spec2, datastore=store).run(samples, months,
                                                ss.NETFLIX_HIGH)
    extra = sum(store.fetch_counts().values()) - before
    print(f"\nrepeat query with warm block cache: {extra} data-node "
          f"fetches, hit_rate={rep2.cache_stats['hit_rate']:.2f}")
    assert np.array_equal(rep2.result["monthly_mean"], mean), \
        "cached repeat run diverged"

    # same job, virtual-time backend at 8 workers: statistics must be
    # bit-identical (same seed, same engine, same reduce-tree order)
    sim = Platform(PlatformSpec(
        platform="BTS", n_workers=8, backend="simulated",
        knee_bytes=knee)).run(samples, months, ss.NETFLIX_HIGH)
    same = np.array_equal(sim.result["monthly_mean"], mean)
    print(f"\nsimulated backend (8 virtual workers): "
          f"makespan {sim.makespan:.2f}s, statistics bit-identical: {same}")
    assert same, "backends diverged"


if __name__ == "__main__":
    main()
