#!/usr/bin/env python3
"""Find the highest open-loop rate at which a cell's service sustains
the cell's query, once, on the chip.

    python3 bench/sweep.py --workload netflix-monthly-closed --seed 7 \\
        --seconds 20 --rates 0.25 0.5 1 2

One set-up, then one window per rate, in order: Poisson arrivals of the
cell's query at that rate.  A rate is sustained when every job due in the window
finished within a second of its close and the median latency of the
last third of the jobs is within 1.5x that of the first third (the
backlog did not grow).  An open-loop cell offers about 0.8x the highest
sustained rate, written into its traffic file by hand, with these
readings in PERF.md.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def say(text: str) -> None:
    print(f"# {text}", flush=True)


def reading(rate: float, window, seconds: float) -> dict:
    jobs = [j for j in window.jobs if j.result is not None]
    lat = [j.latency_s * 1e3 for j in jobs]
    third = max(1, len(lat) // 3)
    first = statistics.median(lat[:third]) if lat else float("nan")
    last = statistics.median(lat[-third:]) if lat else float("nan")
    import numpy as np

    return {"rate_hz": rate, "due": len(window.jobs), "finished": len(jobs),
            "p50_ms": statistics.median(lat) if lat else None,
            "p95_ms": float(np.percentile(lat, 95)) if lat else None,
            "first_third_p50_ms": first, "last_third_p50_ms": last,
            "window_s": window.seconds,
            "sustained": (len(jobs) == len(window.jobs)
                          and window.seconds <= seconds + 1.0
                          and last <= 1.5 * first)}


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, default=10.0)
    parser.add_argument("--rates", type=float, nargs="+", required=True)
    args = parser.parse_args(argv)

    from bench import harness, traffic

    s = harness.open_session(ROOT, args.workload, seed=args.seed,
                             t_start=T_START, say=say)
    rows = []
    try:
        for i, rate in enumerate(args.rates):
            mix = dict(s.cell.traffic, arrivals="poisson", rate_hz=rate,
                       schedule_seed=0)
            plan = traffic.plan(mix, args.seconds, args.seed + i)
            window = traffic.drive(s.service, s.handle, s.workload, plan,
                                   args.seconds)
            rows.append(reading(rate, window, args.seconds))
            say(json.dumps(rows[-1]))
            if len(rows) >= 2 and not any(r["sustained"] for r in rows[-2:]):
                break
    finally:
        s.close()
    ok = [r["rate_hz"] for r in rows if r["sustained"]]
    print(json.dumps({"readings": rows,
                      "highest_sustained_hz": max(ok) if ok else None}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
