#!/usr/bin/env python3
"""The control of a cell's comparison: the plain reference put in the
program's place and computed in bfloat16, one precision below the
configuration's float32, judged by the same gaps and limits as a run.

    python3 bench/control.py --workload <cell> --seeds 101 102 103

For each seed: the cell's data set from that seed, the first
``sample_jobs`` job seeds that a run with that seed would draw, and for
each job the gaps between the bfloat16 reference and the float64 one.
Every seed's gaps have to fail the cell's limits in at least one number.
It needs no chip (the reference runs on the host), and it runs at the
cell's own size.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time
from typing import Dict, List, Optional

ROOT = pathlib.Path(__file__).resolve().parent.parent
if __name__ == "__main__":
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import ml_dtypes  # noqa: E402

from bench import data, harness, reference, traffic  # noqa: E402

LOWER = ml_dtypes.bfloat16


def control_gaps(cell: harness.Cell, seed: int,
                 jobs: Optional[int] = None) -> Dict[str, float]:
    """The widest gap of each output of the control over the jobs a run
    with ``seed`` would check first."""
    ds = data.generate(cell.config, seed)
    knee = float(cell.config["knee_bytes"])
    query = cell.traffic["query"]
    n = cell.checks["sample_jobs"] if jobs is None else jobs
    worst: Dict[str, float] = {}
    for job_seed in traffic.plan(cell.traffic, 1.0, seed).seeds[:n]:
        want = reference.job_result(ds, knee, int(job_seed), query)
        got = reference.job_result(ds, knee, int(job_seed), query, LOWER)
        for key, gap in reference.gaps(got, want).items():
            worst[key] = max(worst.get(key, 0.0), gap)
    return worst


def fails(cell: harness.Cell, gaps: Dict[str, float]) -> bool:
    limits = cell.checks["limits"]
    return any(gaps[k] > limits[k] for k in limits)


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", type=int, nargs="+", required=True)
    parser.add_argument("--jobs", type=int, default=None)
    args = parser.parse_args(argv)
    cell = harness.load_cell(ROOT, args.workload)
    rows = []
    for seed in args.seeds:
        t0 = time.perf_counter()
        gaps = control_gaps(cell, seed, args.jobs)
        rows.append({"seed": seed, "gaps": gaps, "fails": fails(cell, gaps),
                     "seconds": time.perf_counter() - t0})
        print(f"# {json.dumps(rows[-1])}", flush=True)
    least = {k: min(r["gaps"][k] for r in rows) for k in rows[0]["gaps"]}
    print(json.dumps({"workload": args.workload, "least": least,
                      "limits": cell.checks["limits"],
                      "all_fail": all(r["fails"] for r in rows)}))
    return 0 if all(r["fails"] for r in rows) else 1


if __name__ == "__main__":
    sys.exit(main())
