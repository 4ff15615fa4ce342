#!/usr/bin/env python3
"""Run one cell of the chip benchmark and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Cells, configurations, traffic mixes and metrics are files found by the
names in ``BENCHMARK.json`` (see ``bench/harness.py``).  Informational
lines go to standard output before the result; the last line of standard
output is the result's JSON object.  The numbers compared with the plain
reference, each with its limit, are the last lines of standard error.
Without a TPU (or with fewer chips than the cell asks for) the run exits
non-zero and prints no result.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def say(text: str) -> None:
    print(f"# {text}", flush=True)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)

    from bench import harness

    try:
        result = harness.run_cell(ROOT, args.workload, seed=args.seed,
                                  seconds=args.seconds,
                                  trace=bool(args.trace), t_start=T_START,
                                  say=say)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    harness.print_checks(result["check"])
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
