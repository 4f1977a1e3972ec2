#!/usr/bin/env python3
"""The control of ``correct``: a cell served with one stated guarantee
broken, which the comparison has to judge not correct.

    python3 bench/control.py --workload netflow-w1.saturate \\
        --seeds 11,12,13 --seconds 10

Each seed is one run of the cell as ``bench/run.py`` makes it, in this
one process, with slot tables that take at most ``MAX_NEW`` new partial
matches per tick where the configuration states 2,048: smaller buffers
would make the join kernels cheaper, and break the stated guarantee of
zero overflow.  One JSON line per seed gives ``correct`` and the numbers
compared; the benchmark's own runs never run this.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

MAX_NEW = 8


def broken(cell):
    """The cell with its tables' ``max_new`` cut to ``MAX_NEW``."""
    cell.config["service"]["max_new"] = MAX_NEW
    return cell


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)

    from bench import harness, spec

    cell = broken(spec.resolve(args.workload))
    for seed in (int(s) for s in args.seeds.split(",")):
        try:
            r = harness.run_cell(cell, seed, args.seconds, False,
                                 time.perf_counter())
        except harness.NoChip as e:
            print(f"bench/control.py: {e}", file=sys.stderr)
            return 2
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "max_new": MAX_NEW, "correct": r["correct"],
                          "checks": r["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
