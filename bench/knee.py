#!/usr/bin/env python3
"""Find the knee of a cell's deployment: the highest Poisson arrival rate
at which the backlog does not grow.  One process, one set-up, then the
open-loop arrivals step through the rates:

    python3 bench/knee.py --workload netflow-w1.steady --seed 7 \\
        --rates 200,240,270,290,310 --step-seconds 24

For each step it prints the edges served per second and the backlog's
slope (edges waiting in the capture points and the frontier, by least
squares over the step's ticks).  A rate is sustained where the edges
served per second reach 98% of it and the backlog grows by under 2% of
it per second.  The last line is the JSON of the steps and the knee,
which goes into the configuration's ``knee_edges_per_s`` by hand.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def slope(points) -> float:
    """Least-squares slope of ``(t, y)`` points."""
    n = len(points)
    mt = sum(t for t, _ in points) / n
    my = sum(y for _, y in points) / n
    var = sum((t - mt) ** 2 for t, _ in points)
    return sum((t - mt) * (y - my) for t, y in points) / var if var else 0.0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True,
                    help="comma-separated arrival rates, edges/s, rising")
    ap.add_argument("--step-seconds", type=float, default=24.0)
    args = ap.parse_args(argv)

    from bench import harness, spec

    cell = spec.resolve(args.workload)
    rates = [float(r) for r in args.rates.split(",")]
    step = args.step_seconds
    try:
        run = harness.Run(cell, args.seed, rates=[(r, step) for r in rates])
    except harness.NoChip as e:
        print(f"bench/knee.py: {e}", file=sys.stderr)
        return 2
    loop, frontier = run.loop, run.frontier
    seen: list = []        # (t since the clock started, chunk, backlog)

    def on_tick(info):
        if loop.t0 is None:
            if loop.queued == 0:
                loop.start()
            return
        t = harness.clock() - loop.t0
        seen.append((t, info.chunk, loop.queued + frontier.buffered))
        if t >= step * len(rates):
            raise harness.StopRun

    run.serve(on_tick)
    out = []
    for i, rate in enumerate(rates):
        lo, hi = i * step, (i + 1) * step
        ticks = [x for x in seen if lo < x[0] <= hi]
        if len(ticks) < 2:
            out.append({"rate": rate, "ticks": len(ticks)})
            continue
        served = sum(c for _, c, _ in ticks[1:]) / (ticks[-1][0] - ticks[0][0])
        grow = slope([(t, b) for t, _, b in ticks])
        out.append({"rate": rate, "ticks": len(ticks), "served_per_s": served,
                    "backlog_slope_per_s": grow,
                    "sustained": served >= 0.98 * rate
                    and grow < 0.02 * rate})
        harness.say(f"rate {rate}: {len(ticks)} ticks, served "
                    f"{served:.3f} edges/s, backlog slope {grow:.3f} edges/s")
    knee = max((s["rate"] for s in out if s.get("sustained")), default=None)
    print(json.dumps({"workload": args.workload, "seed": args.seed,
                      "steps": out, "knee_edges_per_s": knee}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
