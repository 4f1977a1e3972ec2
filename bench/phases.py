#!/usr/bin/env python3
"""One run of a benchmark cell, and where its ticks' time went:

    python3 bench/phases.py --workload <cell> --seed <n> --seconds <s> \\
        [--trace 0|1] [--slice PATH] [--keep PATH]

It runs the cell as ``bench/run.py`` does (``bench.harness.run_cell``)
and keeps what the harness reduces away:

* every run: the tick latency each window tick reported (``ServeInfo``),
  their median, and the last tick's live-row counters beside a count of
  the live rows taken on the host from the slot states after the run;
* with ``--trace 1``: the profile the harness reads, reduced once more
  by ``bench.devscope`` before the harness deletes it: device time per
  tick by engine phase and kernel, the share of device-idle time no
  program span covers, and the longest ops and idle gaps by the
  program's names.  ``--slice`` writes the scoped ops and annotations
  around the longest idle gap (the test data of ``bench/tests``), and
  ``--keep`` the profile itself, gzipped.

The last line of standard output is the JSON of all of it, with the
harness's own result line under ``result``.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def host_rows(svc) -> tuple[int, int]:
    """Live and allocated table rows of every slot group, counted on the
    host from the slot states."""
    import numpy as np

    live = cap = 0
    for g in svc._iter_groups():
        if g.idle:
            continue
        eng = g.sstate.engines
        for t in [t for sub in eng.levels for t in sub] + list(eng.l0):
            v = np.asarray(t.valid)
            live += int(v.sum())
            cap += v.size
    return live, cap


def cut_slice(rec: dict, margin_ns: int = 20_000_000) -> dict:
    """The ops and program spans within ``margin_ns`` of device 0's
    longest idle gap in the window, with that span as the window, and
    the last Pallas kernel of each scope before it (outside it)."""
    from bench import devtrace

    lo, hi = devtrace.window_of(rec)
    dev = min(rec["devices"], key=int)
    ops = rec["devices"][dev]
    busy = devtrace.union(c for s, d, *_ in ops
                          if (c := devtrace.clip(s, s + d, lo, hi)))
    s, e = max(devtrace.gaps(busy, lo, hi), key=lambda g: g[1] - g[0])
    a, b = max(lo, s - margin_ns), min(hi, e + margin_ns)
    keep = lambda x: x[0] < b and x[0] + x[1] > a
    last = {x[4]: x for x in ops if x[0] + x[1] <= a
            and 'custom_call_target="tpu_custom_call"' in x[3]}
    return {"about": "device 0's ops and the program's repro.* spans "
                     f"within {margin_ns / 1e6:.0f} ms of the longest idle "
                     "gap of a traced window (bench.window is that span), "
                     "and the last Pallas kernel of each scope before it",
            "devices": {dev: sorted(list(last.values())
                                    + [x for x in ops if keep(x)])},
            "host": [x for x in rec["host"]
                     if keep(x) and x[2].startswith("repro.")]
            + [[a, b - a, devtrace.WINDOW]],
            "n_scoped": rec.get("n_scoped")}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1)
    ap.add_argument("--slice", default=None)
    ap.add_argument("--keep", default=None,
                    help="write the profile here, gzipped")
    args = ap.parse_args(argv)

    from bench import devscope, devtrace, harness, spec

    runs, windows, seen, scoped = [], [], [], {}

    class Run(harness.Run):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            runs.append(self)

        def serve(self, on_tick):
            def tick(info):
                seen.append(info)
                on_tick(info)
            super().serve(tick)

    class Window(harness.Window):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            windows.append(self)

    reduce = devtrace.from_xspace

    def from_xspace(path):
        t = time.perf_counter()
        if args.keep:
            import gzip
            import shutil

            with open(devscope.newest_xplane(path), "rb") as src, \
                    gzip.open(args.keep, "wb") as dst:
                shutil.copyfileobj(src, dst)
        try:
            scoped.update(devscope.from_xspace(path))
        except Exception as e:          # keep the run's result line
            scoped["error"] = repr(e)
        harness.say(f"scoped trace read in {time.perf_counter() - t:.3f} s")
        return reduce(path)

    harness.Run, harness.Window = Run, Window
    devtrace.from_xspace = from_xspace
    cell = spec.resolve(args.workload)
    try:
        result = harness.run_cell(cell, args.seed, args.seconds,
                                  bool(args.trace), T_START)
    except harness.NoChip as e:
        print(f"bench/phases.py: {e}", file=sys.stderr)
        return 2
    tick_ms = [x[2] for x in windows[0].ticks]
    last = seen[-1]
    live, cap = host_rows(runs[0].svc)
    out = {"result": result, "tick_ms": tick_ms,
           "median_tick_ms": statistics.median(tick_ms),
           "last_tick": {"live_rows": last.live_rows,
                         "capacity_rows": last.capacity_rows,
                         "live_pairs": last.live_pairs,
                         "capacity_pairs": last.capacity_pairs},
           "host_rows": {"live_rows": live, "capacity_rows": cap}}
    if scoped:
        try:
            out["scoped"] = devscope.summarize(
                scoped, devscope.window_ticks(scoped))
        except Exception as e:          # keep the run's result line
            out["scoped"] = {"error": scoped.get("error", repr(e))}
        if args.slice and "devices" in scoped:
            os.makedirs(os.path.dirname(os.path.abspath(args.slice)),
                        exist_ok=True)
            with open(args.slice, "w") as f:
                json.dump(cut_slice(scoped), f)
    for k, v in out.items():
        if k != "result":
            harness.say(f"{k}: {json.dumps(v)}")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
