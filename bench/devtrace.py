"""From a ``jax.profiler`` trace to the numbers the metrics read.

``from_xspace`` reduces an ``.xplane.pb`` to a small JSON-able record:
per TPU device the ``XLA Ops`` events (start, duration, HLO opcode, name)
and, from the host planes, the benchmark's own annotations (``bench.*``:
the measured window, source polls, match delivery, the tick callback).
``summarize`` does the arithmetic on that record, so a committed small
record checks it (``bench/tests/test_devtrace.py``).

* busy: the union of a device's op intervals inside the window;
  idle share = 1 - busy / window.
* op time by opcode, clipped to the window; ``custom-call`` is where the
  Pallas kernels run.
* idle gaps: the holes in that union, each labelled by the annotation
  that covers most of it, or ``service (not annotated)``.

On a TPU an ``XLA Ops`` event is named by its HLO text (``%fusion.3 =
s32[...] fusion(...)``), which gives its opcode; host and device events
share one clock.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
WINDOW = "bench.window"
UNLABELLED = "service (not annotated)"
# "%name = type opcode(operands)...": the HLO text an op event is named
# by on a TPU; a tuple type is parenthesised and holds spaces
HLO_TEXT = re.compile(r"^%?[^\s=]+\s*=\s*(?:\(.*?\)|\S+)\s+([a-z][a-z0-9_-]*)\(")


def opcode_of(name: str) -> str:
    """The HLO opcode of an op event, from the HLO text it is named by;
    else the name without its ``.N`` suffix."""
    m = HLO_TEXT.match(name)
    return m.group(1) if m else re.sub(r"\.\d+$", "", name)


def from_xspace(path: str) -> dict:
    """Reduce one ``.xplane.pb`` (or the newest under a directory)."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                                 recursive=True), key=os.path.getmtime)
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = found[-1]
    pd = ProfileData.from_file(path)
    devices: dict[str, list] = {}
    host: list = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == OPS_LINE:
                ops = devices.setdefault(m.group(1), [])
                for ev in line.events:
                    ops.append([int(ev.start_ns), int(ev.duration_ns),
                                opcode_of(ev.name), ev.name])
            elif not m:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        host.append([int(ev.start_ns), int(ev.duration_ns),
                                     ev.name])
    return {"devices": devices, "host": host}


def union(intervals) -> list[tuple[int, int]]:
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(s: int, e: int, lo: int, hi: int) -> tuple[int, int] | None:
    s, e = max(s, lo), min(e, hi)
    return (s, e) if e > s else None


def window_of(rec: dict) -> tuple[int, int]:
    """The measured window: the ``bench.window`` annotation."""
    spans = [(s, s + d) for s, d, n in rec["host"] if n == WINDOW]
    if len(spans) != 1:
        raise ValueError(f"expected one {WINDOW} annotation, found "
                         f"{len(spans)}")
    return spans[0]


def summarize(rec: dict, top: int = 10) -> dict:
    """Per-device busy and op time in the window, the mean over devices,
    and the breakdown lists of device 0: the ops that took most time (by
    opcode and HLO instruction name) and the longest idle gaps."""
    lo, hi = window_of(rec)
    per_dev = {}
    for dev, ops in sorted(rec["devices"].items(), key=lambda kv: int(kv[0])):
        by_opcode: dict[str, int] = defaultdict(int)
        by_op: dict[str, int] = defaultdict(int)
        spans = []
        for s, d, opcode, name in ops:
            c = clip(s, s + d, lo, hi)
            if c is None:
                continue
            spans.append(c)
            by_opcode[opcode] += c[1] - c[0]
            by_op[f"{opcode}:{name.split(' = ', 1)[0]}"] += c[1] - c[0]
        busy = union(spans)
        per_dev[dev] = {
            "busy_ns": sum(e - s for s, e in busy),
            "by_opcode": dict(by_opcode),
            "by_op": dict(by_op),
            "busy": busy,
        }
    if not per_dev:
        raise ValueError("the trace holds no TPU device ops")
    n = len(per_dev)
    window_ns = hi - lo
    busy_ns = sum(d["busy_ns"] for d in per_dev.values()) / n
    opcodes = {k for d in per_dev.values() for k in d["by_opcode"]}
    mean_by_opcode = {k: sum(d["by_opcode"].get(k, 0)
                             for d in per_dev.values()) / n for k in opcodes}
    first = per_dev[min(per_dev, key=int)]
    ops_top = sorted(first["by_op"].items(), key=lambda kv: -kv[1])[:top]
    holes = sorted(gaps(first["busy"], lo, hi), key=lambda g: g[0] - g[1])
    return {
        "n_devices": n,
        "window_ns": window_ns,
        "busy_ns": busy_ns,
        "by_opcode_ns": mean_by_opcode,
        "device_ops": [[k, v / 1e9] for k, v in ops_top],
        "idle_gaps": [[label, (e - s) / 1e9] for s, e, label in
                      label_gaps(holes[:top], rec["host"])],
    }


def gaps(busy: list[tuple[int, int]], lo: int, hi: int) -> list[tuple]:
    """The holes in a sorted, disjoint ``busy`` inside ``[lo, hi]``."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def label_gaps(holes, host) -> list[tuple[int, int, str]]:
    """Each gap labelled by the annotation that covers most of it, with
    the share it covers (the program's own code runs around the
    benchmark's callbacks), or ``UNLABELLED`` where none falls in it."""
    notes = sorted((s, s + d, n.removeprefix("bench."))
                   for s, d, n in host if n != WINDOW)
    starts = [a for a, _, _ in notes]
    longest = max((b - a for a, b, _ in notes), default=0)
    out = []
    for s, e in holes:
        cover: dict[str, int] = defaultdict(int)
        for a, b, name in notes[bisect.bisect_left(starts, s - longest):
                                bisect.bisect_right(starts, e)]:
            c = clip(a, b, s, e)
            if c is not None:
                cover[name] += c[1] - c[0]
        best = max(cover.items(), key=lambda kv: kv[1], default=None)
        label = UNLABELLED if best is None else \
            f"{best[0]} ({best[1] / (e - s):.0%} of the gap)"
        out.append((s, e, label))
    return out
