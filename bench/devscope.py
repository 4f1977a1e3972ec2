"""Device time by engine phase, and idle gaps named by the program's own
spans, from a ``jax.profiler`` trace of the slot tick.

The program names its work: every phase of the tick body runs under a
``jax.named_scope`` (``engine.label_scan``, ``engine.level_append``,
``engine.level_join``, ``engine.level_recon``, ``engine.l0_compact``,
``engine.l0_join``, ``engine.l0_append``, ``engine.emit``,
``engine.expire``), which lands in each op's HLO ``op_name``; the join
kernels are the ``custom-call`` ops ``compat_join_pairs`` and
``compat_mask``; and every host span of ``repro.obs`` is a ``repro.*``
profiler annotation while it runs.

``from_xspace`` reduces an ``.xplane.pb`` to a JSON-able record like
``bench.devtrace.from_xspace``'s, with a fifth field on each op, its
innermost ``engine.*`` scope (None where none is found), and the
``repro.*`` annotations beside the ``bench.*`` ones.  A TPU v5e's ``XLA
Ops`` events carry no ``op_name`` stat, so the scope is read through
the module's HLO, which the profile holds.  ``summarize`` splits the
window's device time by scope into kernel (``custom-call``) and other
time, and measures the share of device-idle time that no program span
below ``serve.round`` covers.  ``bench/phases.py`` runs a cell and
prints both.
"""

from __future__ import annotations

import bisect
import re
from collections import Counter, defaultdict

from bench import devtrace

SCOPE = re.compile(r"engine\.[a-z0-9_]+")
CALLS = re.compile(r"calls=%?([\w.-]+)")
ROUND = "repro.serve.round"
KERNEL = "custom-call"
MODULES_LINE = "XLA Modules"
METADATA_PLANE = b"/host:metadata"

# the per-tick phase metrics: (scopes, which device time under them)
PHASES = {
    "engine.append_ms_per_tick": (("engine.level_append",
                                   "engine.l0_append"), "other"),
    "engine.recon_ms_per_tick": (("engine.level_recon",), "all"),
    "engine.compact_ms_per_tick": (("engine.l0_compact", "engine.emit"),
                                   "all"),
    "engine.join_prep_ms_per_tick": (("engine.level_join",
                                      "engine.l0_join"), "other"),
    "engine.expire_ms_per_tick": (("engine.expire",), "all"),
    "kernel.level_join_ms_per_tick": (("engine.level_join",), KERNEL),
    "kernel.l0_join_ms_per_tick": (("engine.l0_join",), KERNEL),
}


def scope_of(text: str) -> str | None:
    """The innermost ``engine.*`` scope an op_name names."""
    found = SCOPE.findall(text)
    return found[-1] if found else None


def from_xspace(path: str) -> dict:
    """Reduce one ``.xplane.pb`` (or the newest under a directory).

    Each op is found in its module's HLO (``module_scopes``): its module
    is the ``XLA Modules`` event around it, its instruction the name in
    the event's HLO text; ``op_scope`` gives the scope."""
    from jax.profiler import ProfileData

    path = newest_xplane(path)
    with open(path, "rb") as f:
        hlo = module_scopes(f.read())
    devices: dict[str, list] = {}
    host: list = []
    n_scoped = 0
    for plane in ProfileData.from_file(path).planes:
        m = devtrace.DEVICE_PLANE.match(plane.name)
        if m:
            lines = {line.name: line for line in plane.lines}
            mods = sorted((int(ev.start_ns), ev.name) for ev in getattr(
                lines.get(MODULES_LINE), "events", ()))
            starts = [a for a, _ in mods]
            ops = devices.setdefault(m.group(1), [])
            for ev in getattr(lines.get(devtrace.OPS_LINE), "events", ()):
                start = int(ev.start_ns)
                i = bisect.bisect_right(starts, start) - 1
                table = hlo.get(mods[i][1]) if i >= 0 else None
                scope = None if table is None else op_scope(table, ev.name)
                n_scoped += scope is not None
                ops.append([start, int(ev.duration_ns),
                            devtrace.opcode_of(ev.name), ev.name, scope])
        else:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(("bench.", "repro.")):
                        host.append([int(ev.start_ns), int(ev.duration_ns),
                                     ev.name])
    return {"devices": devices, "host": host, "n_scoped": n_scoped}


# --------------------------------------------------------------------- #
# The modules' HLO, from the profile's raw bytes: a protobuf walk of
# XSpace -> XPlane "/host:metadata" -> XEventMetadata (the module, named
# "<module>(<program id>)" as the device's "XLA Modules" events are) ->
# XStat "Hlo Proto" -> HloProto.hlo_module -> computations ->
# instructions (name, metadata.op_name).  ProfileData does not expose
# event metadata, so the fields are read by number.
# --------------------------------------------------------------------- #


def _varint(b, i: int) -> tuple[int, int]:
    x = shift = 0
    while True:
        c = b[i]
        i += 1
        x |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return x, i


def _fields(b):
    """(field number, value) of a protobuf message's fields: an int for
    a varint, a memoryview for length-delimited and fixed-width ones."""
    b = memoryview(b)
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        kind = key & 7
        if kind == 0:
            v, i = _varint(b, i)
        elif kind == 2:
            size, i = _varint(b, i)
            v, i = b[i:i + size], i + size
        elif kind in (1, 5):
            size = 8 if kind == 1 else 4
            v, i = b[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {kind}")
        yield key >> 3, v


def _sub(b, field: int) -> list:
    return [v for f, v in _fields(b) if f == field]


def module_scopes(xspace: bytes) -> dict[str, dict]:
    """Per module of the profile: ``{"ops": {instruction: scope},
    "comps": {computation: most common scope of its instructions}}``."""
    out = {}
    for plane in _sub(xspace, 1):                  # XSpace.planes
        names = _sub(plane, 2)                     # XPlane.name
        if not names or bytes(names[0]) != METADATA_PLANE:
            continue
        stat_names = {}
        for entry in _sub(plane, 5):               # XPlane.stat_metadata
            k, meta = _sub(entry, 1), _sub(entry, 2)
            if k and meta and _sub(meta[0], 2):
                stat_names[k[0]] = bytes(_sub(meta[0], 2)[0]).decode()
        for entry in _sub(plane, 4):               # XPlane.event_metadata
            meta = _sub(entry, 2)
            if not meta or not _sub(meta[0], 2):
                continue
            name = bytes(_sub(meta[0], 2)[0]).decode()
            for stat in _sub(meta[0], 5):           # XEventMetadata.stats
                ids, raw = _sub(stat, 1), _sub(stat, 6)
                if raw and stat_names.get(ids[0] if ids else None) \
                        == "Hlo Proto":
                    out[name] = _hlo_scopes(raw[0])
    return out


def _packed(v) -> list[int]:
    """A packed repeated varint field."""
    out, i = [], 0
    while i < len(v):
        x, i = _varint(v, i)
        out.append(x)
    return out


def _hlo_scopes(hlo_proto) -> dict:
    """Every instruction's scope: its own op_name's, else (a fusion or
    call) the most common one in the computation it calls, else (an op
    the compiler added, such as a layout copy) that of the nearest
    scoped user, else of the nearest scoped operand, in its
    computation."""
    comps = []
    for module in _sub(hlo_proto, 1):              # HloProto.hlo_module
        for comp in _sub(module, 3):               # .computations
            ins = []
            for i in _sub(comp, 2):                # .instructions
                meta = _sub(i, 7)                  # .metadata
                op_name = _sub(meta[0], 2) if meta else []   # .op_name
                ins.append({
                    "name": bytes(_sub(i, 1)[0]).decode(),
                    "id": (_sub(i, 35) or [None])[0],
                    "operands": [x for v in _sub(i, 36) for x in _packed(v)],
                    "calls": [x for v in _sub(i, 38) for x in _packed(v)],
                    "scope": scope_of(bytes(op_name[0]).decode())
                    if op_name else None})
            comps.append(((_sub(comp, 5) or [None])[0],
                          bytes(_sub(comp, 1)[0]).decode(), ins))
    by_id, by_name = {}, {}
    for cid, cname, ins in comps:
        seen = Counter(x["scope"] for x in ins if x["scope"])
        by_id[cid] = by_name[cname] = \
            seen.most_common(1)[0][0] if seen else None
    ops = {}
    for _, _, ins in comps:
        for x in ins:
            if x["scope"] is None:
                x["scope"] = next((by_id[c] for c in x["calls"]
                                   if by_id.get(c)), None)
        scope = {x["id"]: x["scope"] for x in ins}
        users: dict = defaultdict(list)
        for x in ins:
            for o in x["operands"]:
                users[o].append(x["id"])
        for _ in range(8):                 # chains of added copies
            changed = False
            for x in ins:
                if scope[x["id"]] is None:
                    near = [scope.get(u) for u in users[x["id"]]] \
                        + [scope.get(o) for o in x["operands"]]
                    found = next((sc for sc in near if sc), None)
                    if found:
                        scope[x["id"]] = found
                        changed = True
            if not changed:
                break
        for x in ins:
            ops[x["name"]] = scope[x["id"]]
    return {"ops": ops, "comps": by_name}


def op_scope(table: dict, hlo_text: str) -> str | None:
    """An op event's scope from its module's table: its instruction's
    own, else that of the computation it calls (a fusion's body)."""
    name = hlo_text.split(" = ", 1)[0].strip().lstrip("%")
    scope = table["ops"].get(name)
    if scope is None:
        m = CALLS.search(hlo_text)
        if m:
            scope = table["comps"].get(m.group(1))
    return scope


def newest_xplane(path: str) -> str:
    """``path``, or the newest ``.xplane.pb`` under it."""
    import glob
    import os

    if not os.path.isdir(path):
        return path
    found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return found[-1]


def by_scope_ns(rec: dict) -> dict[str | None, dict[str, float]]:
    """Device time in the window by scope, split into the kernels
    (``custom-call``) and all other ops: the mean over devices."""
    lo, hi = devtrace.window_of(rec)
    out: dict = defaultdict(lambda: {KERNEL: 0.0, "other": 0.0})
    n = len(rec["devices"])
    for ops in rec["devices"].values():
        for s, d, opcode, _name, *rest in ops:
            c = devtrace.clip(s, s + d, lo, hi)
            if c is None:
                continue
            kind = KERNEL if opcode == KERNEL else "other"
            out[rest[0] if rest else None][kind] += (c[1] - c[0]) / n
    return dict(out)


def phases_ms_per_tick(split: dict, n_ticks: int) -> dict[str, float]:
    """The ``PHASES`` metrics from a ``by_scope_ns`` split."""
    out = {}
    for name, (scopes, kind) in PHASES.items():
        ns = 0.0
        for sc in scopes:
            t = split.get(sc, {})
            ns += (t.get(KERNEL, 0.0) + t.get("other", 0.0)
                   if kind == "all" else t.get(kind, 0.0))
        out[name] = ns / 1e6 / n_ticks
    return out


def _program_spans(rec: dict) -> list[tuple[int, int, str]]:
    """The ``repro.*`` annotations below ``serve.round``, sorted."""
    return sorted((s, s + d, n) for s, d, n in rec["host"]
                  if n.startswith("repro.") and n != ROUND)


def idle_unattributed_share(rec: dict) -> float | None:
    """Share of the window's device-idle time (device 0) that no
    program span below ``serve.round`` covers."""
    lo, hi = devtrace.window_of(rec)
    dev = min(rec["devices"], key=int)
    busy = devtrace.union(c for s, d, *_ in rec["devices"][dev]
                          if (c := devtrace.clip(s, s + d, lo, hi)))
    holes = devtrace.gaps(busy, lo, hi)
    idle = sum(e - s for s, e in holes)
    if not idle:
        return None
    covered = devtrace.union(c for s, e, _ in _program_spans(rec)
                             if (c := devtrace.clip(s, e, lo, hi)))
    seen = 0
    for s, e in holes:
        for a, b in covered:
            c = devtrace.clip(a, b, s, e)
            if c is not None:
                seen += c[1] - c[0]
    return 1.0 - seen / idle


def label_gaps(holes, host) -> list[tuple[int, int, str]]:
    """Each gap labelled by the innermost program span that covers most
    of it: the shortest ``repro.*`` span over half of the gap, else the
    one that covers most; where no program span falls in it, by the
    benchmark's annotations as ``bench.devtrace.label_gaps`` does."""
    spans = sorted((s, s + d, n) for s, d, n in host
                   if n.startswith("repro."))
    starts = [a for a, _, _ in spans]
    longest = max((b - a for a, b, _ in spans), default=0)
    out = []
    for s, e in holes:
        best = None
        for a, b, name in spans[bisect.bisect_left(starts, s - longest):
                                bisect.bisect_right(starts, e)]:
            c = devtrace.clip(a, b, s, e)
            if c is None:
                continue
            share = (c[1] - c[0]) / (e - s)
            key = (share > 0.5, -(b - a) if share > 0.5 else share)
            if best is None or key > best[0]:
                best = (key, name, share)
        if best is None:
            out.extend(devtrace.label_gaps([(s, e)], [
                h for h in host if not h[2].startswith("repro.")]))
        else:
            out.append((s, e, f"{best[1].removeprefix('repro.')} "
                              f"({best[2]:.0%} of the gap)"))
    return out


def summarize(rec: dict, n_ticks: int, top: int = 10) -> dict:
    """The scope split, the phase metrics per tick, the unattributed
    idle share, and device 0's longest ops (with their scope) and
    longest idle gaps (named by program spans)."""
    lo, hi = devtrace.window_of(rec)
    split = by_scope_ns(rec)
    dev = min(rec["devices"], key=int)
    by_op: dict = defaultdict(int)
    spans = []
    for s, d, opcode, name, *rest in rec["devices"][dev]:
        c = devtrace.clip(s, s + d, lo, hi)
        if c is None:
            continue
        spans.append(c)
        op = f"{opcode}:{name.split(' = ', 1)[0].lstrip('%')}"
        by_op[(op, rest[0] if rest else None)] += c[1] - c[0]
    holes = sorted(devtrace.gaps(devtrace.union(spans), lo, hi),
                   key=lambda g: g[0] - g[1])
    kernel_ns = sum(t[KERNEL] for t in split.values())
    other_ns = sum(t["other"] for t in split.values())
    return {
        "n_ticks": n_ticks,
        "by_scope_ms_per_tick": {
            str(k): {kind: v / 1e6 / n_ticks for kind, v in t.items()}
            for k, t in sorted(split.items(), key=lambda kv: str(kv[0]))},
        "phases": phases_ms_per_tick(split, n_ticks),
        "kernel_ms_per_tick": kernel_ns / 1e6 / n_ticks,
        "table_ms_per_tick": other_ns / 1e6 / n_ticks,
        "idle_unattributed_share": idle_unattributed_share(rec),
        "device_ops": [[op, scope, ns / 1e9] for (op, scope), ns in
                       sorted(by_op.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[label, (e - s) / 1e9] for s, e, label in
                      label_gaps(holes[:top], rec["host"])],
        "n_scoped": rec.get("n_scoped"),
    }


def window_ticks(rec: dict) -> int:
    """The ticks inside the window: ``repro.tick`` spans within it."""
    lo, hi = devtrace.window_of(rec)
    return sum(1 for s, d, n in rec["host"]
               if n == "repro.tick" and lo <= s and s + d <= hi)
