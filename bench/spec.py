"""Finds a cell's parts by name: the workload in ``BENCHMARK.json``, its
configuration ``bench/configs/<config>.json``, its traffic mix
``bench/mixes/<traffic>.json`` and each per-layer metric's reader
``bench/metrics/<metric>.py``.  Adding a cell takes new files and new
``BENCHMARK.json`` entries, and no edit here."""

from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    end_to_end: list[dict]   # the manifest's metrics this cell reports
    per_layer: list[dict]


def _reports(metric: dict, cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def resolve(name: str, root: str = ROOT) -> Cell:
    manifest = load_json(os.path.join(root, "BENCHMARK.json"))
    found = [w for w in manifest["workloads"] if w["name"] == name]
    if not found:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = found[0]
    conf = next(c for c in manifest["configs"] if c["name"] == w["config"])
    config = load_json(os.path.join(root, conf["file"]))
    mix = load_json(os.path.join(root, "bench", "mixes",
                                 f"{w['traffic']}.json"))
    e2e = [m for m in manifest["end_to_end"] if _reports(m, name)]
    layer = [m for m in manifest["per_layer"] if _reports(m, name)]
    return Cell(name, int(w["chips"]), config, mix, e2e, layer)


def metric_reader(name: str, root: str = ROOT):
    """The ``read(ctx)`` function of ``bench/metrics/<name>.py``."""
    path = os.path.join(root, "bench", "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
