"""A cell is found from files alone: a configuration, a mix and a metric
reader added under a fresh root resolve without any code change."""

import json
import os

from bench import spec


def test_resolve_from_files_alone(tmp_path):
    root = tmp_path
    for d in ("configs", "mixes", "metrics"):
        os.makedirs(root / "bench" / d)
    (root / "bench" / "configs" / "cfg-x.json").write_text(
        json.dumps({"name": "cfg-x", "window": 10}))
    (root / "bench" / "mixes" / "mix-y.json").write_text(
        json.dumps({"schedule": [[0.5, 10]], "prefill_windows": 1}))
    (root / "bench" / "metrics" / "layer.thing_ms.py").write_text(
        "def read(ctx):\n    return None if ctx is None else 2 * ctx\n")
    (root / "BENCHMARK.json").write_text(json.dumps({
        "configs": [{"name": "cfg-x", "file": "bench/configs/cfg-x.json"}],
        "workloads": [{"name": "cfg-x.mix-y", "config": "cfg-x",
                       "traffic": "mix-y", "chips": 1},
                      {"name": "cfg-x.other", "config": "cfg-x",
                       "traffic": "mix-y", "chips": 4}],
        "end_to_end": [{"name": "setup_s", "unit": "s"},
                       {"name": "rate", "unit": "1/s",
                        "workloads": ["cfg-x.other"]}],
        "per_layer": [{"name": "layer.thing_ms", "unit": "ms",
                       "workloads": ["cfg-x.mix-y"]}],
    }))
    cell = spec.resolve("cfg-x.mix-y", root=str(root))
    assert cell.chips == 1 and cell.config["window"] == 10
    assert cell.mix["schedule"] == [[0.5, 10]]
    assert [m["name"] for m in cell.end_to_end] == ["setup_s"]
    assert [m["name"] for m in cell.per_layer] == ["layer.thing_ms"]
    read = spec.metric_reader("layer.thing_ms", root=str(root))
    assert read(3) == 6 and read(None) is None
    other = spec.resolve("cfg-x.other", root=str(root))
    assert [m["name"] for m in other.end_to_end] == ["setup_s", "rate"]
    assert other.per_layer == []


def test_every_manifest_metric_has_a_reader():
    manifest = spec.load_json(os.path.join(spec.ROOT, "BENCHMARK.json"))
    for m in manifest["per_layer"]:
        assert callable(spec.metric_reader(m["name"]))
    for w in manifest["workloads"]:
        cell = spec.resolve(w["name"])
        assert cell.per_layer and len(cell.end_to_end) >= 2
