"""A configuration, a traffic mix and a per-layer metric added as new
files, with entries in BENCHMARK.json, are found by name: no existing file
of the benchmark changes."""
from __future__ import annotations

import hashlib
import itertools
import json
from types import SimpleNamespace

from bench import harness, traffic


def _digest(root):
    return {p: hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted((root / "bench").rglob("*")) if p.is_file()}


def test_new_files_are_found_without_editing_any(checkout, tmp_path):
    import shutil

    root = tmp_path / "co"
    shutil.copytree(checkout, root)
    before = _digest(root)
    cfg = json.loads((root / "bench/tests/data/vgg16-64.json").read_text())
    cfg["name"] = "vgg16-48"
    cfg["image"] = 48
    (root / "bench/configs/vgg16-48.json").write_text(json.dumps(cfg))
    (root / "bench/traffic/b3.burst.json").write_text(json.dumps(
        {"loop": "closed", "clients": 1, "batch": 3, "distinct_inputs": 2,
         "fault": {"kind": "straggler", "slowdown": 4.0}}))
    (root / "bench/metrics/requests_seen.py").write_text(
        "def read(rec):\n    return float(len(rec.requests)) or None\n")
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "vgg16-48", "source": "x",
                            "file": "bench/configs/vgg16-48.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "vgg16-48.b3.burst",
                              "config": "vgg16-48", "traffic": "b3.burst",
                              "chips": 1, "why": "test"})
    spec["per_layer"].append({"name": "requests_seen", "unit": "requests",
                              "better": "higher", "source": "host_clock",
                              "layer": "harness", "moves": "images_per_s",
                              "workloads": ["vgg16-48.b3.burst"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))

    cell = harness.find_cell(root, "vgg16-48.b3.burst")
    assert cell.cfg["image"] == 48 and cell.mix["batch"] == 3
    assert "requests_seen" in [m["name"] for m in cell.per_layer]
    other = harness.find_cell(root, "vgg16-64.b1.straggler")
    assert "requests_seen" not in [m["name"] for m in other.per_layer]
    read = harness.metric_reader(root, "requests_seen")
    assert read(SimpleNamespace(requests=[1, 2, 3])) == 3.0
    stream = traffic.requests(cell.mix, 11, 4)
    assert {r.input for r in itertools.islice(stream, 8)} == {0, 1}
    layers = cell.reference.conv_layers(cell.cfg)
    assert layers[-1]["size"] == 3
    # nothing that was there before changed
    after = _digest(root)
    assert all(after[p] == h for p, h in before.items())
