"""A later PR adds a configuration, a traffic mix and a metric as new
files plus new entries: the loader finds each by name, and no file that
was there is edited."""

import hashlib
import json
import shutil
from pathlib import Path

from benchmark.spec import Spec

ROOT = Path(__file__).resolve().parents[2]


def digests(root: Path) -> dict:
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(root.rglob("*")) if p.is_file()
            and "__pycache__" not in p.parts}


def test_new_files_are_found_by_name(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "benchmark", tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = digests(tmp_path / "benchmark")

    # the new configuration, mix and metric: new files only
    cfg = json.loads((ROOT / "benchmark/configs/pod2pod-default.json")
                     .read_text())
    cfg["pods"] = 32
    (tmp_path / "benchmark/configs/pod32.json").write_text(json.dumps(cfg))
    mix = json.loads((ROOT / "benchmark/traffic/64B.sat.json").read_text())
    mix["frame_bytes"] = 1514
    (tmp_path / "benchmark/traffic/1514B.sat.json").write_text(
        json.dumps(mix))
    (tmp_path / "benchmark/metrics/frames_per_s.py").write_text(
        "def read(run):\n    return 42.0\n")

    # ... and new entries in BENCHMARK.json
    doc = json.loads((tmp_path / "BENCHMARK.json").read_text())
    doc["configs"].append({"name": "pod32", "source": "https://example.org",
                           "file": "benchmark/configs/pod32.json",
                           "reduced": ["pods"], "why": "test"})
    doc["workloads"].append({"name": "pod32.1514B.sat", "config": "pod32",
                             "traffic": "1514B.sat", "chips": 1,
                             "why": "test"})
    doc["per_layer"].append({"name": "frames_per_s", "unit": "1/s",
                             "better": "higher", "source": "host_clock",
                             "layer": "pump", "moves": "delivered_mpps",
                             "workloads": ["pod32.1514B.sat"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(doc))

    spec = Spec(tmp_path)
    cell = spec.cell("pod32.1514B.sat")
    assert spec.config(cell["config"])["pods"] == 32
    assert spec.traffic(cell["traffic"])["frame_bytes"] == 1514
    assert spec.system(spec.config("pod32")).build.__name__ == "build"
    assert hasattr(spec.reference(spec.config("pod32")), "Reference")
    names = {m["name"]: r for m, r in spec.metrics(cell["name"], True)}
    assert names["frames_per_s"].read({}) == 42.0
    # a metric limited to other cells stays out of this one
    assert "device_idle_pct.paced" not in names

    after = digests(tmp_path / "benchmark")
    assert {k: v for k, v in after.items() if k in before} == before


def test_metrics_follow_their_workloads():
    spec = Spec(ROOT)
    sat = {m["name"] for m, _ in spec.metrics("node5k.newflow.sat", False)}
    paced = {m["name"] for m, _ in spec.metrics("node5k.newflow.paced",
                                                 False)}
    assert sat == {"delivered_mpps", "setup_s"}
    assert paced == {"lat_p50_us", "setup_s"}
    for cell in spec.doc["workloads"]:
        layers = spec.metrics(cell["name"], True)
        assert layers, cell["name"]
        e2e = {m["name"] for m, _ in spec.metrics(cell["name"], False)}
        for m, _ in layers:
            assert m["moves"] in e2e, (cell["name"], m["name"])
