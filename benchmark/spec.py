"""Finds everything a cell needs by the names in ``BENCHMARK.json``:

- a configuration: the file its entry names (``benchmark/configs/``),
  whose ``system`` names the module under ``benchmark/systems/`` that builds it, and
  whose ``reference`` names its plain reference beside it;
- a traffic mix: ``benchmark/traffic/<traffic>.json``;
- a metric: its reader ``benchmark/metrics/<name>.py``, a module with
  ``read(run) -> float | None``.

A later cell, configuration, mix or metric is new files plus new
entries; nothing here names one.
"""

from __future__ import annotations

import importlib.util
import json
import re
from pathlib import Path
from typing import Dict, List, Tuple

ROOT = Path(__file__).resolve().parents[1]
BENCH = "benchmark"


def load_module(path: Path):
    name = "bench_" + re.sub(r"[^A-Za-z0-9_]", "_",
                             f"{path.parent.name}_{path.stem}")
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise ImportError(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Spec:
    def __init__(self, root: Path = ROOT):
        self.root = Path(root)
        self.doc = json.loads((self.root / "BENCHMARK.json").read_text())
        self.bench = self.root / BENCH

    def cell(self, name: str) -> Dict:
        for w in self.doc["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")

    def config(self, name: str) -> Dict:
        for c in self.doc["configs"]:
            if c["name"] == name:
                cfg = json.loads((self.root / c["file"]).read_text())
                cfg.setdefault("name", name)
                return cfg
        raise KeyError(f"no config {name!r} in BENCHMARK.json")

    def traffic(self, name: str) -> Dict:
        path = self.bench / "traffic" / f"{name}.json"
        if not path.exists():
            raise KeyError(f"no traffic mix {path}")
        return json.loads(path.read_text())

    def system(self, cfg: Dict):
        return load_module(self.bench / "systems" / f"{cfg['system']}.py")

    def reference(self, cfg: Dict):
        return load_module(self.bench / "configs" / f"{cfg['reference']}.py")

    def metrics(self, cell: str, trace: bool) -> List[Tuple[Dict, object]]:
        """(entry, reader) of every metric the cell reports in this kind
        of run: end-to-end with ``trace`` off, per-layer with it on."""
        out = []
        for m in self.doc["per_layer" if trace else "end_to_end"]:
            if "workloads" in m and cell not in m["workloads"]:
                continue
            out.append((m, load_module(self.bench / "metrics"
                                       / f"{m['name']}.py")))
        return out


def peaks(device_kind: str, root: Path = ROOT) -> Dict:
    """Published peaks of one chip; a device missing from the table is
    an error, never a default."""
    table = json.loads((Path(root) / BENCH / "peaks.json").read_text())
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} has no entry in "
                       f"{BENCH}/peaks.json")
    return table[device_kind]
