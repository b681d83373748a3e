"""Finds every part of a cell by its name in `BENCHMARK.json`.

A cell (`workloads` entry) names a configuration, a traffic mix and the
chips it needs. The parts live in files of their own under the benchmark
directory, so that a later cell adds files and edits none:

    BENCHMARK.json                        cells, metrics, bounds
    <file of the configs entry>           one deployment (sizes, method)
    bench/traffic/<traffic>.json          one traffic mix
    bench/metrics/<metric>.py             one per-layer metric and its reader
    bench/methods/<method>.py             one method's reference check
"""

from __future__ import annotations

import importlib.util
import json
import re
from dataclasses import dataclass
from pathlib import Path

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
BENCH_DIR = "bench"


def check_name(name: str, what: str) -> str:
    if not isinstance(name, str) or not NAME.match(name):
        raise ValueError(f"{what} {name!r} is not a valid name")
    return name


def _load_module(path: Path, tag: str):
    if not path.is_file():
        raise FileNotFoundError(f"no {tag} file {path}")
    mod_name = "bench_" + tag + "_" + re.sub(r"\W", "_", path.stem)
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    name: str
    config: dict
    traffic: dict
    chips: int
    end_to_end: list
    per_layer: list


class Catalog:
    """`BENCHMARK.json` of the checkout at `root`, and lookups by name."""

    def __init__(self, root):
        self.root = Path(root)
        self.spec = json.loads((self.root / "BENCHMARK.json").read_text())
        self.bench = self.root / BENCH_DIR

    def _entry(self, key: str, name: str) -> dict:
        for e in self.spec[key]:
            if e["name"] == name:
                return e
        raise KeyError(f"no {key} entry named {name!r} in BENCHMARK.json")

    def config(self, name: str) -> dict:
        entry = self._entry("configs", check_name(name, "config"))
        cfg = json.loads((self.root / entry["file"]).read_text())
        if cfg.get("name") != name:
            raise ValueError(f"{entry['file']} names {cfg.get('name')!r}, "
                             f"not {name!r}")
        return cfg

    def traffic(self, name: str) -> dict:
        path = self.bench / "traffic" / f"{check_name(name, 'traffic')}.json"
        mix = json.loads(path.read_text())
        if mix.get("name") != name:
            raise ValueError(f"{path} names {mix.get('name')!r}")
        return mix

    def metric(self, name: str):
        """The reader module of a per-layer metric."""
        return _load_module(self.bench / "metrics"
                            / f"{check_name(name, 'metric')}.py", "metric")

    def method(self, name: str):
        """The reference check of a valuation method."""
        return _load_module(self.bench / "methods"
                            / f"{check_name(name, 'method')}.py", "method")

    def _applies(self, metric: dict, cell: str) -> bool:
        cells = metric.get("workloads")
        if cells is not None:
            return cell in cells
        return True

    def cell(self, name: str) -> Cell:
        w = self._entry("workloads", check_name(name, "workload"))
        e2e = [m for m in self.spec["end_to_end"] if self._applies(m, name)]
        moved = {m["name"] for m in e2e}
        layer = [m for m in self.spec["per_layer"]
                 if self._applies(m, name) and m["moves"] in moved]
        return Cell(name, self.config(w["config"]), self.traffic(w["traffic"]),
                    int(w["chips"]), e2e, layer)
