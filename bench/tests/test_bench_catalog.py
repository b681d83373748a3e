"""BENCHMARK.json and the files it names: every part of every cell
resolves by name, keeps to the allowed names and units, and a cell made of
new files loads with no edit to any file already there."""

import json
import re
import shutil
from pathlib import Path

import pytest

import catalog

BENCH = Path(catalog.__file__).parent
ROOT = BENCH.parent
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in SPEC["workloads"]]
TEXT = re.compile(r"^[^\t\n]{1,200}$")


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["bench"]
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert 1 <= SPEC["run_seconds"] <= 51
    assert len(json.dumps(SPEC)) < 64 * 1024


def test_names_units_and_text():
    names = []
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in SPEC[key]:
            assert catalog.NAME.match(e["name"]), e["name"]
            names.append((key if key in ("configs", "workloads")
                          else "metric", e["name"]))
    assert len(names) == len(set(names))
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert catalog.UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    for e in SPEC["configs"] + SPEC["workloads"]:
        assert TEXT.match(e["why"])
    for m in SPEC["per_layer"]:
        assert TEXT.match(m["layer"])
    for c in SPEC["configs"]:
        assert TEXT.match(c["source"])
        assert all(catalog.NAME.match(k) for k in c["reduced"])


def test_bounds():
    bounds = {m["name"]: m["bound"] for m in SPEC["end_to_end"]}
    assert bounds["setup_s"] == 0.25
    assert all(0.01 <= b <= 0.25 for b in bounds.values())
    assert all(m["source"] in ("host_clock", "device_trace")
               for m in SPEC["end_to_end"])


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_resolves(cell):
    cat = catalog.Catalog(ROOT)
    c = cat.cell(cell)
    assert c.chips in (1, 4)
    assert c.traffic["batch"] >= 1
    e2e = {m["name"] for m in c.end_to_end}
    assert {"points_per_s", "peak_hbm_gib", "setup_s"} <= e2e
    assert c.per_layer
    cat.method(c.config["method"]).Check
    for m in c.per_layer:
        mod = cat.metric(m["name"])
        assert (mod.LAYER, mod.UNIT, mod.BETTER, mod.SOURCE, mod.MOVES) == (
            m["layer"], m["unit"], m["better"], m["source"], m["moves"])
        assert callable(mod.read)


def test_configs_state_their_cuts():
    for entry in SPEC["configs"]:
        cfg = json.loads((ROOT / entry["file"]).read_text())
        assert cfg["name"] == entry["name"]
        assert cfg["source"] == entry["source"]
        assert sorted(cfg["reduced"]) == sorted(entry["reduced"])
        assert all(v > 0 for v in cfg["check"]["limits"].values())
    four = [w for w in SPEC["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(SPEC["workloads"]) // 2)


def test_every_per_layer_metric_has_its_file():
    files = {p.stem for p in (BENCH / "metrics").glob("*.py")}
    assert {m["name"] for m in SPEC["per_layer"]} <= files


def test_a_new_cell_is_files_only(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    before = {p: p.read_bytes() for p in (tmp_path / "bench").rglob("*")
              if p.is_file()}
    spec = json.loads(json.dumps(SPEC))
    (tmp_path / "bench" / "configs" / "knn-small.json").write_text(
        json.dumps(dict(json.loads((BENCH / "configs" / "knn-cifar10.json")
                                   .read_text()), name="knn-small",
                        n=10000)))
    (tmp_path / "bench" / "traffic" / "trickle.json").write_text(
        json.dumps({"name": "trickle", "loop": "closed", "clients": 1,
                    "batch": 32, "order": "cycle"}))
    (tmp_path / "bench" / "metrics" / "step.count.py").write_text(
        "LAYER = 'device'\nUNIT = 'steps'\nBETTER = 'higher'\n"
        "SOURCE = 'program_counter'\nMOVES = 'points_per_s'\n"
        "def read(red):\n    return red['steps']\n")
    spec["configs"].append({"name": "knn-small", "source": "x",
                            "file": "bench/configs/knn-small.json",
                            "reduced": ["n"], "why": "x"})
    spec["workloads"].append({"name": "knn-small.trickle",
                              "config": "knn-small", "traffic": "trickle",
                              "chips": 1, "why": "x"})
    spec["per_layer"].append({"name": "step.count", "unit": "steps",
                              "better": "higher",
                              "source": "program_counter", "layer": "device",
                              "moves": "points_per_s",
                              "workloads": ["knn-small.trickle"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    cat = catalog.Catalog(tmp_path)
    c = cat.cell("knn-small.trickle")
    assert c.config["n"] == 10000 and c.traffic["batch"] == 32
    assert [m["name"] for m in c.per_layer] == ["step.count"]
    assert cat.metric("step.count").read({"steps": 3}) == 3
    for p, data in before.items():
        assert p.read_bytes() == data


@pytest.mark.parametrize("bad", ["a b", "x,y", "a/b", "", "é"])
def test_bad_names_are_refused(bad):
    with pytest.raises(ValueError):
        catalog.check_name(bad, "workload")
