"""The trace reduction on synthetic event lists."""

import pytest

import reduce_trace as rt

RULES = {
    "step": "^jit_step",
    "ops": [
        {"layer": "distance", "pattern": "distance_kernel"},
        {"layer": "sort_rank", "pattern": "^sort"},
        {"layer": "fill", "pattern": "^fill"},
    ],
}


def ev(name, start, dur):
    return {"name": name, "start_ns": start, "dur_ns": dur}


def test_union_merges_overlaps_and_gaps():
    assert rt.union_seconds([(0, 10), (5, 20), (30, 40)]) == pytest.approx(
        30e-9)
    assert rt.union_seconds([]) == 0.0


def test_gaps_inside_window():
    assert rt.gaps([(10, 20), (15, 30), (50, 60)], 0, 100) == [
        (0, 10), (30, 50), (60, 100)]


def test_classify_top_level_ops_first_rule_wins_and_other():
    ops = [ev("distance_kernel.1", 0, 100), ev("sort.3", 100, 300),
           ev("fill_loop", 400, 500), ev("fill_body", 450, 100),
           ev("copy.2", 900, 50)]
    got = rt.classify(ops, RULES)
    assert got == pytest.approx({"distance": 100e-9, "sort_rank": 300e-9,
                                 "fill": 500e-9, "other": 50e-9})


def _devices():
    ops = [ev("distance_kernel", 1000, 100),
           ev("sort.1", 1100, 400), ev("fill.1", 1500, 2000),
           ev("copy", 4000, 500), ev("sort.2", 9000, 100)]
    mods = [ev("jit_step(123)", 1000, 3500), ev("jit_copy", 4000, 500)]
    return [{"plane": "/device:TPU:1", "ops": ops, "modules": mods},
            {"plane": "/device:TPU:0", "ops": ops, "modules": mods}]


def test_reduce_events_idle_share_layers_and_gap_names():
    spans = [("window", 0, 6000), ("update", 0, 900),
             ("block_until_ready", 3600, 6000)]
    red = rt.reduce_events(_devices(), spans, RULES, chips=2)
    assert red["window_s"] == pytest.approx(6e-6)
    # busy: 1000..3500 and 4000..4500 on each chip; sort.2 is past the window
    assert red["busy_s"] == pytest.approx(3e-6)
    assert red["layers"]["step"] == pytest.approx(3.5e-6)
    assert red["layers"]["other"] == pytest.approx(0.5e-6)
    assert red["layers"]["fill"] == pytest.approx(2e-6)
    got = red["breakdown"]["idle_gaps"]
    assert [k for k, _ in got] == ["block_until_ready", "update",
                                   "block_until_ready"]
    assert [v for _, v in got] == pytest.approx([1.5e-6, 1e-6, 0.5e-6])
    assert red["breakdown"]["device_ops"][0][0] == "fill"
    idle = 100 * (1 - red["busy_s"] / red["window_s"])
    assert idle == pytest.approx(50.0)


def test_reduce_events_needs_a_window():
    with pytest.raises(ValueError):
        rt.reduce_events(_devices(), [("update", 0, 10)], RULES, chips=1)


def test_rules_file_classifies_every_layer_named_by_a_metric():
    from pathlib import Path

    rules = rt.load_rules(Path(rt.__file__).parent)
    layers = {r["layer"] for r in rules["ops"]}
    assert {"fill", "distance", "sort_rank"} <= layers
    # every "<layer>." metric of BENCHMARK.json reads a layer classified here
    import json

    spec = json.loads((Path(rt.__file__).parents[1] / "BENCHMARK.json")
                      .read_text())
    named = {m["name"].split(".")[0] for m in spec["per_layer"]}
    assert named - {"session", "device", "step"} <= layers
