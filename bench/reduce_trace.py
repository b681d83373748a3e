"""Reduction of a profiler trace of the window to per-layer numbers.

The JAX profiler writes an `.xplane.pb`; `jax.profiler.ProfileData` reads
it. Each chip is a plane `/device:TPU:<i>`; its line `XLA Ops` holds one
event per device operation and its line `XLA Modules` one per program
execution. The benchmark's own host spans (`run.Spans`) are annotations
on a host plane, on the same clock.

Which operation belongs to which layer is data, `layers.json`: patterns
matched against a top-level op's HLO text, which is the event's name on a
TPU; operations no pattern matches count as `other`. No number is read from the program
beyond its kernel and operation names.

    busy_s      union of the `XLA Ops` intervals in the window, averaged
                over the chips
    window_s    the window span's length
    layers      seconds per layer in the window, averaged over the chips
    breakdown   layers by time, and the first chip's longest idle gaps,
                each named after the host span that covered it
"""

from __future__ import annotations

import json
import re
from pathlib import Path

import roofline

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
GAP_SPANS = ("update", "check", "block_until_ready", "reference")


def load_rules(bench_dir) -> dict:
    return json.loads((Path(bench_dir) / "layers.json").read_text())


def union_seconds(intervals) -> float:
    """Length of the union of (start_ns, end_ns) intervals, in seconds."""
    total, end = 0.0, None
    for s, e in sorted(intervals):
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total / 1e9


def gaps(intervals, lo: float, hi: float):
    """The idle (start_ns, end_ns) gaps of `intervals` inside [lo, hi]."""
    out, cur = [], lo
    for s, e in sorted(intervals):
        if s > cur:
            out.append((cur, min(s, hi)))
        cur = max(cur, e)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi))
    return [(s, e) for s, e in out if e > s]


def classify(ops, rules: dict) -> dict:
    """Seconds per layer of `ops` ({name, start_ns, dur_ns}). Only
    top-level ops are matched: an op that starts inside an earlier one
    (the body of a `while`, say) is part of it and counted with it. The
    first rule whose pattern is found in the op's text wins, and an op no
    rule matches counts as `other`."""
    pats = [(r["layer"], re.compile(r["pattern"])) for r in rules["ops"]]
    out: dict[str, float] = {}
    end = None
    for ev in sorted(ops, key=lambda e: (e["start_ns"], -e["dur_ns"])):
        if end is not None and ev["start_ns"] < end:
            continue
        end = ev["start_ns"] + ev["dur_ns"]
        layer = next((name for name, p in pats if p.search(ev["name"])),
                     "other")
        out[layer] = out.get(layer, 0.0) + ev["dur_ns"] / 1e9
    return out


def module_seconds(modules, rules: dict) -> float:
    """Seconds of the executions of the step program (`rules["step"]`)."""
    p = re.compile(rules["step"])
    return sum(ev["dur_ns"] for ev in modules if p.search(ev["name"])) / 1e9


def name_gap(gap, spans) -> str:
    """The benchmark host span that covers the middle of an idle gap."""
    mid = (gap[0] + gap[1]) / 2
    best = None
    for name, s, e in spans:
        if s <= mid <= e and (best is None or e - s < best[2] - best[1]):
            best = (name, s, e)
    return best[0] if best else "no span"


def _events(line):
    return [{"name": ev.name, "start_ns": ev.start_ns,
             "dur_ns": ev.duration_ns} for ev in line.events]


def read_xplane(path):
    """(devices, host spans) of one trace: per device plane its ops and
    module events; host spans as (name, start_ns, end_ns)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    devices, spans = [], []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {ln.name: ln for ln in plane.lines}
            devices.append({
                "plane": plane.name,
                "ops": _events(lines[OPS_LINE]) if OPS_LINE in lines else [],
                "modules": (_events(lines[MODULES_LINE])
                            if MODULES_LINE in lines else []),
            })
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for ev in ln.events:
                    if ev.name in GAP_SPANS or ev.name == "window":
                        spans.append((ev.name, ev.start_ns,
                                      ev.start_ns + ev.duration_ns))
    return devices, spans


def reduce_events(devices, spans, rules: dict, chips: int) -> dict:
    """Per-layer seconds, busy and window time and the breakdown from the
    events of the first `chips` device planes, clipped to the window."""
    win = [(s, e) for name, s, e in spans if name == "window"]
    if not win:
        raise ValueError("no window span in the trace")
    lo, hi = win[0]
    devices = sorted(devices, key=lambda d: int(d["plane"].rsplit(":", 1)[1]))
    devices = devices[:chips]
    layers: dict[str, float] = {}
    busy, gap_list = 0.0, []
    for dev in devices:
        ops = [ev for ev in dev["ops"]
               if ev["start_ns"] >= lo and ev["start_ns"] < hi]
        for name, secs in classify(ops, rules).items():
            layers[name] = layers.get(name, 0.0) + secs / len(devices)
        step = module_seconds(
            [ev for ev in dev["modules"] if lo <= ev["start_ns"] < hi], rules)
        layers["step"] = layers.get("step", 0.0) + step / len(devices)
        iv = [(ev["start_ns"], min(ev["start_ns"] + ev["dur_ns"], hi))
              for ev in ops]
        busy += union_seconds(iv) / len(devices)
        if dev is devices[0]:
            gap_list = [(name_gap(g, spans), (g[1] - g[0]) / 1e9)
                        for g in gaps(iv, lo, hi)]
    gap_list.sort(key=lambda g: -g[1])
    ranked = sorted(((k, v) for k, v in layers.items() if k != "step"),
                    key=lambda kv: -kv[1])
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy,
        "layers": layers,
        "breakdown": {"device_ops": [[k, v] for k, v in ranked[:10]],
                      "idle_gaps": [[k, v] for k, v in gap_list[:10]]},
    }


def work(cfg: dict, traffic: dict, peak: dict) -> dict:
    """The least time per step and chip of the kernels with a roofline."""
    n, d, shards = int(cfg["n"]), int(cfg["d"]), int(cfg["shards"])
    tb = int(traffic["batch"])
    out = {"distance_min_s": roofline.min_seconds(
        *roofline.distance_work(-(-tb // shards), n, d), peak)}
    if cfg["method"] in ("sti", "sii"):
        out["fill_min_s"] = roofline.min_seconds(
            0.0, roofline.fill_bytes(tb, n // shards, n), peak)
    return out


def reduce_dir(tdir, devs, *, steps: int, cfg: dict, traffic: dict,
               spans: dict, bench) -> dict:
    """The reduction a run's metrics read (see the module docstring)."""
    paths = sorted(Path(tdir).rglob("*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {tdir}")
    devices, host = read_xplane(paths[-1])
    red = reduce_events(devices, host, load_rules(bench), len(devs))
    red["steps"] = steps
    red["host"] = spans
    red["work"] = work(cfg, traffic,
                       roofline.peaks(bench, devs[0].device_kind))
    return red
