"""The program's own stage names in a profiler trace of the window: a
reading beside `reduce_trace.py`, which it leaves as it is.

The step names its stages with `jax.named_scope`, and
`ValuationSession.update` writes host spans with integer counters;
`scopes.json` lists both sets of names. A device op carries its scopes in
the `op_name` of its HLO instruction, which `op_paths` reads from the
program's `HloProto` that the trace holds (the op events' `tf_op` stat
leaves out the fill's `while` on a TPU).

    scopes      seconds per listed scope in the window, averaged over the
                chips: each top-level op goes to the innermost listed
                scope on its path, else to `unscoped`; empty when no op
                carries a listed scope (a program without them)
    program     the durations (s) of the program's spans in the window,
                by name
    counts      the program spans' integer stats summed, by span name
    idle_gaps   the first chip's longest idle gaps, each named after the
                innermost host span, the benchmark's or the program's,
                that covers it

`merge` lays these over a `reduce_trace` reduction: `breakdown.device_ops`
lists the scopes where there are any, else the layers. Run alone, it
reduces a kept trace of one `bench/run.py --trace 1` window:

    python3 bench/scope_trace.py TRACE.xplane.pb[.gz] --steps N [--chips C]
"""

from __future__ import annotations

import argparse
import gzip
import json
import sys
from pathlib import Path

import reduce_trace as rt

UNSCOPED = "unscoped"


def load_scopes(bench_dir) -> dict:
    return json.loads((Path(bench_dir) / "scopes.json").read_text())


def scope_of(path: str, names) -> str | None:
    """The innermost of `names` on an op's scope path
    (`jit(step)/update/to_train/gather`); the last component is the op
    itself, not a scope."""
    for part in reversed(path.split("/")[:-1]):
        if part in names:
            return part
    return None


def top_level(ops):
    """The top-level `ops` ({name, start_ns, dur_ns}) in start order, by
    the rule of `reduce_trace.classify`: an op that starts inside an
    earlier one (the body of a `while`, say) is part of it and left out."""
    end = None
    for ev in sorted(ops, key=lambda e: (e["start_ns"], -e["dur_ns"])):
        if end is not None and ev["start_ns"] < end:
            continue
        end = ev["start_ns"] + ev["dur_ns"]
        yield ev


def classify_scopes(ops, names) -> dict:
    """Seconds per scope of the top-level `ops`, each by the innermost of
    `names` on its `path`, else `unscoped`; empty when no op carries one
    of `names`."""
    out: dict[str, float] = {}
    scoped = False
    for ev in top_level(ops):
        scope = scope_of(ev.get("path", ""), names)
        scoped = scoped or scope is not None
        scope = scope or UNSCOPED
        out[scope] = out.get(scope, 0.0) + ev["dur_ns"] / 1e9
    return out if scoped else {}


def _varint(buf, i: int):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _pb_fields(buf):
    """(field number, value) of each field of one protobuf message: an int
    for a varint, a memoryview for a length-delimited field; fixed-width
    fields, which nothing here reads, are skipped."""
    buf = memoryview(buf)
    i = 0
    while i < len(buf):
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            val, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
            continue
        else:
            raise ValueError(f"unknown protobuf wire type {wire}")
        yield key >> 3, val


def _ints(v) -> list:
    """A repeated integer field: packed (a memoryview) or one varint."""
    if isinstance(v, int):
        return [v]
    out, i = [], 0
    while i < len(v):
        x, i = _varint(v, i)
        out.append(x)
    return out


def _str(v) -> str:
    return bytes(v).decode()


def hlo_paths(hlo, names) -> dict:
    """{instruction name: scope path} of the entry computation of one
    serialized `HloProto` (hlo_module 1; HloModuleProto: computations 3,
    entry_computation_id 6; HloComputationProto: instructions 2, id 5,
    root_id 6; HloInstructionProto: name 1, opcode 2, metadata 7
    (OpMetadata: op_name 2), tuple_index 13, id 35, operand_ids 36,
    called_computation_ids 38).

    The path is the instruction's `op_name` where that carries one of
    `names`. The TPU compiler makes some instructions with no op_name (a
    scatter's expansion into a sort and a custom fusion, the windows of a
    cumulative sum, layout copies); such an instruction takes the scope
    named most often among the op_names of the instructions fused into it
    and the paths of the values it reads (an element of a tuple that a
    fusion returns has the path of the root operand that computed it),
    the first named on a tie. Two stages can read one value after XLA
    merges equal computations, so one operand alone does not decide. The
    proto lists callees before callers and operands before users, so one
    pass resolves them."""
    module = list(_pb_fields(dict(_pb_fields(hlo)).get(1, b"")))
    entry = next((v for f, v in module if f == 6), None)
    by_id: dict[int, str] = {}      # instruction id -> path
    tuples: dict[int, list] = {}    # fusion id -> paths of its outputs
    roots: dict[int, list] = {}     # computation id -> root tuple paths
    named: dict[int, list] = {}     # computation id -> its own paths
    out: dict[str, str] = {}
    for f, comp in module:
        if f != 3:
            continue
        cf = list(_pb_fields(comp))
        cid = next((v for g, v in cf if g == 5), None)
        root = next((v for g, v in cf if g == 6), None)
        named[cid] = []
        for g, ins in cf:
            if g != 2:
                continue
            d = {1: b"", 2: b"", 7: b"", 13: 0, 35: None}
            operands, called = [], []
            for h, v in _pb_fields(ins):
                if h == 36:
                    operands += _ints(v)
                elif h == 38:
                    called += _ints(v)
                else:
                    d[h] = v
            path = _str(dict(_pb_fields(d[7])).get(2, b""))
            opcode = _str(d[2])
            if scope_of(path, names) is not None:
                named[cid].append(path)
            else:
                votes = [p for c in called[:1] for p in named.get(c, [])]
                if opcode == "get-tuple-element" and operands[0] in tuples:
                    outs = tuples[operands[0]]
                    votes.append(outs[d[13]] if d[13] < len(outs) else "")
                else:
                    votes += [by_id.get(o, "") for o in operands]
                votes = [p for p in votes if p]
                count: dict[str | None, int] = {}
                for p in votes:
                    sc = scope_of(p, names)
                    count[sc] = count.get(sc, 0) + 1
                path = max(votes, key=lambda p: count[scope_of(p, names)],
                           default="")
            by_id[d[35]] = path
            if d[35] == root and opcode == "tuple":
                roots[cid] = [by_id.get(o, "") for o in operands]
            if called and called[0] in roots:
                tuples[d[35]] = roots[called[0]]
            if cid == entry:
                out[_str(d[1])] = path
    return out


def op_paths(raw: bytes, names) -> dict:
    """{device plane name: {op event name: scope path}} of one serialized
    `XSpace`, read from the proto itself: `ProfileData` exposes neither
    an op's program nor the HLO.

    The host plane `/host:metadata` holds each program's `HloProto` as the
    stat "Hlo Proto" of an event metadata whose id is the program id.
    On a device plane an op's event metadata (XPlane.event_metadata 4:
    name 2, display_name 4, stats 5) names its HLO instruction
    (display_name) and program (stat "program_id"); stats (XStat:
    metadata_id 1, a varint in 3 or 4, bytes in 6) name their kind
    through the plane's stat metadata (5: id 1, name 2). The path is
    `hlo_paths`'s; an op name that two programs give different paths
    maps to "" (no scope)."""
    planes = []
    for num, plane in _pb_fields(raw):
        if num != 1:
            continue
        name, events, stat_names = "", [], {}
        for f, v in _pb_fields(plane):
            if f == 2:
                name = _str(v)
            elif f in (4, 5):
                entry = dict(_pb_fields(v)).get(2)
                if entry is None:
                    continue
                if f == 4:
                    events.append(entry)
                else:
                    md = dict(_pb_fields(entry))
                    stat_names[md.get(1, 0)] = _str(md.get(2, b""))
        planes.append((name, events, stat_names))

    def stats(em, stat_names):
        return {stat_names.get(st.get(1)): st.get(3, st.get(4, st.get(6)))
                for st in (dict(_pb_fields(v))
                           for f, v in _pb_fields(em) if f == 5)}

    programs = {}
    for name, events, stat_names in planes:
        if name != "/host:metadata":
            continue
        for em in events:
            hlo = stats(em, stat_names).get("Hlo Proto")
            pid = dict(_pb_fields(em)).get(1)
            if hlo is not None and pid is not None:
                programs[pid & (2**64 - 1)] = hlo_paths(hlo, names)
    out = {}
    for name, events, stat_names in planes:
        if not rt.DEVICE_PLANE.match(name):
            continue
        paths: dict[str, str] = {}
        for em in events:
            md = dict(_pb_fields(em))
            op, instr = _str(md.get(2, b"")), _str(md.get(4, b""))
            pid = stats(em, stat_names).get("program_id")
            path = (programs.get(pid & (2**64 - 1), {}).get(instr, "")
                    if isinstance(pid, int) else "")
            paths[op] = path if paths.get(op, path) == path else ""
        out[name] = paths
    return out


def read_xplane(raw: bytes, scopes: dict):
    """(devices, host spans, program spans) of one serialized `XSpace`
    (the bytes of an `.xplane.pb`): per
    device plane its ops, each with its scope `path`; the benchmark's
    host spans as (name, start_ns, end_ns), as `reduce_trace.read_xplane`
    gives them; the program's spans that `scopes` lists as
    {name, start_ns, dur_ns, stats} with their integer stats."""
    from jax.profiler import ProfileData

    paths = op_paths(raw, scopes["scopes"])
    names = set(scopes["spans"])
    pd = ProfileData.from_serialized_xspace(raw)
    devices, spans, program = [], [], []
    for plane in pd.planes:
        if rt.DEVICE_PLANE.match(plane.name):
            on = paths.get(plane.name, {})
            ops = [dict(name=ev.name, start_ns=ev.start_ns,
                        dur_ns=ev.duration_ns, path=on.get(ev.name, ""))
                   for ln in plane.lines if ln.name == rt.OPS_LINE
                   for ev in ln.events]
            devices.append({"plane": plane.name, "ops": ops})
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for ev in ln.events:
                    if ev.name in rt.GAP_SPANS or ev.name == "window":
                        spans.append((ev.name, ev.start_ns,
                                      ev.start_ns + ev.duration_ns))
                    elif ev.name in names:
                        program.append({
                            "name": ev.name, "start_ns": ev.start_ns,
                            "dur_ns": ev.duration_ns,
                            "stats": {k: v for k, v in ev.stats
                                      if isinstance(v, int)}})
    return devices, spans, program


def reduce_events(devices, spans, program, scopes: dict,
                  chips: int) -> dict:
    """Scopes, program spans and counts, and the named idle gaps (see the
    module docstring) of the first `chips` device planes, clipped to the
    window, as `reduce_trace.reduce_events` clips them."""
    win = [(s, e) for name, s, e in spans if name == "window"]
    if not win:
        raise ValueError("no window span in the trace")
    lo, hi = win[0]
    devices = sorted(devices, key=lambda d: int(d["plane"].rsplit(":", 1)[1]))
    devices = devices[:chips]
    gap_spans = list(spans) + [(ev["name"], ev["start_ns"],
                                ev["start_ns"] + ev["dur_ns"])
                               for ev in program]
    by_scope: dict[str, float] = {}
    gap_list = []
    for dev in devices:
        ops = [ev for ev in dev["ops"] if lo <= ev["start_ns"] < hi]
        for name, secs in classify_scopes(ops, scopes["scopes"]).items():
            by_scope[name] = by_scope.get(name, 0.0) + secs / len(devices)
        if dev is devices[0]:
            iv = [(ev["start_ns"], min(ev["start_ns"] + ev["dur_ns"], hi))
                  for ev in ops]
            gap_list = [(rt.name_gap(g, gap_spans), (g[1] - g[0]) / 1e9)
                        for g in rt.gaps(iv, lo, hi)]
    gap_list.sort(key=lambda g: -g[1])
    durations: dict[str, list] = {}
    counts: dict[str, dict] = {}
    for ev in program:
        if not lo <= ev["start_ns"] < hi:
            continue
        durations.setdefault(ev["name"], []).append(ev["dur_ns"] / 1e9)
        c = counts.setdefault(ev["name"], {})
        for k, v in ev["stats"].items():
            c[k] = c.get(k, 0) + v
    return {"scopes": by_scope, "program": durations, "counts": counts,
            "idle_gaps": [[k, v] for k, v in gap_list[:10]]}


def reduce_file(path, bench_dir, chips: int) -> dict:
    """`reduce_events` of one `.xplane.pb` (or one gzipped), by
    `bench_dir`'s scopes.json."""
    scopes = load_scopes(bench_dir)
    raw = Path(path).read_bytes()
    if Path(path).suffix == ".gz":
        raw = gzip.decompress(raw)
    devices, spans, program = read_xplane(raw, scopes)
    return reduce_events(devices, spans, program, scopes, chips)


def merge(red: dict, part: dict) -> dict:
    """`red`, a `reduce_trace` reduction, with `part`'s scopes, program
    spans and counts, and a breakdown whose device ops are the scopes
    where there are any (else `red`'s layers) and whose idle gaps are
    named by the program's spans too. `red`'s other values stay as they
    are."""
    out = dict(red, scopes=part["scopes"], program=part["program"],
               counts=part["counts"])
    ops = red["breakdown"]["device_ops"]
    if part["scopes"]:
        ops = [[k, v] for k, v in sorted(part["scopes"].items(),
                                         key=lambda kv: -kv[1])[:10]]
    out["breakdown"] = {"device_ops": ops, "idle_gaps": part["idle_gaps"]}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("trace", help="an .xplane.pb, or one gzipped")
    ap.add_argument("--steps", type=int, required=True,
                    help="steps in the trace's window")
    ap.add_argument("--chips", type=int, default=1)
    args = ap.parse_args(argv)
    part = reduce_file(args.trace, Path(__file__).parent, args.chips)
    per_step = {k: 1e3 * v / args.steps for k, v in part["scopes"].items()}
    print(json.dumps({
        "scopes_ms_per_step": per_step,
        "scope_sum_ms_per_step": sum(per_step.values()),
        "program_ms_per_step": {k: 1e3 * sum(v) / args.steps
                                for k, v in part["program"].items()},
        "counts": part["counts"], "idle_gaps": part["idle_gaps"]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
