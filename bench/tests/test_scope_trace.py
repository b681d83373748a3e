"""The scope and span reading of a trace, on synthetic events and protos."""

from pathlib import Path

import pytest

import reduce_trace as rt
import scope_trace as st

BENCH = Path(st.__file__).parent

RULES = {
    "step": "^jit_step",
    "ops": [
        {"layer": "distance", "pattern": "distance_kernel"},
        {"layer": "sort_rank", "pattern": "^sort"},
        {"layer": "fill", "pattern": "^fill"},
    ],
}
SCOPES = {"scopes": ["distance", "sort", "rank", "label_gather",
                     "to_train", "fill", "update"],
          "spans": ["session.update", "session.pad", "session.dispatch"]}
SPANS = [("window", 0, 6000), ("update", 0, 900),
         ("block_until_ready", 3600, 6000)]


def ev(name, start, dur, path=None):
    out = {"name": name, "start_ns": start, "dur_ns": dur}
    if path is not None:
        out["path"] = path
    return out


def test_scopes_file_lists_the_program_names():
    scopes = st.load_scopes(BENCH)
    assert {"distance", "sort", "rank", "label_gather", "contrib",
            "recurrence", "to_train", "fill", "update",
            "collective"} == set(scopes["scopes"])
    assert scopes["spans"] == ["session.update", "session.pad",
                               "session.dispatch"]
    assert st.UNSCOPED not in scopes["scopes"]


@pytest.mark.parametrize("path, want", [
    ("jit(step)/distance/pallas_call", "distance"),
    ("jit(step)/sort/jit(argsort)/sort", "sort"),
    ("jit(step)/update/to_train/gather", "to_train"),
    ("jit(step)/update/add", "update"),
    ("jit(step)/update/fill/while", "fill"),
    # the last component is the op, not a scope
    ("jit(step)/sort", None),
    ("jit(step)/jit(main)/copy", None),
    ("", None),
])
def test_scope_of_takes_the_innermost_listed_scope(path, want):
    assert st.scope_of(path, SCOPES["scopes"]) == want


def test_classify_scopes_innermost_and_unscoped():
    ops = [ev("fusion.1", 0, 100, "jit(step)/distance/pallas_call"),
           ev("sort.2", 100, 300, "jit(step)/sort/jit(argsort)/sort"),
           ev("fusion.3", 400, 50, "jit(step)/update/to_train/gather"),
           ev("fusion.4", 450, 20, "jit(step)/update/reduce_sum"),
           ev("copy.5", 470, 10, "")]
    got = st.classify_scopes(ops, SCOPES["scopes"])
    assert got == pytest.approx({"distance": 100e-9, "sort": 300e-9,
                                 "to_train": 50e-9, "update": 20e-9,
                                 "unscoped": 10e-9})


def test_classify_scopes_counts_top_level_ops_only():
    # the body of the fill's while starts inside it: counted with it, not
    # under its own scope
    ops = [ev("while.1", 0, 1000, "jit(step)/update/fill/while"),
           ev("fusion.2", 10, 500, "jit(step)/update/to_train/gather"),
           ev("fusion.3", 600, 100, ""),
           ev("fusion.4", 1000, 40, "jit(step)/rank/scatter")]
    got = st.classify_scopes(ops, SCOPES["scopes"])
    assert got == pytest.approx({"fill": 1000e-9, "rank": 40e-9})


def test_classify_scopes_is_empty_without_any_scope():
    ops = [ev("sort.1", 0, 10, "jit(step)/sort"), ev("copy", 10, 5)]
    assert st.classify_scopes(ops, SCOPES["scopes"]) == {}


def _devices(scoped=True):
    paths = {"distance_kernel": "jit(step)/distance/pallas_call",
             "sort.1": "jit(step)/sort/jit(argsort)/sort",
             "fill.1": "jit(step)/update/fill/while"}
    ops = [ev("distance_kernel", 1000, 100),
           ev("sort.1", 1100, 400), ev("fill.1", 1500, 2000),
           ev("copy", 4000, 500), ev("sort.2", 9000, 100)]
    if scoped:
        ops = [dict(e, path=paths.get(e["name"], "")) for e in ops]
    mods = [ev("jit_step(123)", 1000, 3500), ev("jit_copy", 4000, 500)]
    return [{"plane": "/device:TPU:1", "ops": ops, "modules": mods},
            {"plane": "/device:TPU:0", "ops": ops, "modules": mods}]


def _program():
    return [{"name": "session.update", "start_ns": 100, "dur_ns": 700,
             "stats": {"points": 256, "slices": 1}},
            {"name": "session.pad", "start_ns": 150, "dur_ns": 100,
             "stats": {}},
            {"name": "session.dispatch", "start_ns": 300, "dur_ns": 450,
             "stats": {}},
            {"name": "session.update", "start_ns": 7000, "dur_ns": 10,
             "stats": {"points": 9, "slices": 1}}]


def test_scope_paths_leave_the_layer_reduction_unchanged():
    plain = rt.reduce_events(_devices(scoped=False), SPANS, RULES, chips=2)
    scoped = rt.reduce_events(_devices(), SPANS, RULES, chips=2)
    assert scoped == plain


def test_reduce_events_scopes_spans_and_counts():
    layers = rt.reduce_events(_devices(), SPANS, RULES, chips=2)["layers"]
    part = st.reduce_events(_devices(), SPANS, _program(), SCOPES, chips=2)
    assert part["scopes"] == pytest.approx(
        {"distance": 100e-9, "sort": 400e-9, "fill": 2000e-9,
         "unscoped": 500e-9})
    # the scopes sum to the layers' ops
    assert sum(part["scopes"].values()) == pytest.approx(
        sum(v for k, v in layers.items() if k != "step"))
    # program spans inside the window only (the second update is past it)
    assert part["program"]["session.update"] == pytest.approx([700e-9])
    assert part["program"]["session.dispatch"] == pytest.approx([450e-9])
    assert part["counts"]["session.update"] == {"points": 256, "slices": 1}


def test_reduce_events_needs_a_window():
    with pytest.raises(ValueError):
        st.reduce_events(_devices(), [("update", 0, 10)], [], SCOPES, 1)


def test_gap_inside_session_dispatch_is_named_after_it():
    part = st.reduce_events(_devices(), SPANS, _program(), SCOPES, chips=2)
    # 0..1000 idle: its middle (500) lies in session.dispatch (300..750),
    # inside session.update (100..800), inside the benchmark's update
    assert [k for k, v in part["idle_gaps"] if v == pytest.approx(1e-6)] == [
        "session.dispatch"]
    assert rt.name_gap((110, 130), [("update", 0, 900)] + [
        (e["name"], e["start_ns"], e["start_ns"] + e["dur_ns"])
        for e in _program()]) == "session.update"


def test_without_program_spans_the_gaps_read_as_the_layer_reduction():
    red = rt.reduce_events(_devices(), SPANS, RULES, chips=2)
    part = st.reduce_events(_devices(), SPANS, [], SCOPES, chips=2)
    assert part["idle_gaps"] == red["breakdown"]["idle_gaps"]


def test_merge_lists_scopes_and_keeps_every_value_of_the_reduction():
    red = rt.reduce_events(_devices(), SPANS, RULES, chips=2)
    part = st.reduce_events(_devices(), SPANS, _program(), SCOPES, chips=2)
    got = st.merge(red, part)
    assert {k: got[k] for k in red if k != "breakdown"} == {
        k: v for k, v in red.items() if k != "breakdown"}
    assert got["breakdown"]["device_ops"][0] == ["fill",
                                                 pytest.approx(2e-6)]
    assert {k for k, _ in got["breakdown"]["device_ops"]} == set(
        part["scopes"])
    assert got["breakdown"]["idle_gaps"] == part["idle_gaps"]
    assert got["counts"] == part["counts"]


def test_merge_falls_back_to_layers_without_scopes():
    red = rt.reduce_events(_devices(scoped=False), SPANS, RULES, chips=2)
    part = st.reduce_events(_devices(scoped=False), SPANS, [], SCOPES,
                            chips=2)
    got = st.merge(red, part)
    assert got["scopes"] == {} and got["program"] == {}
    assert got["counts"] == {}
    assert got["breakdown"] == red["breakdown"]
    assert [k for k, _ in got["breakdown"]["device_ops"]] == [
        "fill", "other", "sort_rank", "distance"]


def test_reduce_file_reads_the_session_spans_of_a_cpu_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.session import ValuationSession

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(16, 3)), jnp.float32)
    y = jnp.asarray(rng.integers(0, 2, size=(16,)), jnp.int32)
    sess = ValuationSession(x, y, k=3, mode="knn_shapley", test_batch=4)
    sess.update(x[:4], y[:4])  # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("window"):
            sess.update(x[:6], y[:6])
            sess.update(x[:1], y[:1])
            jax.block_until_ready(sess._state)
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.rglob("*.xplane.pb")
    part = st.reduce_file(path, BENCH, chips=1)
    # a CPU trace has no TPU plane: no scopes, no gaps
    assert part["scopes"] == {} and part["idle_gaps"] == []
    assert part["counts"]["session.update"] == {"points": 7, "slices": 3}
    assert len(part["program"]["session.update"]) == 2
    assert len(part["program"]["session.dispatch"]) == 3


# --------------------------------------------- scope paths from the proto
def _varint(n: int) -> bytes:
    n &= 2**64 - 1
    out = b""
    while True:
        b, n = n & 0x7F, n >> 7
        out += bytes([b | (0x80 if n else 0)])
        if not n:
            return out


def pb(*fields) -> bytes:
    """A protobuf message from (field number, value) pairs: an int is a
    varint, str or bytes length-delimited, a list of ints packed."""
    out = b""
    for num, v in fields:
        if isinstance(v, int):
            out += _varint(num << 3) + _varint(v)
            continue
        if isinstance(v, list):
            v = b"".join(_varint(x) for x in v)
        elif isinstance(v, str):
            v = v.encode()
        out += _varint(num << 3 | 2) + _varint(len(v)) + v
    return out


def instr(iid, name, opcode, op_name="", operands=(), called=(),
          tuple_index=None):
    fields = [(1, name), (2, opcode), (7, pb((2, op_name))), (35, iid)]
    if operands:
        fields.append((36, list(operands)))
    if called:
        fields.append((38, list(called)))
    if tuple_index is not None:
        fields.append((13, tuple_index))
    return pb(*fields)


def computation(cid, root, *instrs):
    return pb(*[(2, i) for i in instrs], (5, cid), (6, root))


def hlo_module():
    """A step as the TPU compiler leaves it: a multi-output fusion, a
    scatter expanded into instructions with no op_name, a copy of a
    parameter, the fill's while."""
    lg, rk = "jit(step)/label_gather/gather", "jit(step)/rank/select_n"
    fused_tuple = computation(
        10, 103,
        instr(101, "param_0", "parameter"),
        instr(102, "gather.3", "gather", lg, [101]),
        instr(104, "select.1", "select", rk, [101]),
        instr(103, "tuple.1", "tuple", "", [102, 104]))
    fused_mixed = computation(
        20, 203,
        instr(201, "param_0", "parameter"),
        instr(202, "concatenate.1", "concatenate",
              "jit(step)/rank/concatenate", [201]),
        instr(204, "gather.9", "gather",
              "jit(step)/update/to_train/gather", [201]),
        instr(203, "and.1", "and", "", [202, 204]))
    entry = computation(
        1, 9,
        instr(1, "p.0", "parameter"),
        instr(2, "sort.0", "sort", "jit(step)/sort/jit(argsort)/sort", [1]),
        instr(3, "fusion.18", "fusion", lg, [2], called=[10]),
        instr(4, "get-tuple-element.50", "get-tuple-element", "", [3],
              tuple_index=0),
        instr(5, "get-tuple-element.51", "get-tuple-element", "", [3],
              tuple_index=1),
        instr(6, "and_reduce_fusion", "fusion", "", [4, 5], called=[20]),
        instr(7, "sort.2", "sort", "", [6, 2]),
        instr(8, "copy.1", "copy", "", [1]),
        instr(9, "while.1", "while", "jit(step)/update/fill/while", [7]))
    return pb((1, pb((3, fused_tuple), (3, fused_mixed), (3, entry),
                     (6, 1))))


def test_hlo_paths_name_compiler_made_instructions_by_their_stage():
    got = {k: st.scope_of(v, SCOPES["scopes"])
           for k, v in st.hlo_paths(hlo_module(), SCOPES["scopes"]).items()}
    assert got == {
        "p.0": None, "sort.0": "sort", "fusion.18": "label_gather",
        # an element of the fusion's tuple: the root operand computing it
        "get-tuple-element.50": "label_gather",
        "get-tuple-element.51": "rank",
        # fused rank + to_train, read label_gather + rank: rank, 2 to 1
        "and_reduce_fusion": "rank",
        # rank and sort read: a tie, the first named wins
        "sort.2": "rank",
        # nothing upstream names a stage: no scope
        "copy.1": None,
        "while.1": "fill"}


PID = 10142023210398144953   # over 2**63: an int64 id, negative on the wire


def xspace(ops, programs):
    """An XSpace with the programs' HloProtos on `/host:metadata` and op
    event metadata on one device plane: ops as (long name, instruction,
    program id)."""
    meta = pb((2, "/host:metadata"),
              *[(4, pb((1, pid), (2, pb(
                  (1, pid), (2, f"jit_step({pid})"),
                  (5, pb((1, 1), (6, hlo))))))) for pid, hlo in programs],
              (5, pb((1, 1), (2, pb((1, 1), (2, "Hlo Proto"))))))
    dev = pb((2, "/device:TPU:0"),
             *[(4, pb((1, k + 1), (2, pb(
                 (1, k + 1), (2, long), (4, name),
                 (5, pb((1, 7), (3, pid)))))))
               for k, (long, name, pid) in enumerate(ops)],
             (5, pb((1, 7), (2, pb((1, 7), (2, "program_id"))))))
    return pb((1, meta), (1, dev), (1, pb((2, "/host:CPU"))))


def test_op_paths_map_each_op_event_to_its_instruction_path():
    ops = [("%sort.2 = (s32[8]) sort(...)", "sort.2", PID),
           ("%while.1 = (f32[4,4]) while(...)", "while.1", PID),
           ("%copy.1 = f32[4] copy(...)", "copy.1", PID),
           # a program the trace holds no HLO of
           ("%fusion.7 = f32[4] fusion(...)", "fusion.7", 5),
           # one op text in two programs with different paths
           ("%sort.0 = s32[8] sort(...)", "sort.0", PID),
           ("%sort.0 = s32[8] sort(...)", "while.1", PID)]
    got = st.op_paths(xspace(ops, [(PID, hlo_module())]),
                      SCOPES["scopes"])
    assert set(got) == {"/device:TPU:0"}
    paths = got["/device:TPU:0"]
    assert st.scope_of(paths["%sort.2 = (s32[8]) sort(...)"],
                       SCOPES["scopes"]) == "rank"
    assert paths["%while.1 = (f32[4,4]) while(...)"] == (
        "jit(step)/update/fill/while")
    assert paths["%copy.1 = f32[4] copy(...)"] == ""
    assert paths["%fusion.7 = f32[4] fusion(...)"] == ""
    assert paths["%sort.0 = s32[8] sort(...)"] == ""


def test_op_paths_without_programs_give_no_scope():
    ops = [("%sort.0 = s32[8] sort(...)", "sort.0", PID)]
    paths = st.op_paths(xspace(ops, []), SCOPES["scopes"])["/device:TPU:0"]
    assert paths == {"%sort.0 = s32[8] sort(...)": ""}
