"""reprolint suite: every rule trips on its fixture and stays quiet on the
fixed twin; the contract checker passes the live registries and catches
deliberately broken entries; the CLI gate exits by contract."""

import jax.numpy as jnp
import pytest

from repro.analysis import lint_source, lint_tree, load_baseline
from repro.analysis.baseline import split_baselined, write_baseline
from repro.analysis.findings import Finding

# ------------------------------------------------------------------ fixtures
# code -> (tripping source, fixed source). Each fixed twin is the tripping
# snippet with exactly the rule's fix applied, so a rule that matches too
# broadly fails here, not in review.
FIXTURES = {
    "R101": (
        """
import jax
step = jax.jit(lambda a, b: a + b, donate_argnums=(0,))
def run(acc, xs):
    for x in xs:
        out = step(acc, x)
    return acc.sum()
""",
        """
import jax
step = jax.jit(lambda a, b: a + b, donate_argnums=(0,))
def run(acc, xs):
    for x in xs:
        acc = step(acc, x)
    return acc.sum()
""",
    ),
    "R201": (
        """
import jax
TABLE = {"a": 1}
@jax.jit
def f(x):
    return x * TABLE["a"]
""",
        """
import jax
TABLE = (("a", 1),)
@jax.jit
def f(x):
    return x * TABLE[0][1]
""",
    ),
    "R202": (
        """
import functools
@functools.lru_cache(maxsize=None)
def make_step(k, fill_static=()):
    return k
make_step(3, fill_static={"chunk": 1})
""",
        """
import functools
@functools.lru_cache(maxsize=None)
def make_step(k, fill_static=()):
    return k
make_step(3, fill_static=(("chunk", 1),))
""",
    ),
    "R203": (
        """
import jax
@jax.jit
def f(x):
    n = x.shape[0]
    if n > 2:
        return x * 2
    return x
""",
        """
import jax
import jax.numpy as jnp
@jax.jit
def f(x):
    n = x.shape[0]
    return jnp.where(jnp.arange(n) > 2, x * 2, x)
""",
    ),
    "R301": (
        """
import jax
from jax.sharding import PartitionSpec as P
from jax.experimental.shard_map import shard_map
def local(x):
    return jax.lax.psum(x, "rows")
def build(mesh):
    return shard_map(local, mesh=mesh,
                     in_specs=(P("shards"),), out_specs=P("shards"))
""",
        """
import jax
from jax.sharding import PartitionSpec as P
from jax.experimental.shard_map import shard_map
def local(x):
    return jax.lax.psum(x, "shards")
def build(mesh):
    return shard_map(local, mesh=mesh,
                     in_specs=(P("shards"),), out_specs=P("shards"))
""",
    ),
    "R302": (
        """
import jax
def partial_sum(x):
    return jax.lax.psum_scatter(x, "shards", tiled=True)
""",
        """
import jax
from jax.sharding import PartitionSpec as P
from repro import compat
def partial_sum(x):
    return jax.lax.psum_scatter(x, "shards", tiled=True)
def build(mesh):
    return compat.shard_map(partial_sum, mesh=mesh,
                            in_specs=(P("shards"),), out_specs=P("shards"))
""",
    ),
    "R401": (
        """
from jax.experimental import pallas as pl
import jax
def fill(x):
    return pl.pallas_call(
        kern, grid=(4, 4),
        in_specs=[pl.BlockSpec((8, 8), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((8, 8), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((32, 32), jnp.float32),
    )(x)
""",
        """
from jax.experimental import pallas as pl
import jax
def fill(x):
    return pl.pallas_call(
        kern, grid=(4, 4),
        in_specs=[pl.BlockSpec((8, 8), lambda i, j: (i, 0))],
        out_specs=pl.BlockSpec((8, 8), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((32, 32), jnp.float32),
    )(x)
""",
    ),
    "R402": (
        """
from jax.experimental import pallas as pl
import jax
def fill(acc, x):
    return pl.pallas_call(
        kern, grid=(4,),
        input_output_aliases={2: 0},
        out_shape=jax.ShapeDtypeStruct((32,), jnp.float32),
    )(acc, x)
""",
        """
from jax.experimental import pallas as pl
import jax
def fill(acc, x):
    return pl.pallas_call(
        kern, grid=(4,),
        input_output_aliases={0: 0},
        out_shape=jax.ShapeDtypeStruct((32,), jnp.float32),
    )(acc, x)
""",
    ),
    "R403": (
        """
from jax.experimental import pallas as pl
import jax
def fill(x, bn):
    return pl.pallas_call(
        kern, grid=(x.shape[0] // bn,),
        out_shape=jax.ShapeDtypeStruct((32,), jnp.float32),
    )(x)
""",
        """
from jax.experimental import pallas as pl
import jax
import jax.numpy as jnp
def fill(x, bn):
    pad = (-x.shape[0]) % bn
    x = jnp.pad(x, ((0, pad), (0, 0)))
    return pl.pallas_call(
        kern, grid=(x.shape[0] // bn,),
        out_shape=jax.ShapeDtypeStruct((32,), jnp.float32),
    )(x)
""",
    ),
    "R501": (
        """
import jax.numpy as jnp
def mm(a, b):
    return jnp.einsum("ij,jk->ik", a.astype(jnp.bfloat16), b)
""",
        """
import jax.numpy as jnp
def mm(a, b):
    return jnp.einsum("ij,jk->ik", a.astype(jnp.bfloat16), b,
                      preferred_element_type=jnp.float32)
""",
    ),
    "R601": (
        """
import jax.numpy as jnp
IDX = jnp.arange(128)
""",
        """
import functools
import jax.numpy as jnp
DTYPE = jnp.float32
@functools.lru_cache(maxsize=None)
def idx():
    return jnp.arange(128)
""",
    ),
    "R602": (
        """
import jax
NDEV = jax.device_count()
""",
        """
import jax
def ndev():
    return jax.device_count()
""",
    ),
    "R603": (
        """
from repro.launch.compile_cache import enable_compile_cache
enable_compile_cache()
""",
        """
from repro.launch.compile_cache import enable_compile_cache
def main():
    enable_compile_cache()
""",
    ),
    "R701": (
        """
import numpy as np
def serve(batch):
    vals = np.asarray(batch.values)
    return vals.sum()
""",
        """
import numpy as np
def serve(batch):
    # sync-point: result extraction must land on the host
    vals = np.asarray(batch.values)
    return vals.sum()
""",
    ),
}

# path-scoped rules only fire on matching relpaths; fixtures for them are
# linted as if they lived at this path (everything else uses the default
# "<snippet>", which no path-scoped rule matches)
FIXTURE_PATHS = {"R701": "serving/valuation_service.py"}


@pytest.mark.parametrize("code", sorted(FIXTURES))
def test_rule_trips_on_fixture(code):
    trip, _ = FIXTURES[code]
    relpath = FIXTURE_PATHS.get(code, "<snippet>")
    got = {f.code for f in lint_source(trip, relpath, codes={code})}
    assert got == {code}


@pytest.mark.parametrize("code", sorted(FIXTURES))
def test_rule_passes_fixed_fixture(code):
    _, fixed = FIXTURES[code]
    relpath = FIXTURE_PATHS.get(code, "<snippet>")
    assert lint_source(fixed, relpath, codes={code}) == []


def test_hostsync_rule_is_path_scoped():
    # the R701 trip fixture is CLEAN outside the request-path modules, in
    # scope for every serving/ file and core/resilient.py, and satisfied
    # by a def-header annotation as well as a line-level one
    trip, _ = FIXTURES["R701"]
    assert lint_source(trip, "kernels/sti_pipeline.py",
                       codes={"R701"}) == []
    assert {f.code for f in lint_source(
        trip, "core/resilient.py", codes={"R701"})} == {"R701"}
    header = trip.replace(
        "def serve(batch):",
        "def serve(batch):  # sync-point: host staging by design")
    assert lint_source(header, "serving/engine.py", codes={"R701"}) == []


# R501 kernel-body extension: casts hoisted into locals inside a Pallas
# kernel body must still trip; preferred_element_type still passes.
_R501_KERNEL_TRIP = """
import functools
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

def _kern(x_ref, w_ref, o_ref, *, compute_dtype):
    xq = x_ref[...].astype(compute_dtype)
    w = w_ref[...].astype(compute_dtype)
    o_ref[...] = jax.lax.dot_general(xq, w, (((1,), (0,)), ((), ())))

def run(x, w):
    kernel = functools.partial(_kern, compute_dtype=jnp.bfloat16)
    return pl.pallas_call(
        kernel, out_shape=jax.ShapeDtypeStruct((8, 8), jnp.float32)
    )(x, w)
"""

_R501_KERNEL_PASS = _R501_KERNEL_TRIP.replace(
    "jax.lax.dot_general(xq, w, (((1,), (0,)), ((), ())))",
    "jax.lax.dot_general(xq, w, (((1,), (0,)), ((), ())),\n"
    "        preferred_element_type=jnp.float32)",
)


def test_r501_trips_on_hoisted_cast_in_kernel_body():
    got = lint_source(_R501_KERNEL_TRIP, codes={"R501"})
    assert {f.code for f in got} == {"R501"}
    assert len(got) == 1
    assert "kernel" in got[0].message


def test_r501_passes_kernel_body_with_preferred_element_type():
    assert lint_source(_R501_KERNEL_PASS, codes={"R501"}) == []


def test_r501_hoisted_cast_outside_kernel_body_stays_quiet():
    # the name-tracking pass is scoped to kernel bodies: ordinary functions
    # keep the literal-operand behaviour (no new false positives)
    plain = """
import jax.numpy as jnp
def mm(a, b):
    aq = a.astype(jnp.bfloat16)
    return jnp.dot(aq, b)
"""
    assert lint_source(plain, codes={"R501"}) == []


def test_all_rule_codes_have_fixtures():
    # ISSUE acceptance: >= 6 distinct rule codes, each with trip + pass
    from repro.analysis.rules import all_rules

    assert set(FIXTURES) == set(all_rules())
    assert len(FIXTURES) >= 6


def test_inline_suppression():
    trip, _ = FIXTURES["R601"]
    suppressed = trip.replace(
        "jnp.arange(128)", "jnp.arange(128)  # reprolint: disable=R601"
    )
    assert lint_source(suppressed) == []
    wrong_code = trip.replace(
        "jnp.arange(128)", "jnp.arange(128)  # reprolint: disable=R501"
    )
    assert {f.code for f in lint_source(wrong_code)} == {"R601"}
    disable_all = trip.replace(
        "jnp.arange(128)", "jnp.arange(128)  # reprolint: disable=all"
    )
    assert lint_source(disable_all) == []


def test_findings_carry_fixits_and_locations():
    for code, (trip, _) in FIXTURES.items():
        for f in lint_source(trip, codes={code}):
            assert f.line > 0
            assert f.message
            assert f.fixit, f"rule {code} has no fix-it message"
            assert f"{f.path}:{f.line}: {code}" in f.render()


# ------------------------------------------------------------- baseline
def test_fingerprint_survives_line_shift():
    trip, _ = FIXTURES["R501"]
    shifted = "# a new leading comment\n\n" + trip
    (a,) = lint_source(trip, codes={"R501"})
    (b,) = lint_source(shifted, codes={"R501"})
    assert a.line != b.line
    assert a.fingerprint == b.fingerprint


def test_baseline_roundtrip(tmp_path):
    trip, _ = FIXTURES["R501"]
    findings = lint_source(trip, codes={"R501"})
    path = tmp_path / "baseline.txt"
    write_baseline(findings, path)
    baseline = load_baseline(path)
    new, old = split_baselined(findings, baseline)
    assert new == [] and len(old) == 1
    # an edited offending line changes the fingerprint: baseline goes stale
    edited = lint_source(trip.replace('"ij,jk->ik"', '"ab,bc->ac"'),
                         codes={"R501"})
    new, old = split_baselined(edited, baseline)
    assert len(new) == 1 and old == []


def test_missing_baseline_is_empty(tmp_path):
    assert load_baseline(tmp_path / "absent.txt") == {}


def test_malformed_baseline_rejected(tmp_path):
    p = tmp_path / "bad.txt"
    p.write_text("R501 deadbeef extra-token\n")
    with pytest.raises(ValueError, match="malformed"):
        load_baseline(p)


# ---------------------------------------------------------- the real tree
def test_repo_tree_is_clean_under_baseline():
    new, _ = split_baselined(lint_tree(), load_baseline())
    assert new == [], "\n".join(f.render() for f in new)


def test_checked_in_baseline_entries_are_justified():
    baseline = load_baseline()
    assert baseline, "expected at least one intentional baselined finding"
    for fingerprint, justification in baseline.items():
        assert len(justification) > 20, (
            f"baseline entry {fingerprint} needs a real justification"
        )


# ------------------------------------------------------------- contracts
def test_contract_checker_clean_on_live_registries():
    from repro.analysis.contracts import check_contracts

    findings = check_contracts()
    assert findings == [], "\n".join(f.render() for f in findings)


def test_contract_checker_catches_bad_fill_entry():
    from repro.analysis.contracts import check_fill_registries
    from repro.core.sti_knn import _FILL_FNS

    _FILL_FNS["_broken"] = lambda g, ranks: jnp.zeros((3, 5), jnp.float16)
    try:
        got = {(f.code, f.path) for f in check_fill_registries()}
    finally:
        _FILL_FNS.pop("_broken")
    assert ("C101", "registry://fill/_broken") in got
    # both the shape and the dtype violation report independently
    assert sum(1 for c, p in got if p.endswith("_broken")) >= 1
    msgs = [f for f in check_fill_registries()]
    assert msgs == []  # registry restored


def test_contract_checker_catches_misshaped_kernel():
    from repro.analysis.contracts import check_step_contracts
    from repro.kernels.stream_kernels import (
        _KERNEL_FACTORIES,
        POINT_STATE,
        UpdateKernel,
        register_update_kernel,
    )

    def bad_factory(method, k, opts, fill, fill_static, axis):
        def contrib(d2, order, match, mask):
            return match * mask[:, None]

        def update(state, u, g, ranks, mask):
            # grows the state: (n,) in, (n, 2) out
            return (jnp.zeros((state[0].shape[0], 2), jnp.float32),)

        return UpdateKernel(method, POINT_STATE, False, None,
                            contrib, update)

    register_update_kernel("_broken_method", POINT_STATE, bad_factory)
    try:
        findings = check_step_contracts(n=16, d=4, k=3, tb=4)
    finally:
        _KERNEL_FACTORIES.pop("_broken_method")
    bad = [f for f in findings if "_broken_method" in f.path]
    assert bad and all(f.code == "C201" for f in bad)
    good = [f for f in findings if "_broken_method" not in f.path]
    assert good == []


def test_engine_table_cross_check():
    from repro.analysis.contracts import check_engine_table
    from repro.core.methods import ENGINES

    assert check_engine_table() == []
    ENGINES["_ghost"] = ("streamed",)
    try:
        got = check_engine_table()
    finally:
        ENGINES.pop("_ghost")
    assert [f.code for f in got] == ["C501"]
    assert "_ghost" in got[0].path


# ------------------------------------------------------------------- CLI
def test_cli_strict_clean_tree_exits_zero(capsys):
    from repro.launch.lint import main

    assert main(["--strict"]) == 0
    out = capsys.readouterr().out
    assert "0 actionable finding(s)" in out


def test_cli_strict_fails_on_new_finding(tmp_path, capsys):
    from repro.launch.lint import main

    bad = tmp_path / "mod.py"
    bad.write_text(FIXTURES["R601"][0])
    assert main(["--strict", "--no-contracts", "--root", str(tmp_path),
                 "--baseline", str(tmp_path / "empty.txt")]) == 1
    assert "R601" in capsys.readouterr().out


def test_cli_json_output(tmp_path, capsys):
    import json

    from repro.launch.lint import main

    (tmp_path / "mod.py").write_text(FIXTURES["R501"][0])
    assert main(["--json", "--no-contracts", "--root", str(tmp_path),
                 "--baseline", str(tmp_path / "empty.txt")]) == 0
    payload = json.loads(capsys.readouterr().out)
    assert [f["code"] for f in payload["new"]] == ["R501"]
    assert payload["new"][0]["fingerprint"]


def test_cli_update_baseline_roundtrip(tmp_path, capsys):
    from repro.launch.lint import main

    (tmp_path / "mod.py").write_text(FIXTURES["R601"][0])
    baseline = tmp_path / "baseline.txt"
    assert main(["--update-baseline", "--no-contracts",
                 "--root", str(tmp_path), "--baseline", str(baseline)]) == 0
    capsys.readouterr()
    assert main(["--strict", "--no-contracts", "--root", str(tmp_path),
                 "--baseline", str(baseline)]) == 0
    assert "1 baselined" in capsys.readouterr().out


def test_cli_exclusive_flags_rejected():
    from repro.launch.lint import main

    assert main(["--no-contracts", "--contracts-only"]) == 2
