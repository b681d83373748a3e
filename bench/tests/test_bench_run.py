"""The harness end to end on the CPU, at tiny sizes, in a checkout of its
own: a cell made only of new files, the reference against the session,
the control, the faults a cell can have, and the refusal without a chip."""

import json
import os
import shutil
import subprocess
import sys
import types
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
REPO = BENCH.parent

TINY = {
    "tiny-sti": {"method": "sti", "n": 128, "d": 16, "test_pool": 100,
                 "classes": 3,
                 "check": {"rows": 8, "limits": {"rows_gap": 1e-4,
                                                 "diag_gap": 1e-4}}},
    "tiny-knn": {"method": "knn_shapley", "n": 160, "d": 16,
                 "test_pool": 100, "classes": 3,
                 "check": {"snapshot_stride": 2,
                           "limits": {"values_gap": 1e-4}}},
}


def make_checkout(tmp: Path, shards: int = 1) -> Path:
    """A checkout with the benchmark, the program and a BENCHMARK.json of
    tiny cells whose configuration and traffic are new files only."""
    root = tmp / "checkout"
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    (root / "src").symlink_to(REPO / "src")
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    for name, cfg in TINY.items():
        cfg = dict(cfg, name=name, k=5, shards=shards, class_sep=0.5)
        (root / "bench" / "configs" / f"{name}.json").write_text(
            json.dumps(cfg))
        spec["configs"].append({"name": name, "source": "tiny", "file":
                                f"bench/configs/{name}.json", "reduced": [],
                                "why": "test"})
        spec["workloads"].append({"name": f"{name}.mini", "config": name,
                                  "traffic": "mini", "chips": shards,
                                  "why": "test"})
    (root / "bench" / "traffic" / "mini.json").write_text(json.dumps(
        {"name": "mini", "loop": "closed", "clients": 1, "batch": 16,
         "order": "cycle"}))
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    return make_checkout(tmp_path_factory.mktemp("bench"))


@pytest.fixture
def run(monkeypatch):
    import jax

    import run as harness

    # keep this test process's JAX configuration (no persistent cache)
    monkeypatch.setattr(harness, "_setup_jax", lambda root: jax)
    return harness


def _count_window_polls(run, monkeypatch) -> list:
    """Records each call of the check's `poll` made by the harness itself
    (the knn_shapley check also polls from `before_step`)."""
    calls = []
    method = run.catalog.Catalog.method

    def counting(self, name):
        base = method(self, name).Check

        class Check(base):
            def poll(self):
                if sys._getframe(1).f_code.co_name == "run_cell":
                    calls.append(1)
                super().poll()

        return types.SimpleNamespace(Check=Check)

    monkeypatch.setattr(run.catalog.Catalog, "method", counting)
    return calls


@pytest.mark.parametrize("cell", ["tiny-sti.mini", "tiny-knn.mini"])
def test_new_cell_runs_correct_on_cpu(checkout, run, monkeypatch, cell):
    import reference

    polls = _count_window_polls(run, monkeypatch)
    res = run.run_cell(checkout, cell, 2**33 + 11, 0.3, False,
                       require_tpu=False, controls=reference.CONTROLS)
    # the window polls the check once after each step it waits for
    assert len(polls) == res["attempted"]
    assert res["correct"] is True
    assert list(res)[-1] == "checks"
    # f32 rounding alone: a step's contribution sums 16 test points, so it
    # rounds at up to 16 f32 spacings (1.9e-6) of its entries
    assert all(c["value"] <= 1e-5 for c in res["checks"].values())
    assert set(res["metrics"]) == {"points_per_s", "peak_hbm_gib",
                                   "setup_s"}
    assert res["attempted"] >= 2 and res["failed"] == 0
    # each control (the reference at a lower precision) is not correct
    assert set(res["control"]) == set(reference.CONTROLS)
    for nums in res["control"].values():
        assert any(nums[k] > c["limit"] for k, c in res["checks"].items())
    assert set(res["parts"]) == {"program", *reference.CONTROLS}


def _broken(run, monkeypatch, fault):
    import jax.numpy as jnp

    build = run.build_session

    def broken_build(cfg, traffic, x, y):
        sess = build(cfg, traffic, x, y)
        step = sess._step
        tb = sess.test_batch

        def faulty(state, xb, yb, mask, *rest):
            if fault == "unchanged":
                return state
            if fault == "half_batch":
                mask = mask * (jnp.arange(tb) < tb // 2)
                return step(state, xb, yb, mask, *rest)
            out = step(state, xb, yb, mask, *rest)
            return tuple(a * (1 + 1e-3) for a in out)  # altered answer

        sess._step = faulty
        return sess

    monkeypatch.setattr(run, "build_session", broken_build)


@pytest.mark.parametrize("fault", ["unchanged", "half_batch", "altered"])
@pytest.mark.parametrize("cell", ["tiny-sti.mini", "tiny-knn.mini"])
def test_faults_come_out_not_correct(checkout, run, monkeypatch, cell,
                                     fault):
    _broken(run, monkeypatch, fault)
    res = run.run_cell(checkout, cell, 5, 0.2, False, require_tpu=False)
    assert res["correct"] is False


def test_a_compile_inside_the_window_fails_the_run(checkout, run,
                                                   monkeypatch):
    import jax

    build = run.build_session

    def recompiling_build(cfg, traffic, x, y):
        sess = build(cfg, traffic, x, y)
        step = sess._step

        def fresh(state, *args):
            # a new function each call: compiled again every step
            return jax.jit(lambda s: tuple(a + 0 for a in s))(
                step(state, *args))

        sess._step = fresh
        return sess

    monkeypatch.setattr(run, "build_session", recompiling_build)
    with pytest.raises(run.WindowCompiled):
        run.run_cell(checkout, "tiny-knn.mini", 5, 0.2, False,
                     require_tpu=False)


SHARDED_SCRIPT = """
import sys, json
sys.path[:0] = [{bench!r}, {src!r}]
import jax
import run as harness
harness._setup_jax = lambda root: jax
if {drop!r}:
    import jax.numpy as jnp
    from jax import lax
    def no_gather(x, axis_name, *, axis=0, tiled=False, **kw):
        d = lax.axis_size(axis_name)
        return jnp.concatenate([x] * d, axis=axis) if tiled else jnp.stack(
            [x] * d, axis)
    def no_scatter(x, axis_name, *, scatter_dimension=0, tiled=False, **kw):
        nl = x.shape[0] // lax.axis_size(axis_name)
        return lax.dynamic_slice_in_dim(x, lax.axis_index(axis_name) * nl, nl)
    lax.all_gather = no_gather
    lax.psum_scatter = no_scatter
res = harness.run_cell({root!r}, "tiny-sti.mini", 7, 0.3, False,
                       require_tpu=False)
print(json.dumps(res))
"""


@pytest.mark.parametrize("drop", [False, True])
def test_sharded_cell_and_missing_exchange(tmp_path, drop):
    root = make_checkout(tmp_path, shards=4)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = SHARDED_SCRIPT.format(bench=str(BENCH), src=str(REPO / "src"),
                         root=str(root), drop=drop)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["device"]["count"] == 4
    assert res["correct"] is (not drop)


def test_run_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload",
         "knn-cifar10.audit", "--seed", "1", "--seconds", "1", "--trace",
         "0"], env=env, capture_output=True, text=True, timeout=300,
        cwd=str(REPO))
    assert out.returncode != 0
    assert out.stdout.strip() == "" or not out.stdout.strip().startswith("{")
    assert "TPU" in out.stderr
