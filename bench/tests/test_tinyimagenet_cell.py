"""The `sti-tinyimagenet.audit` cell on the CPU, over 4 forced host devices.

The cell's own configuration file, with only its scale shrunk (n, the test
pool and the batch; classes 200, k 5, d 768 and 4 shards are kept), runs
through `run.run_cell` in a checkout of its own, in a subprocess that sees
four devices. It must come out correct, and not correct when the sharded
step's exchange (the all-gather of g and ranks, the reduce-scatter of the
diagonal) is replaced by a local copy."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
REPO = BENCH.parent
CELL = "sti-tinyimagenet.audit"
SHRUNK = {"n": 800, "test_pool": 64}
BATCH = 16


def make_checkout(tmp: Path) -> Path:
    """A checkout whose `sti-tinyimagenet` configuration and `audit`
    traffic are the repository's, shrunk to `SHRUNK` and `BATCH`."""
    root = tmp / "checkout"
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    (root / "src").symlink_to(REPO / "src")
    shutil.copy(REPO / "BENCHMARK.json", root / "BENCHMARK.json")
    cfg_path = root / "bench" / "configs" / "sti-tinyimagenet.json"
    cfg = json.loads(cfg_path.read_text())
    assert (cfg["classes"], cfg["k"], cfg["d"], cfg["shards"]) == (
        200, 5, 768, 4)
    cfg_path.write_text(json.dumps(dict(cfg, **SHRUNK)))
    mix_path = root / "bench" / "traffic" / "audit.json"
    mix = json.loads(mix_path.read_text())
    mix_path.write_text(json.dumps(dict(mix, batch=BATCH)))
    return root


SCRIPT = """
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
res = harness.run_cell({root!r}, {cell!r}, 2**33 + 3, 0.3, False,
                       require_tpu=False)
print(json.dumps(res))
"""


@pytest.mark.parametrize("drop", [False, True])
def test_shrunk_cell_runs_on_four_devices(tmp_path, drop):
    root = make_checkout(tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = SCRIPT.format(bench=str(BENCH), src=str(REPO / "src"),
                         root=str(root), cell=CELL, drop=drop)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    lines = out.stdout.strip().splitlines()
    assert json.loads(lines[0])["resolved"]["shards"] == 4
    res = json.loads(lines[-1])
    assert res["device"]["count"] == 4
    assert res["attempted"] >= 2
    assert res["correct"] is (not drop), res["checks"]
    assert set(res["checks"]) == {"rows_gap", "diag_gap"}
