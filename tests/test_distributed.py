"""Multi-device tests: run in SUBPROCESSES with 8 placeholder CPU devices
(jax locks the device count at first init, so the main pytest process must
stay single-device for the smoke tests)."""

import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]


def run_py(code: str, devices: int = 8, timeout: int = 900):
    env = dict(os.environ,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               PYTHONPATH=str(REPO / "src"))
    p = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       env=env, capture_output=True, text=True,
                       timeout=timeout)
    assert p.returncode == 0, f"stdout:\n{p.stdout}\nstderr:\n{p.stderr}"
    return p.stdout


def test_distributed_sti_matches_reference():
    run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro import compat
        from repro.launch.mesh import make_auto_mesh
        from repro.configs.sti_knn_paper import STIConfig
        from repro.core import sti_knn_interactions
        from repro.data import make_moons
        from repro.launch.specs import sti_cell

        n, t, k = 128, 32, 5
        x, y = make_moons(n // 2, noise=0.08, seed=0)
        xt, yt = make_moons(t // 2, noise=0.08, seed=1)
        mesh = make_auto_mesh((4, 2), ("data", "model"))
        scfg = STIConfig(n_train=n, feat_dim=2, k=k, test_chunk=t)
        step, _, _, _ = sti_cell(scfg, mesh)
        with compat.set_mesh(mesh):
            acc, diag = jax.jit(step)(x, y, xt, yt,
                                      jnp.arange(n, dtype=jnp.int32))
        phi = jnp.fill_diagonal(acc / t, diag / t, inplace=False)
        ref = sti_knn_interactions(x, y, xt, yt, k)
        err = float(jnp.max(jnp.abs(phi - ref)))
        assert err < 1e-5, err
        print("ok", err)
    """)


def test_sharded_train_step_runs_and_matches_single_device():
    """A pjit'd train step on a (4, 2) mesh produces the same loss as the
    unsharded step (numerics identical up to f32 reduction order)."""
    run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro import compat
        from repro.launch.mesh import make_auto_mesh
        from repro.configs.base import ModelConfig
        from repro.launch.specs import lm_cell
        from repro.configs.base import ShapeSpec
        from repro.models import build_model
        from repro.training.optimizer import AdamWConfig, adamw_init

        cfg = ModelConfig(name="tiny", family="dense", num_layers=2,
                          d_model=32, num_heads=4, num_kv_heads=2,
                          head_dim=8, d_ff=64, vocab_size=128,
                          tp_pad_heads=2, vocab_pad=32, dtype=jnp.float32)
        shape = ShapeSpec("t", seq_len=16, global_batch=8, kind="train")
        mesh = make_auto_mesh((4, 2), ("data", "model"))
        step, args, in_sh, out_sh = lm_cell(cfg, shape, mesh,
                                            strategy="tp_dp")
        model = build_model(cfg)
        params = model.init(jax.random.key(0))
        opt_state = adamw_init(params)
        toks = jax.random.randint(jax.random.key(1), (8, 16), 0, 128)
        labels = jax.random.randint(jax.random.key(2), (8, 16), 0, 128)
        batch = {"tokens": toks, "labels": labels}
        to_named = lambda tree: jax.tree.map(
            lambda s: jax.sharding.NamedSharding(mesh, s) if s is not None else None,
            tree, is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec) or x is None)
        with compat.set_mesh(mesh):
            f = jax.jit(step, in_shardings=to_named(in_sh),
                        out_shardings=to_named(out_sh))
            p2, o2, metrics = f(params, opt_state, batch)
        loss_sharded = float(metrics["loss"])
        # single-device reference
        (loss_ref, _) = model.loss_fn(params, batch)
        assert abs(loss_sharded - float(loss_ref)) < 1e-3, (
            loss_sharded, float(loss_ref))
        # params actually updated
        d = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(a - b))),
                         p2, params)
        assert max(jax.tree.leaves(d)) > 0
        print("ok", loss_sharded)
    """)


def test_fsdp_constrain_equivalence():
    """FSDP storage + use-constraints computes the same loss as TP."""
    run_py("""
        import jax, jax.numpy as jnp
        from repro import compat
        from repro.launch.mesh import make_auto_mesh
        from repro.configs.base import ModelConfig, ShapeSpec
        from repro.launch.specs import lm_cell
        from repro.models import build_model
        from repro.training.optimizer import adamw_init

        cfg = ModelConfig(name="tiny", family="moe", num_layers=2,
                          d_model=32, num_heads=4, num_kv_heads=2,
                          head_dim=8, d_ff=64, vocab_size=128,
                          num_experts=4, capacity_factor=8.0,
                          moe_group_size=32,
                          tp_pad_heads=2, vocab_pad=32, dtype=jnp.float32)
        shape = ShapeSpec("t", seq_len=16, global_batch=8, kind="train")
        mesh = make_auto_mesh((4, 2), ("data", "model"))
        model = build_model(cfg)
        params = model.init(jax.random.key(0))
        toks = jax.random.randint(jax.random.key(1), (8, 16), 0, 128)
        labels = jax.random.randint(jax.random.key(2), (8, 16), 0, 128)
        batch = {"tokens": toks, "labels": labels}

        losses = {}
        for strat in ("tp_dp", "fsdp"):
            step, args, in_sh, out_sh = lm_cell(cfg, shape, mesh,
                                                strategy=strat)
            to_named = lambda tree: jax.tree.map(
                lambda s: jax.sharding.NamedSharding(mesh, s) if s is not None else None,
                tree, is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec) or x is None)
            opt_state = adamw_init(params)
            with compat.set_mesh(mesh):
                f = jax.jit(step, in_shardings=to_named(in_sh),
                            out_shardings=to_named(out_sh))
                _, _, metrics = f(params, opt_state, batch)
            losses[strat] = float(metrics["loss"])
        assert abs(losses["tp_dp"] - losses["fsdp"]) < 1e-3, losses
        print("ok", losses)
    """)


def test_dryrun_cell_on_local_mesh():
    """The dry-run machinery itself (two compiles + roofline parse) on a
    small mesh/arch -- guards the launch path without the 512-device grid."""
    run_py("""
        import jax, jax.numpy as jnp
        from repro import compat
        from repro.launch.mesh import make_auto_mesh
        from repro.configs.base import ModelConfig, ShapeSpec
        from repro.launch.specs import lm_cell
        from repro.launch.hlo_analysis import analyze_compiled, collective_bytes

        cfg = ModelConfig(name="tiny", family="dense", num_layers=4,
                          d_model=32, num_heads=4, num_kv_heads=2,
                          head_dim=8, d_ff=64, vocab_size=128,
                          tp_pad_heads=2, vocab_pad=32, dtype=jnp.float32,
                          scan_unroll=True)
        shape = ShapeSpec("t", seq_len=32, global_batch=8, kind="train")
        mesh = make_auto_mesh((4, 2), ("data", "model"))
        step, args, in_sh, out_sh = lm_cell(cfg, shape, mesh, strategy="tp_dp")
        to_named = lambda tree: jax.tree.map(
            lambda s: jax.sharding.NamedSharding(mesh, s) if s is not None else None,
            tree, is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec) or x is None)
        with compat.set_mesh(mesh):
            compiled = jax.jit(step, in_shardings=to_named(in_sh),
                               out_shardings=to_named(out_sh)).lower(*args).compile()
        terms = analyze_compiled(compiled, 8, 1e9)
        assert terms.flops_per_chip > 0
        assert terms.bottleneck in ("compute", "memory", "collective")
        coll = collective_bytes(compiled.as_text())
        assert coll["total"] >= 0
        print("ok", terms.bottleneck, coll["total"])
    """)


def test_elastic_restore_across_mesh_shapes(tmp_path):
    """Checkpoint written under a (4,2) mesh restores onto a (2,4) mesh
    (elastic re-mesh: same logical tree, new shardings)."""
    run_py(f"""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.checkpoint.checkpointer import Checkpointer
        from repro.launch.mesh import make_auto_mesh

        tree = {{'w': jnp.arange(64, dtype=jnp.float32).reshape(8, 8),
                 'b': jnp.ones((8,), jnp.float32)}}
        mesh_a = make_auto_mesh((4, 2), ("data", "model"))
        sh_a = {{'w': NamedSharding(mesh_a, P("data", "model")),
                 'b': NamedSharding(mesh_a, P("model"))}}
        placed = jax.device_put(tree, sh_a)
        ck = Checkpointer(r"{tmp_path}")
        ck.save(7, placed)

        mesh_b = make_auto_mesh((2, 4), ("data", "model"))
        sh_b = {{'w': NamedSharding(mesh_b, P("data", "model")),
                 'b': NamedSharding(mesh_b, P("model"))}}
        restored, step = ck.restore(tree, shardings=sh_b)
        assert step == 7
        np.testing.assert_array_equal(np.asarray(restored['w']),
                                      np.asarray(tree['w']))
        assert restored['w'].sharding.mesh.shape['model'] == 4
        print("ok elastic restore")
    """)
