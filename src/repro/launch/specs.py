"""Input specs (ShapeDtypeStruct stand-ins) and step functions for every
(arch x input-shape) dry-run cell, plus the paper's own STI-KNN workload.

Nothing here allocates device memory: params/optimizer/caches/batches are
abstract; `jax.jit(step).lower(**specs)` is the only consumer.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import compat
from repro.configs.base import ModelConfig, ShapeSpec
from repro.models import build_model
from repro.training.optimizer import AdamWConfig, adamw_init, adamw_update
from repro.distributed import sharding as SH

__all__ = ["lm_cell", "sti_cell"]


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def lm_batch_specs(cfg: ModelConfig, shape: ShapeSpec):
    """Abstract batch for one cell."""
    b, s = shape.global_batch, shape.seq_len
    if shape.kind == "decode":
        batch = {"tokens": _sds((b, 1), jnp.int32)}
    elif cfg.family == "vlm":
        batch = {"tokens": _sds((b, s - cfg.num_patches), jnp.int32)}
    else:
        batch = {"tokens": _sds((b, s), jnp.int32)}
    if cfg.family == "vlm" and shape.kind != "decode":
        batch["patch_embeds"] = _sds((b, cfg.num_patches, cfg.d_model), cfg.dtype)
    if cfg.family == "audio":
        batch["frames"] = _sds((b, cfg.encoder_seq, cfg.d_model), cfg.dtype)
        if shape.kind == "decode":
            batch.pop("frames")  # encoder k/v already live in the caches
    if shape.kind == "train":
        batch["labels"] = _sds(batch["tokens"].shape, jnp.int32)
    return batch


def lm_cell(cfg: ModelConfig, shape: ShapeSpec, mesh: Mesh,
            strategy: str | None = None, opt: AdamWConfig | None = None,
            grad_accum: int = 1, cache_seq_shard: bool = True):
    """Build (step_fn, arg_specs, in_shardings, out_shardings) for a cell.

    train  : step(params, opt_state, batch) -> (params, opt_state, metrics)
             grad_accum > 1 scans microbatches (activation-memory lever;
             FLOPs unchanged, grads accumulated in f32 before the update)
    prefill: step(params, batch) -> (last_logits, caches)
    decode : step(params, batch{tokens, caches, index}) -> (logits, caches)
    """
    # Inference kinds serve from bf16 weights replicated over data (TP only):
    # no per-step FSDP gathers, and params/16 chips fits every assigned arch.
    # Training keeps f32 master params, FSDP-stored for the big archs.
    if shape.kind != "train" and strategy is None:
        strategy = "tp_dp"
    strategy = strategy or SH.strategy_for(cfg)
    da = SH.data_axes(mesh)
    cfg = cfg.replace(
        fsdp_constrain=(strategy == "fsdp"),
        shmap_axes=(da, "model") if cfg.num_experts else ())
    model = build_model(cfg)
    rules = SH.rules_for(cfg, strategy, mesh)
    pspec = model.param_spec(rules)
    params = model.abstract(
        dtype=cfg.dtype if shape.kind != "train" else jnp.float32)
    bspec = SH.batch_spec(cfg, shape.kind, mesh)
    batch = lm_batch_specs(cfg, shape)
    bspec = {k: v for k, v in bspec.items() if k in batch}
    # long-context decode: global_batch (1) not divisible by the data axes
    # -> batch replicated; the KV seq dim carries the data sharding instead
    # (flash-decode across chips, see cache_pytree_spec).
    dp = int(np.prod([mesh.shape[a] for a in da]))
    if shape.global_batch % dp:
        bspec = {k: P(*(None,) * len(v)) for k, v in bspec.items()}
    opt = opt or AdamWConfig()

    if shape.kind == "train":
        def step(params, opt_state, batch):
            if grad_accum == 1:
                (loss, metrics), grads = jax.value_and_grad(
                    model.loss_fn, has_aux=True)(params, batch)
            else:
                def micro(carry, mb):
                    (l, m), g = jax.value_and_grad(
                        model.loss_fn, has_aux=True)(params, mb)
                    gs, ls = carry
                    return (jax.tree.map(jnp.add, gs, g), ls + l), None
                mbs = jax.tree.map(
                    lambda x: x.reshape(grad_accum, x.shape[0] // grad_accum,
                                        *x.shape[1:]), batch)
                zeros = jax.tree.map(
                    lambda p: jnp.zeros(p.shape, jnp.float32), params)
                (grads, loss), _ = jax.lax.scan(
                    micro, (zeros, jnp.zeros((), jnp.float32)), mbs)
                grads = jax.tree.map(lambda g: g / grad_accum, grads)
                loss = loss / grad_accum
                metrics = {}
            new_params, new_state, opt_m = adamw_update(
                opt, grads, opt_state, params)
            metrics = dict(metrics, loss=loss, **opt_m)
            return new_params, new_state, metrics

        opt_state = jax.eval_shape(adamw_init, params)
        opt_spec = jax.tree.map(lambda _: None, opt_state)
        opt_spec = type(opt_state)(mu=pspec, nu=pspec, count=P())
        args = (params, opt_state, batch)
        in_sh = (pspec, opt_spec, bspec)
        out_sh = (pspec, opt_spec, None)
        return step, args, in_sh, out_sh

    if shape.kind == "prefill":
        def step(params, batch):
            return model.prefill(params, batch)

        args = (params, batch)
        in_sh = (pspec, bspec)
        return step, args, in_sh, None

    # decode
    max_len = shape.seq_len
    caches = jax.eval_shape(
        functools.partial(model.init_caches, shape.global_batch, max_len))
    cspec = SH.cache_pytree_spec(cfg, caches, shape.kind, mesh,
                                 shape.seq_len,
                                 cache_seq_shard=cache_seq_shard)
    batch = dict(batch, caches=caches, index=_sds((), jnp.int32))
    bspec = dict(bspec, caches=cspec, index=P())

    def step(params, batch):
        return model.decode_step(params, batch)

    args = (params, batch)
    in_sh = (pspec, bspec)
    out_sh = (None, cspec)
    return step, args, in_sh, out_sh


# ---------------------------------------------------------------- STI-KNN
def sti_cell(scfg, mesh: Mesh, *, unroll: bool = False):
    """The paper's workload as a production cell (shard_map formulation).

    Device (d, m) processes its test shard and owns phi column block m:
      1. distances: local (tc, d) x replicated (n, d) GEMM
      2. per-test argsort -> ranks; g via reverse cumsum  (replicated in m)
      3. fill: phi_cols[a, jb] += g[max(rank[a], rank_cols[jb])]
      4. psum over (pod, data) -> every model shard holds the final block.
    Output: phi sharded P(None, 'model'); diag P(None).
    """
    from repro.core.sti_knn import ranks_from_order, superdiagonal_g

    n, d, k = scfg.n_train, scfg.feat_dim, scfg.k
    tc = scfg.test_chunk
    da = SH.data_axes(mesh)
    model_size = mesh.shape["model"]
    n_local = n // model_size
    dp = int(np.prod([mesh.shape[a] for a in da]))
    tc_local = tc // dp

    def local_step(x_train, y_train, x_test, y_test, col_ids):
        # x_test: (tc_local, d) local shard; col_ids: (n_local,) this
        # device's phi column ids.
        d2 = (
            jnp.sum(x_test * x_test, -1, keepdims=True)
            - 2.0 * jnp.matmul(x_test, x_train.T,
                               precision=jax.lax.Precision.HIGHEST)
            + jnp.sum(x_train * x_train, -1)[None, :]
        )
        order = jnp.argsort(d2, axis=-1, stable=True)
        ranks = ranks_from_order(order)
        u = (y_train[order] == y_test[:, None]).astype(jnp.float32) / k
        g = superdiagonal_g(u, k, mode=scfg.mode)
        r_cols = ranks[:, col_ids]  # (tc_local, n_local)

        def body(acc, io):
            g_p, r_p, rc_p = io
            m = jnp.maximum(r_p[:, None], rc_p[None, :])  # (n, n_local)
            return acc + g_p[m], None

        acc0 = jnp.zeros((n, n_local), jnp.float32)
        acc, _ = jax.lax.scan(body, acc0, (g, ranks, r_cols),
                              unroll=tc_local if unroll else 1)
        diag = jnp.sum(
            (y_train[None, :] == y_test[:, None]).astype(jnp.float32) / k, 0)
        acc = jax.lax.psum(acc, da)
        diag = jax.lax.psum(diag, da)
        return acc, diag

    specs_in = (
        P(None, None),        # x_train replicated
        P(None),              # y_train
        P(da, None),          # x_test sharded over data axes
        P(da),                # y_test
        P("model"),           # column ids
    )
    specs_out = (P(None, "model"), P(None))
    step = compat.shard_map(local_step, mesh=mesh, in_specs=specs_in,
                            out_specs=specs_out, check_vma=False)

    args = (
        _sds((n, d), jnp.float32),
        _sds((n,), jnp.int32),
        _sds((tc, d), jnp.float32),
        _sds((tc,), jnp.int32),
        _sds((n,), jnp.int32),
    )
    in_sh = specs_in
    out_sh = specs_out
    return step, args, in_sh, out_sh
