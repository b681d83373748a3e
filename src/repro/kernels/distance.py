"""Pallas TPU kernel: tiled pairwise squared-L2 distances on the MXU.

||a - b||^2 = ||a||^2 - 2 a.b + ||b||^2 : the cross term is a GEMM tiled
(TB, NB, DB) with f32 accumulation in VMEM; row/column squared norms are
precomputed by the wrapper (O(t d + n d)) and fused into the epilogue on the
last reduction step.

Every distance contraction here runs at `lax.Precision.HIGHEST`: the
default TPU precision rounds f32 operands to bf16, which moves near-tied
neighbours and breaks the exact ranks every valuation method depends on.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["distance_pallas", "candidate_sq_dists"]


def candidate_sq_dists(
    x_test: jnp.ndarray,
    x_train: jnp.ndarray,
    cand: jnp.ndarray,
    *,
    train_norms: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """(tb, d) test rows, (n, d) train set, (tb, P) candidate ids ->
    (tb, P) exact squared L2 distances to the candidates only.

    The sparse counterpart of the dense (t, n) distance row: same
    expansion ||a-b||^2 = ||a||^2 - 2 a.b + ||b||^2, but the cross term is
    a gathered row-wise contraction costing O(tb P d) instead of
    O(tb n d). `train_norms` (n,) may be precomputed once per train set
    (the LSH index caches it); otherwise norms are taken over the gathered
    rows. Used by `repro.kernels.ann.topm_candidates` -- the candidate
    stage of `engine="approx"` (DESIGN.md Sec. 16).
    """
    xt = x_test.astype(jnp.float32)
    rows = x_train.astype(jnp.float32)[cand]           # (tb, P, d)
    cross = jnp.einsum("td,tpd->tp", xt, rows,
                       precision=jax.lax.Precision.HIGHEST)
    nt = jnp.sum(xt * xt, axis=-1, keepdims=True)      # (tb, 1)
    if train_norms is not None:
        nn = train_norms.astype(jnp.float32)[cand]     # (tb, P)
    else:
        nn = jnp.sum(rows * rows, axis=-1)
    return jnp.maximum(nt - 2.0 * cross + nn, 0.0)


def _kernel(xt_ref, xn_ref, nt_ref, nn_ref, out_ref, *, n_dblocks):
    """Accumulates the cross-term GEMM directly in the f32 output tile
    (revisiting grid: the tile stays VMEM-resident across the d reduction),
    fusing the norm epilogue on the last step -- no scratch needed."""

    @pl.when(pl.program_id(2) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    xt = xt_ref[...]  # (TB, DB)
    xn = xn_ref[...]  # (NB, DB)
    out_ref[...] += jax.lax.dot_general(
        xt, xn, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )

    @pl.when(pl.program_id(2) == n_dblocks - 1)
    def _epilogue():
        # norms arrive as 2-D (TB, 1) / (1, NB) blocks: a 1-D block's XLA
        # tiling does not match Mosaic's, which the TPU compiler refuses
        d2 = nt_ref[...] - 2.0 * out_ref[...] + nn_ref[...]
        out_ref[...] = jnp.maximum(d2, 0.0)


@functools.partial(
    jax.jit, static_argnames=("block_t", "block_n", "block_d", "interpret")
)
def distance_pallas(
    x_test: jnp.ndarray,
    x_train: jnp.ndarray,
    *,
    block_t: int = 128,
    block_n: int = 128,
    block_d: int = 512,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """(t, d), (n, d) -> (t, n) squared L2 distances (f32)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    t, d = x_test.shape
    n, _ = x_train.shape
    bt, bn, bd = min(block_t, t), min(block_n, n), min(block_d, d)
    tp, np_, dp = (-t) % bt, (-n) % bn, (-d) % bd
    xt = jnp.pad(x_test, ((0, tp), (0, dp)))
    xn = jnp.pad(x_train, ((0, np_), (0, dp)))
    nt = jnp.sum(xt.astype(jnp.float32) ** 2, -1)[:, None]   # (T, 1)
    nn = jnp.sum(xn.astype(jnp.float32) ** 2, -1)[None, :]   # (1, N)
    T, D = xt.shape
    N, _ = xn.shape
    n_dblocks = D // bd
    grid = (T // bt, N // bn, n_dblocks)
    out = pl.pallas_call(
        functools.partial(_kernel, n_dblocks=n_dblocks),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bt, bd), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bn, bd), lambda i, j, kk: (j, kk)),
            pl.BlockSpec((bt, 1), lambda i, j, kk: (i, 0)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((bt, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((T, N), jnp.float32),
        interpret=interpret,
    )(xt, xn, nt, nn)
    return out[:t, :n]
