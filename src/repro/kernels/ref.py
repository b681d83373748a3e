"""Pure-jnp oracles for every Pallas kernel in this package.

Each kernel's tests sweep shapes/dtypes and assert_allclose against these.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["sti_fill_ref", "distance_ref", "flash_attention_ref"]


def sti_fill_ref(g: jnp.ndarray, ranks: jnp.ndarray) -> jnp.ndarray:
    """Sum over test points p of g[p, max(ranks[p, a], ranks[p, b])].

    Args:
      g: (t, n) f32 super-diagonal tables.
      ranks: (t, n) int32 per-test train-point ranks (a permutation row-wise).

    Returns:
      (n, n) f32.
    """

    def one(g_p, r_p):
        return g_p[jnp.maximum(r_p[:, None], r_p[None, :])]

    return jnp.sum(jax.vmap(one)(g, ranks), axis=0).astype(jnp.float32)


def distance_ref(x_test: jnp.ndarray, x_train: jnp.ndarray) -> jnp.ndarray:
    """(t, d), (n, d) -> (t, n) squared L2 distances, f32 accumulation."""
    xt = x_test.astype(jnp.float32)
    xn = x_train.astype(jnp.float32)
    d2 = (
        jnp.sum(xt * xt, -1, keepdims=True)
        - 2.0 * jnp.matmul(xt, xn.T, precision=jax.lax.Precision.HIGHEST)
        + jnp.sum(xn * xn, -1)[None, :]
    )
    return jnp.maximum(d2, 0.0)


def flash_attention_ref(q, k, v, *, causal: bool = True, window: int | None = None):
    """(b, h, s, d) attention oracle with optional sliding window."""
    s = q.shape[-2]
    scale = 1.0 / jnp.sqrt(q.shape[-1]).astype(jnp.float32)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32)) * scale
    qi = jnp.arange(s)[:, None]
    ki = jnp.arange(k.shape[-2])[None, :]
    mask = jnp.ones((s, k.shape[-2]), bool)
    if causal:
        mask &= ki <= qi
    if window is not None:
        mask &= ki > qi - window
    logits = jnp.where(mask, logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32)).astype(q.dtype)
