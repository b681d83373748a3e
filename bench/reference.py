"""Plain references of the valuation methods, written from the papers.

Nothing here imports the program or takes anything it made: distances,
ranks, the recurrences and the sums are computed again from the train set
and the test batches the benchmark generated. Each function runs in blocks
(one test batch, a few rows) so that it fits beside nothing else on a chip.

Precision (`prec`). The configurations state float32 throughout, with the
distance contraction at `Precision.HIGHEST` (f32-exact products): "f32".
The controls, each put in the program's place by `control.py`:
  "lower"          every stage one step down, as a tempting change would
                   make it: the contraction as three bf16 passes (what
                   `Precision.HIGH` does on a TPU, written out so that a CPU
                   computes the same), the recurrence and the sums in bf16;
  "bf16_distance"  the contraction alone in one bf16 pass (what
                   `Precision.DEFAULT` does on a TPU), everything else f32.

    sti  (Belaid et al., arXiv:2304.01224, Eqs. 4-8):
        u[j]    = 1[y(alpha_j) == y_test] / k, j = 0 the nearest
        g[n-1]  = -2 (n-k) / (n (n-1)) u[n-1]
        g[j-1]  = g[j] + 1[j > k] 2 (j-k) / ((j-1) j) (u[j] - u[j-1])
        phi_ab  = sum_p g_p[max(r_p(a), r_p(b))]   (a != b)
        phi_aa  = sum_p u_p(a)
    knn_shapley (Jia et al., arXiv:1908.08619, Thm. 1), 1-based i:
        s[N]    = m[N] / N * min(k, N) / k
        s[i]    = s[i+1] + (m[i] - m[i+1]) / k * min(k, i) / i
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32
BF16 = jnp.bfloat16
PRECS = ("f32", "lower", "bf16_distance")
CONTROLS = PRECS[1:]


def _check_prec(prec: str) -> None:
    if prec not in PRECS:
        raise ValueError(f"unknown precision {prec!r}; one of {PRECS}")


def _sum_dtype(prec: str):
    """The dtype of the recurrence and the sums."""
    return BF16 if prec == "lower" else F32


def _split_bf16(a):
    hi = a.astype(BF16)
    return hi, (a - hi.astype(F32)).astype(BF16)


def cross(a, b, prec: str):
    """(t, d) x (n, d) -> (t, n) inner products."""
    _check_prec(prec)
    if prec == "f32":
        return jnp.matmul(a, b.T, precision=jax.lax.Precision.HIGHEST)
    (a1, a2), (b1, b2) = _split_bf16(a), _split_bf16(b)
    dot = functools.partial(jnp.matmul, preferred_element_type=F32)
    if prec == "bf16_distance":
        return dot(a1, b1.T)
    return dot(a1, b1.T) + dot(a1, b2.T) + dot(a2, b1.T)


def sq_dists(xb, x, prec: str):
    """Squared L2 distances by ||a||^2 - 2 a.b + ||b||^2, clipped at 0."""
    d2 = (jnp.sum(xb * xb, -1)[:, None] - 2.0 * cross(xb, x, prec)
          + jnp.sum(x * x, -1)[None, :])
    return jnp.maximum(d2, 0.0)


def order_and_ranks(d2):
    """Stable nearest-first order (tb, n) and its inverse, the ranks."""
    order = jnp.argsort(d2, axis=-1, stable=True)
    t, n = order.shape
    ranks = jnp.zeros_like(order).at[jnp.arange(t)[:, None], order].set(
        jnp.broadcast_to(jnp.arange(n, dtype=order.dtype), (t, n)))
    return order, ranks


def _rev_cumsum(a):
    return jnp.flip(jnp.cumsum(jnp.flip(a, -1), -1), -1)


def sti_g(u, k: int):
    """(tb, n) sorted u -> (tb, n) g with g[:, 0] = 0 (Eqs. 6-7)."""
    n = u.shape[-1]
    dt = u.dtype
    if n <= k:
        return jnp.zeros_like(u)
    j = jnp.arange(n, dtype=F32)
    on = (j > k) & (j >= 2)
    coef = jnp.where(on, 2.0 * (j - k) / jnp.where(on, (j - 1.0) * j, 1.0),
                     0.0).astype(dt)
    du = jnp.concatenate([jnp.zeros_like(u[:, :1]), u[:, 1:] - u[:, :-1]], -1)
    term = coef * du
    after = jnp.concatenate([_rev_cumsum(term)[:, 1:],
                             jnp.zeros_like(term[:, :1])], -1)
    last = jnp.asarray(-2.0 * (n - k) / (n * (n - 1.0)), dt)
    g = last * u[:, -1:] + after
    return g.at[:, 0].set(0)


@functools.partial(jax.jit, static_argnames=("k", "prec"))
def sti_rows_batch(acc_rows, diag, xb, yb, x, y, rows, *, k: int,
                   prec: str = "f32"):
    """Fold one test batch into the reference rows `acc_rows` (R, n) and
    the diagonal `diag` (n,): the literal max-gather g[max(r_a, r_b)] for
    the R train points `rows` against every train point."""
    dt = _sum_dtype(prec)
    order, ranks = order_and_ranks(sq_dists(xb, x, prec))
    u = ((y[order] == yb[:, None]) / k).astype(dt)
    g = sti_g(u, k)
    tb, n = g.shape

    def one(acc, p):
        r = ranks[p]
        block = g[p][jnp.maximum(r[rows][:, None], r[None, :])]
        return acc + block, None

    acc_rows, _ = jax.lax.scan(one, acc_rows.astype(dt), jnp.arange(tb))
    match = ((y[None, :] == yb[:, None]) / k).astype(dt)
    return acc_rows, diag.astype(dt) + jnp.sum(match, axis=0)


def knn_shapley_sorted(m, k: int):
    """(tb, n) sorted 0/1 match -> (tb, n) Shapley values, sorted order."""
    n = m.shape[-1]
    dt = m.dtype
    i = jnp.arange(1, n + 1, dtype=F32)
    last = m[:, -1:] * (min(k, n) / (k * n))
    step = ((m[:, :-1] - m[:, 1:]) / k
            * (jnp.minimum(float(k), i[:-1]) / i[:-1]).astype(dt))
    return jnp.concatenate([last + _rev_cumsum(step), last], -1)


@functools.partial(jax.jit, static_argnames=("k", "prec"))
def knn_shapley_batch(xb, yb, x, y, *, k: int, prec: str = "f32"):
    """(n,) sum over one test batch of the KNN-Shapley values."""
    dt = _sum_dtype(prec)
    order, _ = order_and_ranks(sq_dists(xb, x, prec))
    s = knn_shapley_sorted((y[order] == yb[:, None]).astype(dt), k)
    return jnp.zeros(x.shape[0], dt).at[order.reshape(-1)].add(s.reshape(-1))


def rel_gap(got, want, scale=None) -> float:
    """max |got - want| / scale, in float64 on the host; the scale is
    max |want| unless given."""
    import numpy as np

    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if scale is None:
        scale = float(np.max(np.abs(want)))
    if scale == 0.0:
        return float("inf") if np.any(got != 0) else 0.0
    return float(np.max(np.abs(got - want))) / scale
