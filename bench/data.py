"""Inputs of a run, made on the device from `--seed`.

Train set and test pool are a class-conditional Gaussian mixture: class c
has a mean drawn once per run, `mu_c ~ N(0, sep^2 I_d)`, and each point is
`mu_y + N(0, I_d)` with its label `y` uniform over the classes. Every seed
gives the same sizes; only the values differ. Everything is made by one
jitted call per array set, in float32, on the device that runs the cell.

The test pool is cut into `ceil(pool / tb)` batches of exactly `tb` points
(the last one wraps round to the start of the pool), so the session is
driven with full batches only and a cycle visits every pool point.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def seed_key(seed: int):
    """A PRNG key from a whole number of any size: the low 32 bits seed the
    key and the high 32 are folded in (`jax.random.key` alone drops them)."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


@functools.partial(jax.jit, static_argnames=("n", "pool", "d", "classes",
                                             "sep", "tb"))
def _mixture(key, *, n: int, pool: int, d: int, classes: int, sep: float,
             tb: int):
    km, ky, kx, kt, kyt = jax.random.split(key, 5)
    mu = sep * jax.random.normal(km, (classes, d), jnp.float32)
    y = jax.random.randint(ky, (n,), 0, classes, jnp.int32)
    x = mu[y] + jax.random.normal(kx, (n, d), jnp.float32)
    yt = jax.random.randint(kyt, (pool,), 0, classes, jnp.int32)
    xt = mu[yt] + jax.random.normal(kt, (pool, d), jnp.float32)
    nb = -(-pool // tb)
    idx = (jnp.arange(nb * tb) % pool).reshape(nb, tb)
    xb = tuple(xt[idx[b]] for b in range(nb))
    yb = tuple(yt[idx[b]] for b in range(nb))
    return x, y, xb, yb


def mixture(seed: int, *, n: int, pool: int, d: int, classes: int,
            sep: float, tb: int):
    """`(x, y, x_batches, y_batches)`: the (n, d) train set and its labels,
    and the test pool as a tuple of (tb, d) / (tb,) batches."""
    return _mixture(seed_key(seed), n=int(n), pool=int(pool), d=int(d),
                    classes=int(classes), sep=float(sep), tb=int(tb))
