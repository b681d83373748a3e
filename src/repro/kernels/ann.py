"""Random-projection LSH: deterministic top-m candidate preselection.

The approximate valuation engine (`engine="approx"`, DESIGN.md Sec. 16)
replaces the O(n) distance row of the streamed pipeline with a candidate
stage: each test point is compared against only the m training points an
LSH index proposes, so the per-test cost falls from O(n d) to
O(L log n + L W d) with L tables and window W -- the Jia et al.
(arXiv 1908.08619) recipe for KNN-Shapley on "data sets containing
millions of data points".

Index layout (`LSHTables`, a pytree so it passes straight through jit):

  * `proj` (L, b, d): random Gaussian projections drawn from an EXPLICIT
    PRNG key -- `engine="approx"` is bit-reproducible given `seed=`, and a
    checkpointed session rebuilds identical tables on restore;
  * sign-bit codes: code(x) = sum_j 1[proj_j . x >= 0] << j, one int32 per
    (table, point);
  * `sorted_codes` / `sort_idx` (L, n): each table's train codes sorted
    with the argsort that produced them, so a query is one
    `searchsorted` (binary search, O(log n)) plus a contiguous window of
    W neighbours in code space.

A query pools the L windows (L*W ids, duplicates included), computes EXACT
squared distances on the pool only (`repro.kernels.distance.
candidate_sq_dists`), masks duplicate ids to an infinite-distance
sentinel, and takes the m nearest by `lax.top_k` -- so the candidate list
is exactly sorted by true distance and the downstream recurrences see the
same sorted-coordinate contract as the dense pipeline, just truncated.

The index also carries the train-set moments (`train_norms`, `train_mean`,
`mean_sq_norm`) that let the wknn rbf bandwidth -- a FULL-row mean of d2
-- be computed analytically in O(d) per test point without materializing
any of the n distances (`full_mean_sq_dist`).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.kernels.distance import candidate_sq_dists

__all__ = [
    "LSHTables",
    "build_tables",
    "lsh_codes",
    "candidate_pool",
    "topm_candidates",
    "matched_prefix_and_recall",
    "full_mean_sq_dist",
    "INVALID_D2",
]

# Squared-distance sentinel for duplicate / out-of-pool candidate slots:
# far above any real squared distance (and above the soft-delete sentinel
# distances ~1e30 would overflow; see stream_kernels.SENTINEL_D2 for the
# related train-slot convention) yet finite in f32.
INVALID_D2 = 1e30
# Anything at or above this is an invalid candidate slot.
_VALID_CUTOFF = 1e29


class LSHTables(NamedTuple):
    """Immutable LSH index over one training set (a jit-transparent pytree).

    Fields: `proj` (L, b, d) f32 projections; `sorted_codes` (L, n) int32
    per-table sign-bit codes in ascending order; `sort_idx` (L, n) int32
    train ids aligned with `sorted_codes`; `train_norms` (n,) f32 squared
    row norms (distance epilogue); `train_mean` (d,) f32 and
    `mean_sq_norm` () f32 train moments (analytic rbf bandwidth).
    """

    proj: jnp.ndarray
    sorted_codes: jnp.ndarray
    sort_idx: jnp.ndarray
    train_norms: jnp.ndarray
    train_mean: jnp.ndarray
    mean_sq_norm: jnp.ndarray


def lsh_codes(proj: jnp.ndarray, x: jnp.ndarray) -> jnp.ndarray:
    """(L, b, d) projections, (p, d) points -> (L, p) int32 sign-bit codes.

    code[l, i] packs the b sign bits of proj[l] . x[i]; b <= 30 keeps the
    code positive in int32 so `searchsorted` order matches unsigned order.
    """
    bits = (
        jnp.einsum(
            "lbd,pd->lpb",
            proj.astype(jnp.float32),
            x.astype(jnp.float32),
        )
        >= 0.0
    )
    weights = (1 << jnp.arange(proj.shape[1], dtype=jnp.int32))[None, None, :]
    return jnp.sum(bits.astype(jnp.int32) * weights, axis=-1)


@functools.partial(jax.jit, static_argnames=("n_tables", "n_bits"))
def build_tables(
    x_train: jnp.ndarray,
    *,
    key: jax.Array,
    n_tables: int = 4,
    n_bits: int = 16,
) -> LSHTables:
    """Build the LSH index for an (n, d) training set.

    `key` is an explicit PRNG key: the same (x_train, key, n_tables,
    n_bits) always yields bit-identical tables, which is what makes
    `engine="approx"` reproducible given `seed=` and lets a restored
    session rebuild the exact index its checkpoint was written under.
    """
    if not 1 <= n_bits <= 30:
        raise ValueError(f"n_bits must be in [1, 30], got {n_bits}")
    if n_tables < 1:
        raise ValueError(f"n_tables must be >= 1, got {n_tables}")
    x = jnp.asarray(x_train, jnp.float32)
    n, d = x.shape
    proj = jax.random.normal(key, (n_tables, n_bits, d), jnp.float32)
    codes = lsh_codes(proj, x)                        # (L, n)
    sort_idx = jnp.argsort(codes, axis=-1, stable=True).astype(jnp.int32)
    sorted_codes = jnp.take_along_axis(codes, sort_idx, axis=-1)
    norms = jnp.sum(x * x, axis=-1)
    return LSHTables(
        proj=proj,
        sorted_codes=sorted_codes,
        sort_idx=sort_idx,
        train_norms=norms,
        train_mean=jnp.mean(x, axis=0),
        mean_sq_norm=jnp.mean(norms),
    )


def candidate_pool(
    tables: LSHTables, xb: jnp.ndarray, window: int
) -> jnp.ndarray:
    """(tb, d) test batch -> (tb, L*window) int32 candidate ids (with
    duplicates): per table, binary-search the query code into the sorted
    code list and take the `window` train ids around it."""
    n = tables.sort_idx.shape[-1]
    w = max(1, min(int(window), n))
    qcodes = lsh_codes(tables.proj, xb)               # (L, tb)
    pos = jax.vmap(jnp.searchsorted)(tables.sorted_codes, qcodes)  # (L, tb)
    start = jnp.clip(pos - w // 2, 0, n - w)
    cols = start[:, :, None] + jnp.arange(w, dtype=start.dtype)[None, None, :]
    ids = jnp.take_along_axis(
        tables.sort_idx[:, None, :], cols, axis=-1
    )                                                  # (L, tb, w)
    return jnp.transpose(ids, (1, 0, 2)).reshape(xb.shape[0], -1)


def _dedup_mask(pool: jnp.ndarray) -> jnp.ndarray:
    """(tb, P) candidate ids -> (tb, P) f32 mask with exactly one 1.0 per
    distinct id per row (the first occurrence in id-sorted order)."""
    order = jnp.argsort(pool, axis=-1, stable=True)
    sorted_ids = jnp.take_along_axis(pool, order, axis=-1)
    first = jnp.concatenate(
        [
            jnp.ones_like(sorted_ids[:, :1], jnp.bool_),
            sorted_ids[:, 1:] != sorted_ids[:, :-1],
        ],
        axis=-1,
    )
    keep = jnp.zeros_like(first)
    rows = jnp.arange(pool.shape[0])[:, None]
    return keep.at[rows, order].set(first).astype(jnp.float32)


def topm_candidates(
    xb: jnp.ndarray,
    x_train: jnp.ndarray,
    tables: LSHTables,
    m: int,
    window: int,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """The candidate stage of the approx pipeline: (tb, d) test batch ->
    `(cand, d2m, valid)`, each (tb, m):

      * `cand` int32 train ids of the m nearest pooled candidates, sorted
        ascending by EXACT squared distance (ties broken by pool position,
        deterministically);
      * `d2m` f32 their exact squared distances (`INVALID_D2` on invalid
        slots);
      * `valid` f32 1.0 where the slot holds a real distinct candidate
        (the pool can carry fewer than m distinct ids).

    Exact distances are computed only on the L*window pool; duplicates are
    masked to `INVALID_D2` so every distinct id appears at most once.
    """
    pool = candidate_pool(tables, xb, window)          # (tb, P)
    if pool.shape[-1] < m:
        raise ValueError(
            f"candidate pool {pool.shape[-1]} (= n_tables * window) is "
            f"smaller than top_m={m}; raise window or n_tables"
        )
    d2 = candidate_sq_dists(xb, x_train, pool, train_norms=tables.train_norms)
    keep = _dedup_mask(pool)
    d2 = jnp.where(keep > 0, d2, jnp.float32(INVALID_D2))
    neg, idx = jax.lax.top_k(-d2, m)                   # ascending d2
    cand = jnp.take_along_axis(pool, idx, axis=-1)
    d2m = -neg
    valid = (d2m < _VALID_CUTOFF).astype(jnp.float32)
    return cand, d2m, valid


def matched_prefix_and_recall(
    cand: jnp.ndarray,
    xb: jnp.ndarray,
    x_train: jnp.ndarray,
    kk: int,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Recall probe: compare candidates against the EXACT top-kk neighbours.

    Args:
      cand: (s, m) candidate ids (ascending by true distance).
      xb: (s, d) the probed test points (an O(s n d) exact distance row is
        computed for them -- keep s small in production).
      kk: probe depth, <= m.

    Returns:
      `(prefix, recall)`, each (s,): `prefix` int32 length of the leading
      run where candidate ids equal the exact nearest-neighbour ids
      (capped at kk) -- because candidates are sorted by exact distance, a
      full prefix certifies positions 1..kk exactly, which is what the
      certified error bounds of `repro.core.approx` consume; `recall` f32
      fraction of the exact top-kk present anywhere in the candidate set.
    """
    from repro.core.sti_knn import pairwise_sq_dists

    d2 = pairwise_sq_dists(xb, x_train)                # (s, n)
    _, true_ids = jax.lax.top_k(-d2, kk)               # (s, kk) ascending d2
    head = cand[:, :kk]
    prefix = jnp.sum(
        jnp.cumprod((head == true_ids).astype(jnp.int32), axis=-1), axis=-1
    )
    hit = jnp.any(true_ids[:, :, None] == cand[:, None, :], axis=-1)
    return prefix.astype(jnp.int32), jnp.mean(
        hit.astype(jnp.float32), axis=-1
    )


def full_mean_sq_dist(xb: jnp.ndarray, tables: LSHTables) -> jnp.ndarray:
    """(tb, d) test batch -> (tb, 1) EXACT mean over all n train points of
    the squared distance, in O(d) per test point:

        mean_j ||x - x_j||^2 = ||x||^2 - 2 x . mean(x_train) + mean||x_j||^2

    This is the wknn rbf bandwidth of the dense pipeline computed without
    touching any of the n distances, so the approx engine's rbf weights
    match the exact engine's up to float rounding."""
    x = xb.astype(jnp.float32)
    mean_d2 = (
        jnp.sum(x * x, axis=-1, keepdims=True)
        - 2.0 * jnp.matmul(x, tables.train_mean[:, None],
                           precision=jax.lax.Precision.HIGHEST)
        + tables.mean_sq_norm
    )
    return jnp.maximum(mean_d2, 0.0)
