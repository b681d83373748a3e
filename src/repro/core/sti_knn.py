"""STI-KNN: exact pair-interaction Shapley-Taylor values for KNN in O(t n^2).

Implements Algorithm 1 of "Optimizing Data Shapley Interaction Calculation
from O(2^n) to O(t n^2) for KNN models" (Belaid et al., 2023), reformulated
for TPU:

  * the paper's sequential recurrence (Alg. 1, lines 3-10) is computed as a
    closed-form reverse cumulative sum (log-depth, VPU friendly);
  * the per-test-point matrix is never materialized: for train points a, b
    with ranks r_p[a], r_p[b] under test point p (rank 0 = closest),
        phi_ab(u_p) = g_p[max(r_p[a], r_p[b])]          (a != b)
    so the final matrix is a streamed mean of outer-max gathers.

Notation (0-based, mirrors the paper's 1-based j = j0 + 1):
  u[j0]    = 1[label(alpha_{j0}) == y_test] / k   (sorted by distance)
  g[n-1]   = -2(n-k)/(n(n-1)) * u[n-1]                         (Eq. 6)
  g[j0-1]  = g[j0] + 1[j0 > k] * 2(j0-k)/((j0-1) j0) * (u[j0]-u[j0-1])
                                                               (Eq. 7)
  phi_{alpha_i, alpha_j} = g[j] for all i < j                  (Eq. 8)
  diagonal phi_ii = mean_p u_p(i)                              (Eq. 4)
If n <= k the valuation function is fully linear and every interaction is 0
(Lemma 1's sum is empty); the code guards this explicitly.
"""

from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp

__all__ = [
    "superdiagonal_g",
    "superdiagonal_g_topm",
    "ranks_from_order",
    "ranks_from_distances",
    "to_train",
    "pairwise_sq_dists",
    "sti_knn_interactions",
    "sti_knn_matrix_one_test",
    "register_fill_fn",
    "register_acc_fill_fn",
    "accumulate_fill",
    "resolve_fill",
    "register_rect_fill_fn",
    "register_rect_acc_fill_fn",
    "accumulate_rect_fill",
    "resolve_rect_fill",
    "InteractionMode",
]

# Coefficient schemes. "sti" is the paper's Shapley-Taylor index; "sii" is
# the Grabisch-Roubens interaction index (paper Sec. 3.2: same recurrence,
# different coefficients -- closed forms derived in DESIGN.md / tests).
InteractionMode = str  # "sti" | "sii"


def _recurrence_coeffs(
    n: int, k: int, mode: InteractionMode, dtype, n_total: int | None = None
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Return (last_coef, step_coef[j0]) for the g recurrence.

    g[n-1] = last_coef * u[n-1]
    g[j0-1] = g[j0] + step_coef[j0] * (u[j0] - u[j0-1])
    step_coef[j0] is zero unless j0 > k (paper condition j > k+1) and j0 >= 2.

    `n_total` supports the truncated top-m estimator (`superdiagonal_g_topm`):
    the step coefficients depend only on the POSITION j0 in the sorted order,
    so they are identical whether the vector holds all n_total points or just
    the closest n=m of them -- but the anchor term multiplies u at position
    n_total-1, so `last_coef` must be computed from the full training-set
    size. Defaults to n (the exact, untruncated recurrence).
    """
    if n_total is None:
        n_total = n
    j0 = jnp.arange(n, dtype=dtype)
    active = (j0 > k) & (j0 >= 2)
    if mode == "sti":
        last = -2.0 * (n_total - k) / (n_total * (n_total - 1.0))
        step = jnp.where(active, 2.0 * (j0 - k) / jnp.where(active, (j0 - 1.0) * j0, 1.0), 0.0)
    elif mode == "sii":
        # SII_{n-1,n} = -u(n)/(n-1); step coefficient 1/(j-2) = 1/(j0-1).
        last = -1.0 / (n_total - 1.0)
        step = jnp.where(active, 1.0 / jnp.where(active, j0 - 1.0, 1.0), 0.0)
    else:
        raise ValueError(f"unknown interaction mode: {mode!r}")
    if n_total <= k:  # valuation fully linear -> all pair interactions vanish
        last = 0.0
        step = jnp.zeros_like(step)
    return jnp.asarray(last, dtype), step


def superdiagonal_g(u_sorted: jnp.ndarray, k: int, *, mode: InteractionMode = "sti") -> jnp.ndarray:
    """Compute the super-diagonal vector g for one (or a batch of) test points.

    Args:
      u_sorted: (..., n) valuation of each sorted train point,
        u[j0] = 1[label match]/k with j0 = 0 the closest point.
      k: KNN parameter.

    Returns:
      (..., n) g with g[j0] = phi_{alpha_{j0-1}, alpha_{j0}}; g[0] is unused
      (set to 0). For train indices a != b:
      phi_ab = g[max(rank_a, rank_b)].
    """
    n = u_sorted.shape[-1]
    dtype = u_sorted.dtype
    if n < 2:
        return jnp.zeros_like(u_sorted)
    last_coef, step_coef = _recurrence_coeffs(n, k, mode, dtype)
    du = u_sorted - jnp.roll(u_sorted, 1, axis=-1)  # u[j0]-u[j0-1]; j0=0 junk
    term = step_coef * du  # zero where inactive (incl. j0 in {0,1})
    # R[j0] = sum_{m >= j0} term[m]; suffix[j0] = R[j0+1]
    rev_cumsum = jnp.flip(jnp.cumsum(jnp.flip(term, -1), -1), -1)
    suffix = jnp.concatenate(
        [rev_cumsum[..., 1:], jnp.zeros_like(rev_cumsum[..., :1])], axis=-1
    )
    g = last_coef * u_sorted[..., -1:] + suffix
    return g.at[..., 0].set(0.0)


def superdiagonal_g_topm(
    u_topm: jnp.ndarray, k: int, n_total: int, *, mode: InteractionMode = "sti"
) -> jnp.ndarray:
    """Truncated-g estimator for `engine="approx"` (DESIGN.md Sec. 16).

    Args:
      u_topm: (..., m) valuation of the m CLOSEST train points only (sorted,
        position 0 = closest) out of a full training set of `n_total`.
      k: KNN parameter.
      n_total: full training-set size the truncation came from.

    Returns:
      (..., m) estimate of g at positions 0..m-1, computed by running the
      exact recurrence over the m known entries and anchoring the tail with
      `last_coef(n_total) * u_topm[m-1]` in place of the unobservable
      `last_coef * u[n_total-1] + sum_{m'>=m} step_coef[m'] * du[m']`. The
      step coefficients are position-only, so every term over the matched
      prefix is EXACT; the dropped tail is what
      `repro.core.approx.interaction_error_bound` certifies. With m ==
      n_total this is exactly `superdiagonal_g` (the anchor tail is the true
      last term and the dropped sum is empty).
    """
    m = u_topm.shape[-1]
    if m < 2 or n_total < 2:
        return jnp.zeros_like(u_topm)
    last_coef, step_coef = _recurrence_coeffs(
        m, k, mode, u_topm.dtype, n_total=n_total
    )
    du = u_topm - jnp.roll(u_topm, 1, axis=-1)
    term = step_coef * du
    rev_cumsum = jnp.flip(jnp.cumsum(jnp.flip(term, -1), -1), -1)
    suffix = jnp.concatenate(
        [rev_cumsum[..., 1:], jnp.zeros_like(rev_cumsum[..., :1])], axis=-1
    )
    g = last_coef * u_topm[..., -1:] + suffix
    return g.at[..., 0].set(0.0)


def pairwise_sq_dists(x_test: jnp.ndarray, x_train: jnp.ndarray) -> jnp.ndarray:
    """(t, d), (n, d) -> (t, n) squared L2 distances via the MXU-friendly
    expansion ||a-b||^2 = ||a||^2 - 2 a.b + ||b||^2.

    The cross term runs at `Precision.HIGHEST` (f32-exact products): the
    TPU default rounds f32 operands to bf16, which reorders near-tied
    neighbours and so changes the exact ranks the valuation depends on."""
    xt = x_test.astype(jnp.float32)
    xn = x_train.astype(jnp.float32)
    cross = jnp.matmul(xt, xn.T, precision=jax.lax.Precision.HIGHEST)
    d2 = (
        jnp.sum(xt * xt, -1, keepdims=True)
        - 2.0 * cross
        + jnp.sum(xn * xn, -1)[None, :]
    )
    return jnp.maximum(d2, 0.0)


def to_train(order: jnp.ndarray, *xs: jnp.ndarray) -> tuple:
    """Bring (t, n) arrays in sorted coordinates back to train coordinates.

    Returns one array per `xs`, with `out[p, i] = x[p, ranks[p, i]]`: the
    inverse of the permutation `order` applied to each row, done as ONE sort
    keyed by `order` that carries every `xs` as an operand (the TPU sorts;
    it gathers and scatters element by element). Each row of `order` is a
    permutation, so the keys are unique and the result is exact whatever
    the sort's stability.
    """
    return jax.lax.sort((order, *xs), dimension=-1, is_stable=False,
                        num_keys=1)[1:]


def ranks_from_order(order: jnp.ndarray) -> jnp.ndarray:
    """(t, n) argsort permutation -> (t, n) integer ranks (0 = closest).

    The inverse of each row of `order`: `to_train` of the sorted positions,
    the one rank convention of the scan path, the local pjit step and the
    fused pipeline (whose interaction update sorts the same positions
    together with its u).
    """
    pos = jax.lax.broadcasted_iota(order.dtype, order.shape, order.ndim - 1)
    return to_train(order, pos)[0]


def ranks_from_distances(d2: jnp.ndarray) -> jnp.ndarray:
    """(t, n) distances -> (t, n) integer ranks (0 = closest), stable ties."""
    return ranks_from_order(jnp.argsort(d2, axis=-1, stable=True))


def _fill_xla(g: jnp.ndarray, ranks: jnp.ndarray) -> jnp.ndarray:
    """Sum over test points of g_p[max(r_p[a], r_p[b])] -> (n, n).

    Pure-XLA reference path. Materializes the full (t, n, n) gather, so peak
    memory is O(t n^2): kept as the correctness oracle, not the default.
    The production fills below (and the Pallas kernel in
    repro.kernels.sti_fill) compute the same quantity in O(chunk * n^2).
    """

    def one(g_p, r_p):
        m = jnp.maximum(r_p[:, None], r_p[None, :])
        return g_p[m]

    return jnp.sum(jax.vmap(one)(g, ranks), axis=0)


def _scan_fill(one_fn: Callable, g, ranks, chunk: int, acc0=None) -> jnp.ndarray:
    """Shared scaffolding for the streaming fills: pad the test dim to a
    multiple of `chunk` (padded rows have g == 0, so every value they
    contribute is exactly 0), then lax.scan `chunk` test points at a time
    into an (n, n) f32 accumulator. `one_fn(g_p, r_p) -> (n, n)` is the
    per-test-point kernel. `acc0` seeds the accumulator (the in-place
    accumulate form: the scan carry IS the caller's accumulator, so no
    second (n, n) temporary is materialized); None starts from zeros."""
    t, n = g.shape
    chunk = max(1, min(int(chunk), t))
    g = g.astype(jnp.float32)
    pad = (-t) % chunk
    if pad:
        g = jnp.pad(g, ((0, pad), (0, 0)))
        ranks = jnp.pad(ranks, ((0, pad), (0, 0)))

    def body(acc, batch):
        gc, rc = batch
        return acc + jnp.sum(jax.vmap(one_fn)(gc, rc), axis=0), None

    acc, _ = jax.lax.scan(
        body,
        jnp.zeros((n, n), jnp.float32) if acc0 is None else acc0,
        (g.reshape(-1, chunk, n), ranks.reshape(-1, chunk, n)),
    )
    return acc


def _select_one(g_p, rr_p, rc_p):
    """Per-test-point (n_rows, n_cols) block g[max(rr[a], rc[b])] as a
    broadcast select with no 2-D gather:

        g[max(i, j)] = where(j >= i, g[j], g[i])

    so only the two (n,)-sized gathers g[rr] and g[rc] exist, and XLA fuses
    the select with the accumulator add: the block is never a temporary."""
    return jnp.where(rc_p[None, :] >= rr_p[:, None],
                     g_p[rc_p][None, :], g_p[rr_p][:, None])


def _square_select_one(g_p, r_p):
    """`_select_one` with one rank vector for rows and columns."""
    return _select_one(g_p, r_p, r_p)


def _fill_chunked(g: jnp.ndarray, ranks: jnp.ndarray, *, chunk: int = 1) -> jnp.ndarray:
    """Chunked scan fill: constant memory in t.

    Per test point the train-coordinate matrix is the broadcast select
    `_select_one` (no (n, n) index or gather temporary). A lax.scan streams
    `chunk` test points at a time into the (n, n) f32 accumulator, so
    nothing of size O(t n^2) ever exists -- this is the default fill.
    """
    return _scan_fill(_square_select_one, g, ranks, chunk)


def _onehot_one(n: int) -> Callable:
    thresh = jnp.arange(n)

    def one(g_p, r_p):
        dg = g_p - jnp.concatenate([g_p[1:], jnp.zeros((1,), g_p.dtype)])
        c = (r_p[:, None] <= thresh[None, :]).astype(jnp.float32)
        return jax.lax.dot_general(
            c * dg[None, :], c, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        )

    return one


def _fill_onehot(g: jnp.ndarray, ranks: jnp.ndarray, *, chunk: int = 1) -> jnp.ndarray:
    """One-hot-matmul MXU fill: expresses the max-gather as a GEMM.

    With C[a, j] = 1[r_a <= j] (cumulative one-hot of the ranks) and
    dg[j] = g[j] - g[j+1] (g[n] := 0), the telescoping sum gives
        sum_j dg[j] C[a, j] C[b, j] = g[max(r_a, r_b)]
    so each test point contributes (C * dg) @ C^T -- an (n, n, n) matmul the
    MXU executes at full tilt. O(t n^3) FLOPs (vs O(t n^2) for the gather
    fills) but no gather unit pressure; wins only where matmul throughput
    dwarfs gather throughput (see EXPERIMENTS.md "Fill variants").
    """
    return _scan_fill(_onehot_one(g.shape[-1]), g, ranks, chunk)


def _acc_fill_chunked(acc, g, ranks, *, chunk: int = 1) -> jnp.ndarray:
    """In-place form of the chunked fill: the scan carry is the caller's
    accumulator, so no second (n, n) temporary exists."""
    return _scan_fill(_square_select_one, g, ranks, chunk, acc0=acc)


def _acc_fill_onehot(acc, g, ranks, *, chunk: int = 1) -> jnp.ndarray:
    return _scan_fill(_onehot_one(g.shape[-1]), g, ranks, chunk, acc0=acc)


# ------------------------------------------------------- rectangular fills
# A RECT fill computes out[a, b] = sum_p g[p, max(r_rows[p,a], r_cols[p,b])]
# for INDEPENDENT row/column index bases over the same global rank space:
# the sharded engine's per-device (n/D, n) row-block update is
# `r_rows = ranks[:, rows_of_this_device]`, `r_cols = ranks`. The square
# fills above are the r_rows == r_cols special case.
def _rect_one(g_p, rr_p, rc_p):
    """Per-test-point rectangular block: (n_rows, n_cols) max-gather."""
    return g_p[jnp.maximum(rr_p[:, None], rc_p[None, :])]


def _rect_fill_xla(g, r_rows, r_cols) -> jnp.ndarray:
    """Rectangular reference fill: materializes the (t, n_rows, n_cols)
    gather. Correctness oracle for the streaming/Pallas rect variants (it
    keeps the literal max-gather, independent of `_select_one`)."""
    return jnp.sum(jax.vmap(_rect_one)(g, r_rows, r_cols), axis=0)


def _scan_rect_fill(g, r_rows, r_cols, chunk: int, acc0=None) -> jnp.ndarray:
    """Rect twin of `_scan_fill`: lax.scan `chunk` test points at a time into
    an (n_rows, n_cols) accumulator (padded test rows have g == 0, so they
    contribute exactly zero). `acc0` seeds the scan carry (the in-place
    accumulate form); None starts from zeros."""
    t, n = g.shape
    nr, nc = r_rows.shape[1], r_cols.shape[1]
    chunk = max(1, min(int(chunk), t))
    g = g.astype(jnp.float32)
    pad = (-t) % chunk
    if pad:
        g = jnp.pad(g, ((0, pad), (0, 0)))
        r_rows = jnp.pad(r_rows, ((0, pad), (0, 0)))
        r_cols = jnp.pad(r_cols, ((0, pad), (0, 0)))

    def body(acc, batch):
        gc, rrc, rcc = batch
        return acc + jnp.sum(jax.vmap(_select_one)(gc, rrc, rcc), axis=0), None

    acc, _ = jax.lax.scan(
        body,
        jnp.zeros((nr, nc), jnp.float32) if acc0 is None else acc0,
        (
            g.reshape(-1, chunk, n),
            r_rows.reshape(-1, chunk, nr),
            r_cols.reshape(-1, chunk, nc),
        ),
    )
    return acc


def _rect_fill_chunked(g, r_rows, r_cols, *, chunk: int = 1) -> jnp.ndarray:
    """Chunked rect scan fill: constant memory in t, per-point blocks as
    the fused broadcast select `_select_one`. The sharded engine's default
    row-block fill."""
    return _scan_rect_fill(g, r_rows, r_cols, chunk)


def _rect_acc_fill_chunked(acc, g, r_rows, r_cols, *, chunk: int = 1):
    """In-place form of the chunked rect fill: the scan carry is the
    caller's (n_rows, n_cols) block, so no second temporary exists."""
    return _scan_rect_fill(g, r_rows, r_cols, chunk, acc0=acc)


@functools.partial(
    jax.jit,
    static_argnames=("k", "mode", "test_batch", "fill_fn_name", "fill_static"),
)
def _sti_knn_jit(
    x_train, y_train, x_test, y_test, k, mode, test_batch, fill_fn_name,
    fill_static=(),
):
    n = x_train.shape[0]
    t = x_test.shape[0]
    acc_dtype = jnp.float32

    def body(carry, batch):
        acc, diag = carry
        xb, yb = batch
        d2 = pairwise_sq_dists(xb, x_train)
        order = jnp.argsort(d2, axis=-1, stable=True)
        ranks = ranks_from_order(order)
        match = (y_train[order] == yb[:, None]).astype(acc_dtype)
        u = match / k
        g = superdiagonal_g(u, k, mode=mode)
        acc = accumulate_fill(acc, g, ranks, fill_fn_name, fill_static)
        # diag term hoisted into the already-computed u: u in train
        # coordinates is u[p, ranks[p, i]] = 1[y_train[i] == y_p]/k, so the
        # (tb, n) label broadcast is not recomputed.
        diag = diag + jnp.sum(jnp.take_along_axis(u, ranks, axis=-1), axis=0)
        return (acc, diag), None

    # Stream test points in batches of `test_batch` (constant memory in t).
    tb = min(test_batch, t)
    num = t // tb
    xr = x_test[: num * tb].reshape(num, tb, -1)
    yr = y_test[: num * tb].reshape(num, tb)
    init = (
        jnp.zeros((n, n), acc_dtype),
        jnp.zeros((n,), acc_dtype),
    )
    (acc, diag), _ = jax.lax.scan(body, init, (xr, yr))
    rem = t - num * tb
    if rem:
        (acc, diag), _ = body((acc, diag), (x_test[num * tb :], y_test[num * tb :]))
    phi = acc / t
    phi = jnp.fill_diagonal(phi, diag / t, inplace=False)
    return phi


# Fill registry: every entry computes sum_p g[p, max(ranks[p,a], ranks[p,b])].
# "xla" is the O(t n^2)-memory oracle; "chunked" (default) and "onehot" stream
# in O(chunk n^2); the Pallas kernel registers itself as "pallas" /
# "pallas_interpret" when repro.kernels is imported (repro/__init__ does).
_FILL_FNS: dict[str, Callable] = {
    "xla": _fill_xla,
    "chunked": _fill_chunked,
    "onehot": _fill_onehot,
}

# Accumulate-fill registry: `fn(acc, g, ranks, **static) -> acc` computes
# acc + fill(g, ranks) WITHOUT materializing the fill's (n, n) result as a
# separate temporary (scan-carry seeding for the XLA fills; the Pallas
# variant aliases the accumulator buffer via input_output_aliases). Entries
# are keyed by the same names as _FILL_FNS; a missing entry falls back to
# the additive form in `accumulate_fill`.
_ACC_FILL_FNS: dict[str, Callable] = {
    "chunked": _acc_fill_chunked,
    "onehot": _acc_fill_onehot,
}


def register_acc_fill_fn(name: str, fn: Callable) -> None:
    """Register the in-place accumulate form of fill `name`:
    `fn(acc, g, ranks, **static_params) -> acc` must equal
    `acc + _FILL_FNS[name](g, ranks, **static_params)`."""
    _ACC_FILL_FNS[name] = fn


def accumulate_fill(acc, g, ranks, fill: str, fill_static: tuple = ()):
    """acc += fill(g, ranks), via the registered in-place accumulate form
    when one exists (no second (n, n) temporary) and the additive fallback
    otherwise. `fill_static` is the hashable params tuple `resolve_fill`
    returns."""
    fn = _ACC_FILL_FNS.get(fill)
    if fn is not None:
        return fn(acc, g, ranks, **dict(fill_static))
    return acc + _FILL_FNS[fill](g, ranks, **dict(fill_static))


# Rectangular fill registries, mirroring _FILL_FNS/_ACC_FILL_FNS one level
# down in generality: `fn(g, r_rows, r_cols, **static) -> (n_rows, n_cols)`
# and the in-place accumulate form `fn(acc, g, r_rows, r_cols, **static)`.
# "chunked" is the XLA block scan (the sharded engine's universal fallback);
# the Pallas rect kernels register as "pallas"/"pallas_interpret" when
# repro.kernels is imported (repro/__init__ does).
_RECT_FILL_FNS: dict[str, Callable] = {
    "xla": _rect_fill_xla,
    "chunked": _rect_fill_chunked,
}

_RECT_ACC_FILL_FNS: dict[str, Callable] = {
    "chunked": _rect_acc_fill_chunked,
}


def register_rect_fill_fn(name: str, fn: Callable) -> None:
    """Register a rectangular fill:
    `fn(g, r_rows, r_cols, **static_params) -> (n_rows, n_cols) f32` with
    hashable static params (they become part of the jit cache key)."""
    _RECT_FILL_FNS[name] = fn


def register_rect_acc_fill_fn(name: str, fn: Callable) -> None:
    """Register the in-place accumulate form of rect fill `name`:
    `fn(acc, g, r_rows, r_cols, **static_params) -> acc` must equal
    `acc + _RECT_FILL_FNS[name](g, r_rows, r_cols, **static_params)`."""
    _RECT_ACC_FILL_FNS[name] = fn


def accumulate_rect_fill(acc, g, r_rows, r_cols, fill: str,
                         fill_static: tuple = ()):
    """acc += rect_fill(g, r_rows, r_cols), via the registered in-place
    accumulate form when one exists (no (n_rows, n_cols) temporary) and the
    additive fallback otherwise. This is the sharded step's local row-block
    update: acc is the device's (n/D, n) block."""
    fn = _RECT_ACC_FILL_FNS.get(fill)
    if fn is not None:
        return fn(acc, g, r_rows, r_cols, **dict(fill_static))
    return acc + _RECT_FILL_FNS[fill](g, r_rows, r_cols, **dict(fill_static))


def resolve_rect_fill(
    fill: str,
    n_rows: int,
    n_cols: int,
    t: int,
    *,
    fill_params: Optional[dict] = None,
    autotune: bool = False,
) -> tuple[str, tuple]:
    """Resolve a rect fill request to (registry_name, hashable static params).

    "auto" consults the autotune cache under the rectangular key (the
    `rows{R}` segment alongside backend/device-count/size buckets); a miss
    runs the tuner (autotune=True) or falls back to the backend heuristic.
    A SQUARE registry name with no rect twin (e.g. "onehot" restored from a
    single-device checkpoint) runs the XLA block scan, with a warning.
    Every other unregistered name raises: a Pallas request is never
    quietly replaced by another fill.
    """
    params = dict(fill_params or {})
    if fill == "auto":
        from repro.kernels.autotune import best_rect_fill  # lazy: no cycle

        name, tuned = best_rect_fill(n_rows, n_cols, t, allow_tune=autotune)
        tuned.update(params)
        params = _accepted_params(_RECT_FILL_FNS[name], tuned)
        fill = name
    if fill not in _RECT_FILL_FNS:
        if fill not in _FILL_FNS:
            raise ValueError(
                f"unknown rect fill {fill!r}; registered: "
                f"{sorted(_RECT_FILL_FNS)}"
            )
        import warnings

        warnings.warn(
            f"fill {fill!r} has no rectangular variant; the "
            f"sharded engine runs the XLA block scan instead",
            stacklevel=2,
        )
        fill, params = "chunked", _accepted_params(
            _RECT_FILL_FNS["chunked"], params
        )
    bad = set(params) - set(_accepted_params(_RECT_FILL_FNS[fill], params))
    if bad:
        raise ValueError(
            f"rect fill {fill!r} does not accept params {sorted(bad)}"
        )
    return fill, tuple(sorted(params.items()))


def _accepted_params(fn: Callable, params: dict) -> dict:
    """Subset of `params` that `fn(g, ranks, **...)` can accept (a fn with
    **kwargs accepts everything)."""
    import inspect

    try:
        sig = inspect.signature(fn)
    except (TypeError, ValueError):
        return dict(params)
    if any(p.kind is inspect.Parameter.VAR_KEYWORD
           for p in sig.parameters.values()):
        return dict(params)
    return {k: v for k, v in params.items() if k in sig.parameters}


def register_fill_fn(name: str, fn: Callable) -> None:
    """Register an alternative fill implementation (e.g. the Pallas kernel).

    `fn(g, ranks, **static_params) -> (n, n) f32`; static params must be
    hashable (they become part of the jit cache key).
    """
    _FILL_FNS[name] = fn


def resolve_fill(
    fill: str,
    n: int,
    t: int,
    *,
    fill_params: Optional[dict] = None,
    autotune: bool = False,
) -> tuple[str, tuple]:
    """Resolve a fill request to (registry_name, hashable static params).

    "auto" consults the persistent autotune cache (repro.kernels.autotune)
    for the winning variant at this (n, t, backend); on a cache miss it
    either runs the tuner (autotune=True) or falls back to the backend
    heuristic. Explicit `fill_params` override tuned ones.
    """
    params = dict(fill_params or {})
    if fill == "auto":
        from repro.kernels.autotune import best_fill  # lazy: avoids cycle

        name, tuned = best_fill(n, t, allow_tune=autotune)
        # User params are a hint for whichever variant wins: keep only the
        # ones the winner accepts (e.g. a chunk= hint is dropped, not a
        # crash, when the cache resolves to the parameterless "xla").
        tuned.update(params)
        params = _accepted_params(_FILL_FNS[name], tuned)
        fill = name
    if fill not in _FILL_FNS:
        raise ValueError(
            f"unknown fill {fill!r}; registered: {sorted(_FILL_FNS)}"
            + (
                " (fill='megakernel' is a whole-step fill available only"
                " through the fused/sharded engines, not the square"
                " registry)"
                if fill == "megakernel"
                else ""
            )
        )
    bad = set(params) - set(_accepted_params(_FILL_FNS[fill], params))
    if bad:
        raise ValueError(
            f"fill {fill!r} does not accept params {sorted(bad)}"
        )
    return fill, tuple(sorted(params.items()))


def sti_knn_interactions(
    x_train: jnp.ndarray,
    y_train: jnp.ndarray,
    x_test: jnp.ndarray,
    y_test: jnp.ndarray,
    k: int,
    *,
    mode: InteractionMode = "sti",
    test_batch: int = 256,
    fill: str = "auto",
    fill_params: Optional[dict] = None,
    autotune: bool = False,
) -> jnp.ndarray:
    """Full STI-KNN: (n, n) symmetric interaction matrix, diagonal = main terms.

    O(t n^2) exactly as the paper's Algorithm 1; test points are streamed so
    peak memory is O(n^2 + test_batch * n) with the default chunked fill
    (fill="xla" restores the seed reference, which peaks at
    O(test_batch * n^2)). fill="auto" consults the block autotuner cache;
    autotune=True times the candidates for this size once and persists the
    winner.
    """
    if x_train.ndim != 2 or x_test.ndim != 2:
        raise ValueError("features must be (num_points, dim)")
    if k < 1:
        raise ValueError("k must be >= 1")
    if x_test.shape[0] < 1:
        raise ValueError("need at least one test point")
    # the fill executes on (test_batch, n) slices: key the autotune lookup on
    # the executed shape, not the total test count
    fill_name, fill_static = resolve_fill(
        fill, x_train.shape[0], min(int(test_batch), x_test.shape[0]),
        fill_params=fill_params, autotune=autotune,
    )
    return _sti_knn_jit(
        x_train, y_train, x_test, y_test, int(k), mode, int(test_batch),
        fill_name, fill_static,
    )


def sti_knn_matrix_one_test(
    u_sorted: jnp.ndarray, k: int, *, mode: InteractionMode = "sti"
) -> jnp.ndarray:
    """Paper Alg. 1 `STI-KNN-one-test` in sorted coordinates: the (n, n)
    pair-interaction matrix for a single test point, zero diagonal.

    Provided for tests/pedagogy; production code streams via
    `sti_knn_interactions`.
    """
    g = superdiagonal_g(u_sorted, k, mode=mode)
    n = u_sorted.shape[-1]
    idx = jnp.arange(n)
    m = jnp.maximum(idx[:, None], idx[None, :])
    phi = g[m]
    return jnp.fill_diagonal(phi, 0.0, inplace=False)
