"""Method-generic streaming valuation: accumulator specs + update kernels.

The fused/sharded pipeline (`repro.kernels.sti_pipeline`) streams test
points through a fixed-shape accumulator update -- that is what makes the
paper's O(t n^2) a wall-clock bound. This module factors the part of that
step that actually differs between valuation methods into two small
objects, so every registered method (interactions AND per-point values)
rides the identical distance -> rank -> contribution -> update pipeline
(DESIGN.md Sec. 12):

  * `AccumulatorSpec` -- the shape/dtype/sharding contract of a method's
    running state: an (n, n) row-blocked matrix plus (n,) diagonal for the
    interaction methods, a single (n,) vector for the point-value methods
    ("knn_shapley", "wknn", "loo"). The spec owns init, the per-array
    partition specs for the sharded engine, the checkpoint array names,
    and the finalize (divide-by-t) rule.
  * `UpdateKernel` -- the per-method pure functions the generic step calls:
    `contrib(d2, order, match, mask) -> u` (the sorted-coordinate per-point
    contribution; the validity mask is folded in here, so padded test rows
    contribute exactly zero through every method) and
    `update(state, u, g, order, mask) -> state`, which brings what it needs
    back to train coordinates with `sti_knn.to_train` (one sort keyed by
    `order`; no gather or scatter).

Kernels are built by registered FACTORIES (`register_update_kernel`) keyed
by method name: a factory binds the static configuration -- k, method
options such as the wknn weight kind, the resolved fill, and the mesh axis
name for the sharded variant -- and returns the closures the step jits.
`axis=None` builds the single-device update; a mesh axis name builds the
shard_map-local update (rect row-block fill + g/rank all-gather for
interactions, an O(n) psum_scatter of the per-train partial for vectors).

Built-in registrations: "sti", "sii" (interaction state), "knn_shapley",
"wknn", "loo" (vector state). The wknn kernel is the exact O(t n^2)
weighted-KNN Shapley recurrence (soft-label weighted utility, arXiv
2401.11103 family): no 2^n subset enumeration anywhere on this path -- the
brute-force oracle survives only as the `engine="oracle"` parity check in
`repro.core.methods`.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core.sti_knn import (
    accumulate_fill,
    accumulate_rect_fill,
    superdiagonal_g,
    to_train,
)

__all__ = [
    "AccumulatorSpec",
    "UpdateKernel",
    "INTERACTION_STATE",
    "POINT_STATE",
    "SENTINEL_COORD",
    "SENTINEL_LABEL",
    "register_update_kernel",
    "make_update_kernel",
    "accumulator_spec",
    "stream_methods",
    "has_stream_kernel",
    "register_megakernel_tables",
    "make_megakernel_tables",
    "compact_order",
    "register_refold_builder",
    "make_refold_kernel",
    "approx_point_methods",
    "make_approx_values",
    "scatter_point_update",
]

# Soft-delete sentinels for fixed-capacity training sets (the online
# valuation service mutates the train set without retracing): a removed /
# never-filled slot keeps its position but gets coordinates SENTINEL_COORD
# and label SENTINEL_LABEL. The squared distance to a sentinel slot is
# ~d * 1e30 -- finite in f32 (1e30 << 3.4e38) yet astronomically larger
# than any real distance, so sentinel slots sort to the tail of every
# neighbour ranking; the label never matches a real test label, so their
# contribution is exactly zero through every registered method. NOTE
# 1e15, not 1e30: the expansion-form distance squares the coordinate, and
# (1e30)^2 overflows f32 to inf, which the -2ab cross term then turns
# into inf - inf = NaN.
SENTINEL_COORD = 1e15
SENTINEL_LABEL = -1
# Any squared distance at or above this is treated as a sentinel column
# (real squared distances would need coordinates ~1e10 to reach it).
SENTINEL_D2 = 1e20


@dataclasses.dataclass(frozen=True)
class AccumulatorSpec:
    """Shape/dtype/sharding contract of one method family's running state.

    `names` are the checkpoint array names (stable across sessions);
    `layouts` name each array's sharded placement: "matrix" = (n, n) row
    blocks ((n/D, n) per device), "vector" = (n,) row-sharded ((n/D,) per
    device). Instances are frozen; the two canonical ones are
    `INTERACTION_STATE` and `POINT_STATE` below.
    """

    kind: str                    # "interaction" | "point"
    names: tuple[str, ...]       # checkpoint / npz array names
    layouts: tuple[str, ...]     # "matrix" | "vector" per array

    def shapes(self, n: int) -> tuple[tuple[int, ...], ...]:
        """Array shapes for an n-point training set, in `names` order."""
        return tuple(
            (n, n) if lay == "matrix" else (n,) for lay in self.layouts
        )

    def init(self, n: int, shardings=None) -> tuple[jnp.ndarray, ...]:
        """Zero-initialized f32 state tuple for an n-point training set,
        on the default device, or laid out per `shardings` (one per array,
        see `shardings()`): each device then allocates only its own block,
        and the whole (n, n) array never exists on one device."""
        if shardings is None:
            return tuple(jnp.zeros(s, jnp.float32) for s in self.shapes(n))
        return tuple(
            jax.jit(lambda s=s: jnp.zeros(s, jnp.float32), out_shardings=sh)()
            for s, sh in zip(self.shapes(n), shardings)
        )

    def partition_specs(self, axis: str) -> tuple[P, ...]:
        """Per-array PartitionSpecs over the 1-D valuation mesh `axis`
        (row blocks for matrices, row shards for vectors)."""
        return tuple(
            P(axis, None) if lay == "matrix" else P(axis)
            for lay in self.layouts
        )

    def shardings(self, mesh, axis: str):
        """Per-array NamedShardings on `mesh` (device_put placement of a
        restored/initial state in a sharded session)."""
        from jax.sharding import NamedSharding

        return tuple(NamedSharding(mesh, s)
                     for s in self.partition_specs(axis))

    def result_arrays(self, state: tuple, t: int) -> dict:
        """Finalize a state of t accumulated test points into the
        `ValuationResult` array kwargs: {"phi": ...} for interaction state
        (running mean, diagonal = main terms), {"point_values": ...} for
        vector state.

        Interaction state must be writable host numpy arrays (what
        `ValuationSession._host_state` returns): `acc` is divided by t in
        place and its diagonal overwritten with diag / t, so the result is
        the only (n, n) buffer and no device ever holds a second one."""
        if self.kind == "interaction":
            acc, diag = state
            acc /= t
            np.fill_diagonal(acc, diag / t)
            return {"phi": acc}
        return {"point_values": state[0] / t}


INTERACTION_STATE = AccumulatorSpec(
    "interaction", ("acc", "diag"), ("matrix", "vector")
)
POINT_STATE = AccumulatorSpec("point", ("vec",), ("vector",))


@dataclasses.dataclass(frozen=True)
class UpdateKernel:
    """One method's bound streaming-step closures (built by a factory).

    `contrib(d2, order, match, mask) -> u` maps the shared pipeline
    intermediates (squared distances, argsort order, sorted label match,
    validity mask) to the method's sorted-coordinate contribution vector;
    `update(state, u, g, order, mask) -> state` folds one test batch into
    the accumulator state (`g` is None unless `needs_g`; `order` is the
    batch's stable argsort, each row a permutation of the train indices).
    Both are pure and trace into the enclosing jit.
    """

    method: str
    spec: AccumulatorSpec
    needs_g: bool                      # compute superdiagonal_g before update
    g_mode: Optional[str]              # "sti" | "sii" | None
    contrib: Callable
    update: Callable


_KERNEL_FACTORIES: dict[str, tuple[AccumulatorSpec, Callable]] = {}


def register_update_kernel(method: str, spec: AccumulatorSpec,
                           factory: Callable) -> None:
    """Register a streaming update kernel for `method`: its state contract
    `spec` plus the factory that builds the bound closures.

    `factory(method, k, opts, fill, fill_static, axis) -> UpdateKernel`
    binds the static configuration (axis=None for the single-device step, a
    mesh axis name for the shard_map-local step) and returns pure closures;
    the kernel it returns must carry the same `spec` registered here (the
    spec is registered separately so `accumulator_spec` lookups never have
    to build a throwaway kernel with placeholder statics).
    """
    _KERNEL_FACTORIES[method] = (spec, factory)


def stream_methods() -> list[str]:
    """Sorted names of every method with a registered streaming kernel."""
    return sorted(_KERNEL_FACTORIES)


def has_stream_kernel(method: str) -> bool:
    """Whether `method` can run on the generic streaming engine."""
    return method in _KERNEL_FACTORIES


def make_update_kernel(
    method: str,
    k: int,
    *,
    opts: Optional[dict] = None,
    fill: Optional[str] = None,
    fill_static: tuple = (),
    axis: Optional[str] = None,
) -> UpdateKernel:
    """Build the bound `UpdateKernel` for `method` (see module docstring).

    `opts` are method statics (e.g. {"weights": "rbf"} for wknn); `fill` /
    `fill_static` name the resolved fill for interaction kernels (the
    RECTANGULAR registry entry when `axis` is given); `axis` selects the
    sharded (shard_map-local) update variant.
    """
    if method not in _KERNEL_FACTORIES:
        raise ValueError(
            f"no streaming kernel for method {method!r}; registered: "
            f"{stream_methods()}"
        )
    return _KERNEL_FACTORIES[method][1](
        method, int(k), dict(opts or {}), fill, fill_static, axis
    )


def accumulator_spec(method: str) -> AccumulatorSpec:
    """The registered `AccumulatorSpec` a method streams into."""
    if method not in _KERNEL_FACTORIES:
        raise ValueError(
            f"no streaming kernel for method {method!r}; registered: "
            f"{stream_methods()}"
        )
    return _KERNEL_FACTORIES[method][0]


# ------------------------------------------------------------- interactions
def _interaction_factory(mode: str) -> Callable:
    """Factory for the "sti"/"sii" pair-interaction kernels: (n, n) acc of
    off-diagonal sums + (n,) diag of main terms, via the (rect) fill
    registries of `repro.core.sti_knn`."""

    def factory(method, k, opts, fill, fill_static, axis):
        def contrib(d2, order, match, mask):
            return match * (mask / k)[:, None]

        def ranks_and_u(order, u):
            # one sort gives the ranks and u in train coordinates,
            # u_train[p, i] = mask_p 1[y_i==y_p]/k: the diag term rides on
            # the fill stage's u, masked for free
            with jax.named_scope("rank"):
                pos = jax.lax.broadcasted_iota(order.dtype, order.shape, 1)
                return to_train(order, pos, u)

        if axis is None:
            def update(state, u, g, order, mask):
                acc, diag = state
                ranks, u_train = ranks_and_u(order, u)
                with jax.named_scope("fill"):
                    acc = accumulate_fill(acc, g, ranks, fill, fill_static)
                with jax.named_scope("to_train"):
                    diag = diag + jnp.sum(u_train, axis=0)
                return (acc, diag)
        else:
            def update(state, u, g, order, mask):
                from repro.kernels.sti_fill import rect_row_view

                # local views: acc (nl, n), diag (nl,), u/order (tb/D, n)
                acc, diag = state
                nl = acc.shape[0]
                ranks, u_train = ranks_and_u(order, u)
                with jax.named_scope("collective"):
                    g_all = jax.lax.all_gather(g, axis, axis=0, tiled=True)
                    r_all = jax.lax.all_gather(ranks, axis, axis=0,
                                               tiled=True)
                with jax.named_scope("fill"):
                    # this device's (tb, nl) row window of the global rank
                    # space
                    r_rows = rect_row_view(
                        r_all, jax.lax.axis_index(axis) * nl, nl
                    )
                    acc = accumulate_rect_fill(
                        acc, g_all, r_rows, r_all, fill, fill_static
                    )
                # the diag update reduces over the test dim, so it needs
                # only a reduce-scatter of the (n,) local partial -- O(n)
                # bytes, not an O(tb n) gather like g/ranks, which the fill
                # genuinely needs whole
                with jax.named_scope("to_train"):
                    part = jnp.sum(u_train, axis=0)
                with jax.named_scope("collective"):
                    part = jax.lax.psum_scatter(part, axis, tiled=True)
                return (acc, diag + part)

        return UpdateKernel(method, INTERACTION_STATE, True, mode,
                            contrib, update)

    return factory


# ------------------------------------------------------------ point values
def _match_contrib(d2, order, match, mask, k, opts):
    """Masked 0/1 label match in sorted coordinates (knn_shapley / loo)."""
    return match * mask[:, None]


def _wknn_contrib(d2, order, match, mask, k, opts):
    """Masked weighted contribution c_j = w_j * 1[y_j == y_test] in sorted
    coordinates -- the soft-label weighted KNN utility's per-point value."""
    from repro.core.wknn import distance_weights

    w = distance_weights(d2, opts.get("weights", "rbf"))
    return jnp.take_along_axis(w, order, axis=-1) * match * mask[:, None]


def _shapley_point_values(u, order, k, opts):
    """(tb, n) per-test-point Shapley values in TRAIN coordinates via the
    Jia et al. reverse-cumsum recurrence -- linear in `u`, so the folded
    validity mask zeroes padded rows exactly. Shared by "knn_shapley"
    (u = 0/1 match) and "wknn" (u = weighted contribution): the recurrence
    proof only uses linearity of the utility in the per-point values."""
    from repro.core.knn_shapley import knn_shapley_from_sorted

    with jax.named_scope("recurrence"):
        values = knn_shapley_from_sorted(u, k)
    with jax.named_scope("to_train"):
        return to_train(order, values)[0]


def _loo_point_values(u, order, k, opts):
    """(tb, n) leave-one-out deltas in TRAIN coordinates: removing sorted
    point j < k slides the (k+1)-th neighbour in, delta = (u[j] - u[k])/k;
    points outside the window contribute zero."""
    n = u.shape[-1]
    with jax.named_scope("recurrence"):
        nxt = u[..., k:k + 1] if n > k else jnp.zeros_like(u[..., :1])
        in_window = (jnp.arange(n) < k)[None, :]
        delta = jnp.where(in_window, (u - nxt) / k, 0.0)
    with jax.named_scope("to_train"):
        return to_train(order, delta)[0]


def _point_factory(contrib_fn: Callable, values_fn: Callable) -> Callable:
    """Factory builder for vector-accumulator methods: `values_fn` maps the
    batch to (tb, n) per-train-point values in train coordinates; the update
    is their test-dim sum (psum_scattered onto the local (n/D,) rows when
    sharded -- the vector twin of the interaction diag update)."""

    def factory(method, k, opts, fill, fill_static, axis):
        def contrib(d2, order, match, mask):
            return contrib_fn(d2, order, match, mask, k, opts)

        def update(state, u, g, order, mask):
            part = jnp.sum(values_fn(u, order, k, opts), axis=0)
            if axis is not None:
                with jax.named_scope("collective"):
                    part = jax.lax.psum_scatter(part, axis, tiled=True)
            return (state[0] + part,)

        return UpdateKernel(method, POINT_STATE, False, None,
                            contrib, update)

    return factory


# ------------------------------------------------- megakernel sorted tables
# The fused megakernel (`repro.kernels.sti_megakernel`) never materializes
# the train-coordinate (tb, n) arrays the three-stage step gathers through
# `order`: its streaming sort yields the batch directly in SORTED
# coordinates, and the rank scatter happens at the accumulator tiles. The
# closures below are the registered contrib/values closures algebraically
# restated on the sorted stream -- legal because every one of them is
# either elementwise in the sorted axis or a recurrence over sorted
# positions, so the order-gather commutes out. Exactness is pinned by the
# megakernel parity suite (tests/test_megakernel.py) and the C601 contract.

_MEGAKERNEL_TABLES: dict[str, Callable] = {}


def register_megakernel_tables(method: str, factory: Callable) -> None:
    """Register `factory(k, opts) -> tables` building the method's
    sorted-coordinate megakernel tables. Interaction factories return
    `tables(d2_sorted, match_sorted, mask) -> (g, u)` ((tb, n) each, both
    in sorted coordinates); point factories return
    `tables(d2_sorted, match_sorted, mask) -> values` ((tb, n), value of
    the train point at each sorted position). The validity mask folds in
    here exactly as in `UpdateKernel.contrib`."""
    _MEGAKERNEL_TABLES[method] = factory


def make_megakernel_tables(method: str, k: int, *,
                           opts: Optional[dict] = None) -> Callable:
    """Resolve the sorted-coordinate table closure the fused megakernel
    applies in-kernel for `method` (see `register_megakernel_tables`).
    Raises KeyError for methods without a megakernel registration --
    `fill="megakernel"` is only resolvable for those."""
    if method not in _MEGAKERNEL_TABLES:
        raise KeyError(
            f"method {method!r} has no megakernel tables; registered: "
            f"{sorted(_MEGAKERNEL_TABLES)}"
        )
    return _MEGAKERNEL_TABLES[method](int(k), dict(opts or {}))


def _interaction_megatables(mode: str) -> Callable:
    """sti/sii megakernel tables: the same u = match * mask/k contribution
    and `superdiagonal_g` recurrence as `_interaction_factory`, minus the
    train-coordinate gathers (the kernel's rank scatter replaces them)."""

    def factory(k, opts):
        def tables(d2s, match_s, mask):
            u = match_s * (mask / k)[:, None]
            return superdiagonal_g(u, k, mode=mode), u

        return tables

    return factory


def _shapley_megatables(weighted: bool) -> Callable:
    """knn_shapley/wknn megakernel tables: `knn_shapley_from_sorted` on the
    (optionally distance-weighted) sorted contribution. `distance_weights`
    is elementwise plus a permutation-invariant row statistic (the rbf
    sigma2 row mean), so evaluating it on the SORTED distances matches the
    three-stage path to float-summation order."""

    def factory(k, opts):
        def tables(d2s, match_s, mask):
            if weighted:
                from repro.core.wknn import distance_weights

                w = distance_weights(d2s, opts.get("weights", "rbf"))
                u = w * match_s * mask[:, None]
            else:
                u = match_s * mask[:, None]
            from repro.core.knn_shapley import knn_shapley_from_sorted

            return knn_shapley_from_sorted(u, k)

        return tables

    return factory


def _loo_megatables(k, opts):
    """loo megakernel tables: the `_loo_point_values` window delta on the
    sorted stream (2-D iota: TPU Mosaic rejects 1-D iota in kernels)."""

    def tables(d2s, match_s, mask):
        u = match_s * mask[:, None]
        n = u.shape[-1]
        nxt = u[..., k:k + 1] if n > k else jnp.zeros_like(u[..., :1])
        pos = jax.lax.broadcasted_iota(jnp.int32, u.shape, u.ndim - 1)
        return jnp.where(pos < k, (u - nxt) / k, 0.0)

    return tables


# -------------------------------------------------------------- refold path
# Incremental train-set mutation (the online valuation service's
# add_points / remove_points) refolds CACHED per-batch intermediates --
# the (tb, n) squared distances and argsort order from the distance stage
# -- against the current liveness mask, skipping the distance matmul and
# the sort entirely. The refold reuses each method's registered
# contrib/update closures, so it is exact by construction: for a removal,
# compacting the cached order (live slots to the front, preserving their
# relative order; dead slots to the tail) reproduces bit-for-bit the order
# a fresh argsort of the mutated train set would produce on the live
# prefix, and every dead-slot contribution is zero through the sentinel
# label (see SENTINEL_COORD above; DESIGN.md Sec. 15 has the proof
# obligations per method).


def compact_order(order: jnp.ndarray, keep: jnp.ndarray):
    """Compact a cached argsort order against a liveness mask.

    Args:
      order: (tb, n) argsort of cached squared distances (train indices,
        closest first).
      keep: (n,) liveness per train slot (0 = removed/free, nonzero =
        live), indexed by train coordinate.

    Returns:
      `new_order` (tb, n) with the live entries moved to the front and the
      dead entries to the tail, each group preserving its relative order --
      exactly what a stable argsort of the mutated distance row produces,
      because dead slots hold sentinel distances larger than any real one.
    """
    keep_s = jnp.take(keep, order) > 0          # liveness in sorted coords
    live = jnp.cumsum(keep_s.astype(jnp.int32), axis=-1)
    dead = jnp.cumsum((~keep_s).astype(jnp.int32), axis=-1)
    n_live = live[..., -1:]
    pos = jnp.where(keep_s, live - 1, n_live + dead - 1)
    row = jnp.arange(order.shape[0], dtype=pos.dtype)[:, None]
    return jnp.zeros_like(order).at[row, pos].set(order)


_REFOLD_BUILDERS: dict[str, Callable] = {}


def register_refold_builder(kind: str, builder: Callable) -> None:
    """Register the refold-step builder for one `AccumulatorSpec.kind`.

    `builder(kernel, k) -> refold` receives the method's bound
    `UpdateKernel` and returns the pure function
    `refold(state, d2, order, yb, mask, y_train, keep) -> state` that
    folds one cached test batch into `state` under the liveness mask
    `keep`. Registered per spec (not per method) because the refold only
    depends on the state contract -- the per-method math rides in through
    the kernel's contrib/update closures.
    """
    _REFOLD_BUILDERS[kind] = builder


def make_refold_kernel(
    method: str,
    k: int,
    *,
    opts: Optional[dict] = None,
    fill: Optional[str] = None,
    fill_static: tuple = (),
) -> Callable:
    """Build `refold(state, d2, order, yb, mask, y_train, keep) -> state`
    for `method`: the incremental-mutation twin of the streaming step,
    driven from cached distance/order intermediates instead of raw test
    features. Single-device only (square fill registry); sharded sessions
    gather their state, refold densely, and re-place (the mutation path is
    off the request hot loop)."""
    spec = accumulator_spec(method)
    builder = _REFOLD_BUILDERS.get(spec.kind)
    if builder is None:
        raise ValueError(
            f"no refold builder for accumulator kind {spec.kind!r}; "
            f"registered: {sorted(_REFOLD_BUILDERS)}"
        )
    kernel = make_update_kernel(
        method, int(k), opts=opts, fill=fill, fill_static=fill_static
    )
    return builder(kernel, int(k))


def _masked_refold_builder(kernel: UpdateKernel, k: int) -> Callable:
    """The generic refold body shared by both state contracts: compact the
    cached order, sentinel-mask dead distance columns (so row statistics
    like the wknn rbf bandwidth see exactly the reduced train set), then
    run the method's own contrib -> [g] -> update closures."""

    def refold(state, d2, order, yb, mask, y_train, keep):
        d2 = jnp.where(keep[None, :] > 0, d2, jnp.float32(SENTINEL_D2 * 1e10))
        new_order = compact_order(order, keep)
        match = (jnp.take(y_train, new_order) == yb[:, None]).astype(
            jnp.float32
        )
        u = kernel.contrib(d2, new_order, match, mask)
        g = (
            superdiagonal_g(u, k, mode=kernel.g_mode)
            if kernel.needs_g
            else None
        )
        return kernel.update(state, u, g, new_order, mask)

    return refold


register_refold_builder("interaction", _masked_refold_builder)
register_refold_builder("point", _masked_refold_builder)


# ------------------------------------------------------ approx (candidate)
# engine="approx" (DESIGN.md Sec. 16) replaces the dense (tb, n) sorted
# pipeline with the (tb, m) CANDIDATE vectors from the LSH stage
# (`repro.kernels.ann.topm_candidates`): candidates arrive already sorted
# by exact distance, so candidate position IS the sorted coordinate and the
# per-method recurrences below are the exact recurrences truncated to the
# top m -- the certified-error estimators of `repro.core.approx`. Results
# land in the (n,) accumulator via a single scatter-add per batch.


def approx_point_methods() -> tuple[str, ...]:
    """Point methods with a candidate-space (engine="approx") value path."""
    return ("knn_shapley", "wknn", "loo")


def make_approx_values(method: str, k: int, *, opts: Optional[dict] = None
                       ) -> Callable:
    """Build the candidate-space value closure for a point method.

    Returns `values(d2m, match, valid, mask, sigma2) -> (tb, m)`: per-test
    values of each CANDIDATE at its candidate position, with the validity
    mask (`valid` marks real distinct candidates, `mask` real test rows)
    folded in so every dropped slot and padded row contributes exactly
    zero. `sigma2` is the (tb, 1) analytic rbf bandwidth
    (`repro.kernels.ann.full_mean_sq_dist`; ignored by non-rbf methods).
    The closures are the train-coordinate `_point_factory` value functions
    restricted to the m nearest positions: knn_shapley/wknn run the
    reverse-cumsum recurrence on the truncated vector, loo slides the
    (k+1)-th CANDIDATE in (exact once the matched prefix covers k+1).
    """
    opts = dict(opts or {})
    k = int(k)
    if method == "knn_shapley":
        def values(d2m, match, valid, mask, sigma2):
            from repro.core.knn_shapley import knn_shapley_from_sorted

            u = match * valid * mask[:, None]
            return knn_shapley_from_sorted(u, k)
    elif method == "wknn":
        kind = opts.get("weights", "rbf")

        def values(d2m, match, valid, mask, sigma2):
            from repro.core.knn_shapley import knn_shapley_from_sorted
            from repro.core.wknn import distance_weights

            w = distance_weights(d2m, kind, sigma2=sigma2)
            u = w * match * valid * mask[:, None]
            return knn_shapley_from_sorted(u, k)
    elif method == "loo":
        def values(d2m, match, valid, mask, sigma2):
            u = match * valid * mask[:, None]
            m = u.shape[-1]
            nxt = u[..., k:k + 1] if m > k else jnp.zeros_like(u[..., :1])
            in_window = (jnp.arange(m) < k)[None, :]
            return jnp.where(in_window, (u - nxt) / k, 0.0)
    else:
        raise ValueError(
            f"no approx candidate-space kernel for method {method!r}; "
            f"available: {approx_point_methods()}"
        )
    return values


def scatter_point_update(vec: jnp.ndarray, cand: jnp.ndarray,
                         vals: jnp.ndarray, valid: jnp.ndarray
                         ) -> jnp.ndarray:
    """Scatter-add (tb, m) candidate-coordinate values into the (n,)
    accumulator: the sparse O(tb m) twin of the dense point update's
    O(tb n) rank gather + sum. Invalid candidate slots are redirected to
    the out-of-bounds index n and dropped by the scatter (`mode="drop"`),
    so no branch is needed in the jitted step."""
    n = vec.shape[0]
    idx = jnp.where(valid > 0, cand, n)
    return vec.at[idx.reshape(-1)].add(vals.reshape(-1), mode="drop")


register_update_kernel("sti", INTERACTION_STATE, _interaction_factory("sti"))
register_update_kernel("sii", INTERACTION_STATE, _interaction_factory("sii"))
register_update_kernel(
    "knn_shapley", POINT_STATE,
    _point_factory(_match_contrib, _shapley_point_values),
)
register_update_kernel(
    "wknn", POINT_STATE, _point_factory(_wknn_contrib, _shapley_point_values)
)
register_update_kernel(
    "loo", POINT_STATE, _point_factory(_match_contrib, _loo_point_values)
)
register_megakernel_tables("sti", _interaction_megatables("sti"))
register_megakernel_tables("sii", _interaction_megatables("sii"))
register_megakernel_tables("knn_shapley", _shapley_megatables(False))
register_megakernel_tables("wknn", _shapley_megatables(True))
register_megakernel_tables("loo", _loo_megatables)
