"""Method-generic streaming valuation pipeline: distance -> rank -> update.

The paper's O(t n^2) bound is only a wall-clock bound if the per-batch
intermediates stay on the device: this module chains the tiled distance
kernel (Pallas on TPU, the MXU-friendly XLA expansion elsewhere), the rank
inversion, the per-method contribution/`superdiagonal_g` stage, and the
method's registered update kernel (`repro.kernels.stream_kernels`) into ONE
jitted step per test batch, so the (tb, n) d2/rank/u/g tensors are internal
to a single XLA program and never round-trip HBM between stages. EVERY
registered valuation method streams through this identical step: "sti"/"sii"
update an (n, n) accumulator + (n,) diagonal via the fill registry;
"knn_shapley"/"wknn"/"loo" update a single (n,) vector (DESIGN.md Sec. 12).

The accumulator state is threaded through the step with buffer donation
(`donate_argnums`): each batch updates it in place, peak device memory is
O(state + tb * n + fill_chunk * n^2) regardless of how many test batches are
streamed, and the test set may live on the host (each batch is transferred
as it is consumed). Every backend donates, the CPU included, so the CPU
tests drive the same in-place steps the chip runs.

Every step carries a per-point validity mask folded into the contribution
`u` (every method's update is linear in `u`, so a masked-out point
contributes exactly zero): a ragged trailing batch is PADDED to the compiled
batch shape by `pad_test_batch` instead of tracing a second
shape-specialized executable.

    from repro.kernels.sti_pipeline import fused_sti_knn_interactions
    phi = fused_sti_knn_interactions(x_train, y_train, x_test, y_test, k=5)

`make_fused_step` / `make_point_step` expose the donated steps themselves
for callers that drive their own stream (the serving engine, sessions);
`prepare_stream_step` is the method-generic front door (tuple-state
contract) that `ValuationSession` drives.

`make_sharded_step` / `prepare_sharded_step` / `sharded_sti_knn_interactions`
are the multi-device form (DESIGN.md Sec. 10): the test stream is row-sharded
over a 1-D `compat.shard_map` mesh, the accumulator is sharded by ROW BLOCKS
of the (n, n) matrix — (n/D, n) per device, so peak accumulator memory falls
as 1/D — and the only per-step collective is an all-gather of the small
(tb, n) g/rank tables; the row blocks are complete sums, so finalize copies
each block to the host and needs no collective over the matrix. Vector-state
methods shard the (n,) accumulator the same way the interaction diagonal
always was (`make_sharded_point_step`): the per-step collective is one O(n)
psum_scatter, never anything n-squared.
"""

from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.core.sti_knn import (
    InteractionMode,
    pairwise_sq_dists,
    resolve_fill,
    resolve_rect_fill,
    superdiagonal_g,
)
from repro.kernels.stream_kernels import (
    AccumulatorSpec,
    UpdateKernel,
    accumulator_spec,
    make_refold_kernel,
    make_update_kernel,
)

__all__ = [
    "fused_sti_knn_interactions",
    "make_fused_step",
    "prepare_fused_step",
    "pad_test_batch",
    "make_point_step",
    "make_approx_point_step",
    "make_approx_interaction_step",
    "ApproxPairAccumulator",
    "make_rank_step",
    "make_refold_step",
    "prepare_refold_step",
    "prepare_stream_step",
    "make_sharded_step",
    "make_sharded_point_step",
    "prepare_sharded_step",
    "prepare_sharded_stream_step",
    "step_gather_bytes",
    "sharded_sti_knn_interactions",
    "stream_point_values",
    "resolve_distance",
]


def resolve_distance(
    distance: str,
    t: int,
    n: int,
    d: int,
    *,
    distance_params: Optional[dict] = None,
    autotune: bool = False,
) -> tuple[str, tuple]:
    """Resolve "auto" | "xla" | "pallas" | "pallas_interpret" to a concrete
    distance implementation name plus hashable static params (autotuned
    Pallas block shapes on TPU, the XLA expansion elsewhere)."""
    params = dict(distance_params or {})
    if distance == "auto":
        from repro.kernels.autotune import best_distance

        name, tuned = best_distance(t, n, d, allow_tune=autotune)
        tuned.update(params)
        # block params are a hint for the Pallas path: dropped, not an
        # error, when "auto" resolves to the XLA expansion off-TPU
        params = {} if name == "xla" else tuned
        distance = name
    if distance not in ("xla", "pallas", "pallas_interpret"):
        raise ValueError(f"unknown distance impl: {distance!r}")
    if distance == "xla":
        if params:
            raise ValueError(
                f"distance='xla' takes no params, got {sorted(params)}"
            )
    else:
        from repro.core.sti_knn import _accepted_params
        from repro.kernels.distance import distance_pallas

        bad = set(params) - set(_accepted_params(distance_pallas, params))
        if bad:
            raise ValueError(
                f"distance={distance!r} does not accept params {sorted(bad)}"
            )
    return distance, tuple(sorted(params.items()))


def _distance_fn(name: str, static: tuple) -> Callable:
    if name == "xla":
        return pairwise_sq_dists
    from repro.kernels.distance import distance_pallas

    kw = dict(static)
    if name == "pallas_interpret":
        kw["interpret"] = True
    return functools.partial(distance_pallas, **kw)


def pad_test_batch(xb, yb, tb: int):
    """Pad a (possibly ragged) test batch to exactly `tb` rows and return
    `(xb, yb, mask)` with mask 1.0 on real points, 0.0 on padding.

    The step folds the mask into `u`; `g`, the fill, and the diagonal term
    are all linear in `u`, so padded points contribute exactly zero and ONE
    compiled step serves every batch size <= tb (no trailing-batch retrace).
    """
    xb = jnp.asarray(xb)
    yb = jnp.asarray(yb)
    b = xb.shape[0]
    if b > tb:
        raise ValueError(f"batch of {b} test points exceeds test_batch={tb}")
    mask = jnp.ones((b,), jnp.float32)
    if b == tb:
        return xb, yb, mask
    pad = tb - b
    return (
        jnp.pad(xb, ((0, pad), (0, 0))),
        jnp.pad(yb, ((0, pad),)),
        jnp.pad(mask, ((0, pad),)),
    )


def _stream_body(kernel: UpdateKernel, k: int, dist_fn: Callable) -> Callable:
    """The ONE generic per-batch step body every method instantiates:

        body(state, xb, yb, mask, x_train, y_train) -> state

    distance -> stable sort carrying the labels -> sorted label match ->
    method contribution (mask folded in) -> optional `superdiagonal_g` ->
    the method's registered update kernel. The per-method parts live
    entirely in `kernel` (repro.kernels.stream_kernels); everything here is
    shared.

    Every permutation of a (tb, n) array is a sort operand, never a gather
    or scatter: the one stable sort of `d2` carries the train indices
    (`order`, exactly `jnp.argsort(d2, stable=True)`) and the train labels,
    and the update kernels return to train coordinates with
    `sti_knn.to_train`, one sort keyed by `order` (DESIGN.md Sec. 12).

    Each stage runs under a `jax.named_scope` (distance, sort,
    label_gather, contrib, recurrence, update; the update kernels add
    rank, to_train, fill and collective inside `update`), so every device
    op of the step carries its stage in its `op_name` metadata. The names
    are the benchmark's layer names (bench/scopes.json): keep them stable.
    """

    def body(state, xb, yb, mask, x_train, y_train):
        with jax.named_scope("distance"):
            d2 = dist_fn(xb, x_train)                       # (tb, n) on-chip
        with jax.named_scope("sort"):
            _, order, y_sorted = jax.lax.sort(              # (tb, n) each
                (d2,
                 jax.lax.broadcasted_iota(jnp.int32, d2.shape, 1),
                 jnp.broadcast_to(y_train, d2.shape)),
                dimension=1, is_stable=True, num_keys=1,
            )
        with jax.named_scope("label_gather"):
            match = (y_sorted == yb[:, None]).astype(jnp.float32)
        with jax.named_scope("contrib"):
            u = kernel.contrib(d2, order, match, mask)
        g = None
        if kernel.needs_g:
            with jax.named_scope("recurrence"):
                g = superdiagonal_g(u, k, mode=kernel.g_mode)
        with jax.named_scope("update"):
            return kernel.update(state, u, g, order, mask)

    return body


@functools.lru_cache(maxsize=None)
def make_fused_step(
    k: int,
    mode: InteractionMode = "sti",
    fill: str = "chunked",
    fill_static: tuple = (),
    distance: str = "xla",
    distance_static: tuple = (),
    donate: bool = True,
) -> Callable:
    """Build the jitted fused interaction step (a thin instantiation of the
    generic `_stream_body` with the "sti"/"sii" update kernel):

        step(acc, diag, xb, yb, mask, x_train, y_train) -> (acc, diag)

    acc (n, n) f32 and diag (n,) f32 are donated (updated in place);
    xb/yb/mask is one (tb, d)/(tb,)/(tb,) test batch (`pad_test_batch`
    builds the mask). The fill accumulates
    through the in-place registry form where one exists (no `acc + fill`
    temporary), and the diagonal term reuses the fill stage's `u` (gathered
    back to train coordinates) instead of re-broadcasting the (tb, n) label
    comparison. All four pipeline stages trace into the one XLA program.
    Cached per static configuration, so repeated streaming runs reuse the
    executable.

    `fill="megakernel"` swaps the whole three-stage body for the fully
    fused single-`pallas_call` step (`repro.kernels.sti_megakernel`):
    identical contract, one kernel per batch, `fill_static` carrying the
    tile shapes / compute dtype instead of fill chunking (the `distance`
    pair is ignored -- the distance stage lives inside the kernel).
    """
    if fill == "megakernel":
        from repro.kernels.sti_megakernel import sti_megakernel

        params = dict(fill_static)

        def mega_step(acc, diag, xb, yb, mask, x_train, y_train):
            return sti_megakernel(
                acc, diag, xb, yb, mask, x_train, y_train,
                k=int(k), mode=mode, **params,
            )

        return jax.jit(mega_step, donate_argnums=(0, 1) if donate else ())
    body = _stream_body(
        make_update_kernel(mode, k, fill=fill, fill_static=fill_static),
        int(k), _distance_fn(distance, distance_static),
    )

    def step(acc, diag, xb, yb, mask, x_train, y_train):
        return body((acc, diag), xb, yb, mask, x_train, y_train)

    return jax.jit(step, donate_argnums=(0, 1) if donate else ())


@functools.lru_cache(maxsize=None)
def make_point_step(
    method: str,
    k: int,
    method_static: tuple = (),
    distance: str = "xla",
    distance_static: tuple = (),
    donate: bool = True,
    fill: Optional[str] = None,
    fill_static: tuple = (),
) -> Callable:
    """Build the jitted vector-accumulator step for a point-value method
    ("knn_shapley", "wknn", "loo"):

        step(vec, xb, yb, mask, x_train, y_train) -> vec

    vec (n,) f32 accumulates the SUM of per-test-point values (finalize
    divides by t); it is donated exactly like the interaction
    accumulators. `method_static` is the hashable method-option tuple (e.g.
    (("weights", "rbf"),) for wknn). Same generic body, same pad/mask
    contract, same executable-per-configuration caching as the fused step.

    Point methods have no fill stage, but `fill="megakernel"` routes the
    step through the fused single-`pallas_call` kernel
    (`sti_megakernel.point_megakernel`) with `fill_static` carrying its
    tile shapes / compute dtype (the `distance` pair is then ignored).
    """
    if fill == "megakernel":
        from repro.kernels.sti_megakernel import point_megakernel

        params = dict(fill_static)
        opts = dict(method_static)

        def mega_step(vec, xb, yb, mask, x_train, y_train):
            return point_megakernel(
                vec, xb, yb, mask, x_train, y_train,
                method=method, k=int(k), opts=opts, **params,
            )

        return jax.jit(mega_step, donate_argnums=(0,) if donate else ())
    body = _stream_body(
        make_update_kernel(method, k, opts=dict(method_static)),
        int(k), _distance_fn(distance, distance_static),
    )

    def step(vec, xb, yb, mask, x_train, y_train):
        return body((vec,), xb, yb, mask, x_train, y_train)[0]

    return jax.jit(step, donate_argnums=(0,) if donate else ())


@functools.lru_cache(maxsize=None)
def make_rank_step(
    distance: str = "xla",
    distance_static: tuple = (),
) -> Callable:
    """Stage A of the incremental-mutation path: the jitted distance + sort
    prefix of the streaming step, split out so its outputs can be CACHED:

        rank(xb, x_train) -> (d2, order)

    d2 (tb, n) f32 squared distances, order (tb, n) int32 stable argsort
    (closest first). The online valuation service runs this once per cached
    test batch and then replays mutations through `make_refold_step`, which
    skips both the distance matmul and the sort. NOT donated: the outputs
    are long-lived cache entries, not streaming temporaries.
    """
    dist_fn = _distance_fn(distance, distance_static)

    def rank(xb, x_train):
        with jax.named_scope("distance"):
            d2 = dist_fn(xb, x_train)
        with jax.named_scope("sort"):
            return d2, jnp.argsort(d2, axis=-1, stable=True)

    return jax.jit(rank)


@functools.lru_cache(maxsize=None)
def make_refold_step(
    method: str,
    k: int,
    method_static: tuple = (),
    fill: str = "chunked",
    fill_static: tuple = (),
    donate: bool = True,
) -> Callable:
    """Stage B of the incremental-mutation path: the jitted refold of one
    CACHED test batch under a train-slot liveness mask (tuple-state):

        step(state, d2, order, yb, mask, y_train, keep) -> state

    `d2`/`order` come from `make_rank_step` (possibly captured against an
    older train-set snapshot); `keep` (n,) marks live slots. The body
    compacts the cached order against `keep` and runs the method's
    registered contrib/[g]/update closures (`stream_kernels.
    make_refold_kernel`), so a remove_points refold is EXACTLY the state a
    full recompute against the mutated train set would produce -- without
    touching the distance or sort stages. Only `state` is donated (the
    cached intermediates are reused across mutations).
    """
    if accumulator_spec(method).kind == "interaction":
        body = make_refold_kernel(
            method, int(k), fill=fill, fill_static=fill_static
        )
    else:
        body = make_refold_kernel(method, int(k), opts=dict(method_static))

    def step(state, d2, order, yb, mask, y_train, keep):
        return tuple(body(state, d2, order, yb, mask, y_train, keep))

    return jax.jit(step, donate_argnums=(0,) if donate else ())


def prepare_refold_step(
    method: str,
    n: int,
    d: int,
    k: int,
    *,
    test_batch: int = 256,
    fill: str = "auto",
    fill_params: Optional[dict] = None,
    distance: str = "auto",
    distance_params: Optional[dict] = None,
    autotune: bool = False,
    method_opts: Optional[dict] = None,
) -> tuple[Callable, Callable, dict, "AccumulatorSpec"]:
    """Resolve the incremental-mutation pair for `method` and return
    `(refold_step, rank_step, resolved, spec)` (see `make_rank_step` /
    `make_refold_step`). Resolution mirrors `prepare_stream_step` -- same
    square fill registry for interaction methods, same distance registry --
    so the refold replays bit-for-bit what the live streaming step folds.
    Always single-device: sharded sessions gather their state dense, refold,
    and re-place (mutations are off the request hot loop)."""
    spec = accumulator_spec(method)
    tb = max(1, int(test_batch))
    dist_name, dist_static = resolve_distance(
        distance, tb, n, d, distance_params=distance_params,
        autotune=autotune,
    )
    if spec.kind == "interaction":
        fill_name, fill_static = resolve_fill(
            fill, n, tb, fill_params=fill_params, autotune=autotune
        )
        refold = make_refold_step(
            method, int(k), (), fill_name, fill_static
        )
        resolved = {"fill": fill_name, "distance": dist_name}
    else:
        refold = make_refold_step(
            method, int(k), _method_static(method_opts)
        )
        resolved = {"fill": None, "distance": dist_name}
    return refold, make_rank_step(dist_name, dist_static), resolved, spec


def _method_static(method_opts: Optional[dict]) -> tuple:
    """Method options as the hashable static tuple the step caches key on."""
    return tuple(sorted((method_opts or {}).items()))


def _tuple_state(inner: Callable) -> Callable:
    """Adapt an unpacked-state step (acc, diag, ...) to the uniform
    tuple-state contract `step(state, *args) -> state`.

    The wrapped jitted step stays reachable as `step.inner` so callers
    (the contract checker's retrace sentinel, the retrace regression
    test) can inspect its compilation cache without unwrapping closures.
    """

    def step(state, *args):
        return tuple(inner(*state, *args))

    step.inner = inner
    return step


def _vector_state(inner: Callable) -> Callable:
    """Adapt a bare-vector step (vec, ...) to the uniform tuple-state
    contract `step(state, *args) -> state`. The jitted step stays
    reachable as `step.inner` (see `_tuple_state`)."""

    def step(state, *args):
        return (inner(state[0], *args),)

    step.inner = inner
    return step


def _resolve_megakernel(
    fill: str, n: int, d: int, k: int, tb: int,
    fill_params: Optional[dict], autotune: bool,
) -> Optional[tuple]:
    """Resolve whether a step should run as the fused megakernel: returns
    its static-param tuple, or None for the three-stage path.

    `fill="megakernel"` forces it (fill_params carry tile shapes / compute
    dtype). `fill="auto"` consults the step-level autotune triad
    (`autotune.best_megastep`, platform-keyed): the megakernel is picked
    only where a tuned run measured it faster than the three-stage step --
    so interpret-mode CPU runs keep today's default unless a TPU tuning
    says otherwise, which is exactly the "selectable via autotune"
    contract."""
    from repro.kernels.sti_megakernel import megakernel_static

    if fill == "megakernel":
        return megakernel_static(fill_params)
    if fill != "auto":
        return None
    from repro.kernels.autotune import best_megastep

    name, params = best_megastep(n, tb, d, int(k), allow_tune=autotune)
    if name != "megakernel":
        return None
    merged = dict(params)
    merged.update(fill_params or {})
    return megakernel_static(merged)


def prepare_fused_step(
    n: int,
    d: int,
    k: int,
    *,
    mode: InteractionMode = "sti",
    test_batch: int = 256,
    fill: str = "auto",
    fill_params: Optional[dict] = None,
    distance: str = "auto",
    distance_params: Optional[dict] = None,
    autotune: bool = False,
) -> tuple[Callable, dict]:
    """Resolve fill/distance for an (n, d) train set streamed in batches of
    `test_batch` and return `(step, resolved)`:

        step(acc, diag, xb, yb, mask, x_train, y_train) -> (acc, diag)

    plus a dict naming the concrete {"fill", "distance"} implementations (for
    result metadata). This is the per-batch unit `ValuationSession` drives for
    unbounded test streams; `fused_sti_knn_interactions` below is the one-shot
    wrapper over the same step.

    `fill="megakernel"` (or an `auto` resolution whose autotune cache says
    the megakernel wins) returns the fused single-`pallas_call` step;
    resolved reports `{"fill": "megakernel", "distance": "fused"}` since
    the distance stage is inside the kernel.
    """
    tb = max(1, int(test_batch))
    mega = _resolve_megakernel(fill, n, d, k, tb, fill_params, autotune)
    if mega is not None:
        step = make_fused_step(int(k), mode, "megakernel", mega)
        return step, {"fill": "megakernel", "distance": "fused"}
    fill_name, fill_static = resolve_fill(
        fill, n, tb, fill_params=fill_params, autotune=autotune
    )
    dist_name, dist_static = resolve_distance(
        distance, tb, n, d, distance_params=distance_params, autotune=autotune
    )
    step = make_fused_step(
        int(k), mode, fill_name, fill_static, dist_name, dist_static
    )
    resolved = {"fill": fill_name, "distance": dist_name}
    return step, resolved


def prepare_stream_step(
    method: str,
    n: int,
    d: int,
    k: int,
    *,
    test_batch: int = 256,
    fill: str = "auto",
    fill_params: Optional[dict] = None,
    distance: str = "auto",
    distance_params: Optional[dict] = None,
    autotune: bool = False,
    method_opts: Optional[dict] = None,
) -> tuple[Callable, dict, "AccumulatorSpec"]:
    """Method-generic form of `prepare_fused_step`: resolve the concrete
    implementations for ANY registered streaming method and return
    `(step, resolved, spec)` with the uniform tuple-state contract

        step(state, xb, yb, mask, x_train, y_train) -> state

    where `state` is `spec.init(n)`-shaped ((acc, diag) for interaction
    methods, (vec,) for point-value methods). Interaction methods resolve
    through the fill registry exactly as `prepare_fused_step`; point methods
    have no fill stage (resolved["fill"] is None) but share the distance
    resolution -- EXCEPT `fill="megakernel"`, which routes ANY method
    through its fused single-`pallas_call` step (resolved["fill"] then
    reports "megakernel" and the distance stage lives inside the kernel).
    `method_opts` carries method statics such as the wknn weight kind.
    This is the per-batch unit `ValuationSession` drives.
    """
    spec = accumulator_spec(method)
    tb = max(1, int(test_batch))
    if spec.kind == "interaction":
        inner, resolved = prepare_fused_step(
            n, d, k, mode=method, test_batch=tb, fill=fill,
            fill_params=fill_params, distance=distance,
            distance_params=distance_params, autotune=autotune,
        )
        return _tuple_state(inner), dict(resolved), spec
    if fill == "megakernel":
        from repro.kernels.sti_megakernel import megakernel_static

        inner = make_point_step(
            method, int(k), _method_static(method_opts),
            fill="megakernel", fill_static=megakernel_static(fill_params),
        )
        resolved = {"fill": "megakernel", "distance": "fused"}
        return _vector_state(inner), resolved, spec
    dist_name, dist_static = resolve_distance(
        distance, tb, n, d, distance_params=distance_params,
        autotune=autotune,
    )
    inner = make_point_step(
        method, int(k), _method_static(method_opts), dist_name, dist_static,
    )
    return _vector_state(inner), {"fill": None, "distance": dist_name}, spec


def stream_point_values(
    method: str,
    x_train: jnp.ndarray,
    y_train: jnp.ndarray,
    x_test: jnp.ndarray,
    y_test: jnp.ndarray,
    k: int,
    *,
    test_batch: int = 512,
    fill: Optional[str] = None,
    fill_params: Optional[dict] = None,
    distance: str = "xla",
    distance_params: Optional[dict] = None,
    method_opts: Optional[dict] = None,
    autotune: bool = False,
) -> jnp.ndarray:
    """(n,) per-point values of `method` ("knn_shapley" | "wknn" | "loo"),
    averaged over the test set, via the generic streaming pipeline.

    One-shot twin of `fused_sti_knn_interactions` for vector-state methods:
    streams ceil(t / test_batch) donated steps, pads the ragged trailing
    batch with a zero validity mask (exact -- every update kernel is linear
    in the masked contribution), and divides by t at the end.
    `fill="megakernel"` routes the step through the fused single-kernel
    path (point methods otherwise have no fill stage). The public
    `knn_shapley_values` / `wknn_shapley_values` / `loo_values` functions
    are thin wrappers over this driver.
    """
    spec = accumulator_spec(method)
    if spec.kind != "point":
        raise ValueError(
            f"method {method!r} streams {spec.kind} state, not point "
            f"values; use fused_sti_knn_interactions / a ValuationSession "
            f"for interaction methods"
        )
    if x_train.ndim != 2 or x_test.ndim != 2:
        raise ValueError("features must be (num_points, dim)")
    if k < 1:
        raise ValueError("k must be >= 1")
    n, d = x_train.shape
    t = x_test.shape[0]
    if t < 1:
        raise ValueError("need at least one test point")
    tb = max(1, min(int(test_batch), t))
    step, _, spec = prepare_stream_step(
        method, n, d, k, test_batch=tb, fill=fill or "auto",
        fill_params=fill_params, distance=distance,
        distance_params=distance_params, autotune=autotune,
        method_opts=method_opts,
    )
    state = spec.init(n)
    x_train = jnp.asarray(x_train)
    y_train = jnp.asarray(y_train)
    for start in range(0, t, tb):
        xb, yb, mask = pad_test_batch(
            jnp.asarray(x_test[start : start + tb]),
            jnp.asarray(y_test[start : start + tb]),
            tb,
        )
        state = step(state, xb, yb, mask, x_train, y_train)
    return spec.result_arrays(state, t)["point_values"]


def fused_sti_knn_interactions(
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
    distance: str = "auto",
    distance_params: Optional[dict] = None,
    autotune: bool = False,
) -> jnp.ndarray:
    """STI-KNN via the fused streaming pipeline; same contract as
    `repro.core.sti_knn_interactions` ((n, n) matrix, diagonal = main terms).

    Streams ceil(t / test_batch) donated steps; a trailing partial batch is
    PADDED to the compiled batch shape with a zero validity mask (exact --
    masked points contribute nothing), so one executable serves every batch
    and t need not divide test_batch.
    """
    if x_train.ndim != 2 or x_test.ndim != 2:
        raise ValueError("features must be (num_points, dim)")
    if k < 1:
        raise ValueError("k must be >= 1")
    n, d = x_train.shape
    t = x_test.shape[0]
    if t < 1:
        raise ValueError("need at least one test point")
    tb = max(1, min(int(test_batch), t))
    # autotune keys use the executed (tb, n) slice shape, not the total t
    step, _ = prepare_fused_step(
        n, d, k, mode=mode, test_batch=tb, fill=fill, fill_params=fill_params,
        distance=distance, distance_params=distance_params, autotune=autotune,
    )
    acc = jnp.zeros((n, n), jnp.float32)
    diag = jnp.zeros((n,), jnp.float32)
    x_train = jnp.asarray(x_train)
    y_train = jnp.asarray(y_train)
    for start in range(0, t, tb):
        xb, yb, mask = pad_test_batch(
            jnp.asarray(x_test[start : start + tb]),
            jnp.asarray(y_test[start : start + tb]),
            tb,
        )
        acc, diag = step(acc, diag, xb, yb, mask, x_train, y_train)
    phi = acc / t
    return jnp.fill_diagonal(phi, diag / t, inplace=False)


# ------------------------------------------------------------------- approx
# engine="approx" (DESIGN.md Sec. 16): the steps below swap the dense
# (tb, n) distance row for the LSH candidate stage
# (`repro.kernels.ann.topm_candidates`), run the per-method recurrences on
# the (tb, m) candidate vectors (already sorted by exact distance, so
# candidate position == sorted coordinate), and land the results sparsely:
# a scatter-add for the (n,) point accumulators, flattened COO triplets
# for the interaction pairs (merged deterministically on the host by
# `ApproxPairAccumulator` so n=10^6 stores only pairs that ever co-occur
# in a candidate set). Each step also runs the recall probe on its first
# `probe` rows -- the measured matched prefix feeds the certified bounds
# of `repro.core.approx`.


def _probe_stats(probe: int, probe_k: int) -> Callable:
    """Bind the in-step recall probe: `run(cand, xb, x_train)` returns the
    (min(probe, tb),) matched-prefix and recall rows via
    `repro.kernels.ann.matched_prefix_and_recall` (empty arrays when
    probing is disabled). Probing the FIRST rows is sound because
    `pad_test_batch` puts real test points first."""
    from repro.kernels.ann import matched_prefix_and_recall

    def run(cand, xb, x_train):
        s = min(int(probe), cand.shape[0])
        if s <= 0:
            return (jnp.zeros((0,), jnp.int32), jnp.zeros((0,), jnp.float32))
        return matched_prefix_and_recall(
            cand[:s], xb[:s], x_train, int(probe_k)
        )

    return run


@functools.lru_cache(maxsize=None)
def make_approx_point_step(
    method: str,
    k: int,
    n: int,
    m: int,
    window: int,
    probe: int = 0,
    probe_k: int = 0,
    method_static: tuple = (),
    donate: bool = True,
) -> Callable:
    """Build the jitted approx step for a point-value method:

        step(vec, xb, yb, mask, x_train, y_train, tables)
            -> (vec, prefix, recall)

    vec (n,) f32 accumulates scatter-added candidate values (donated
    like the dense steps); `tables` is the `LSHTables` pytree the
    session built once per train set. Per batch: candidate top-m gather ->
    label match -> candidate-space recurrence
    (`stream_kernels.make_approx_values`) -> O(tb m) scatter-add, plus the
    `probe`-row recall probe (prefix/recall returned to the host caller).
    O(tb (L log n + L W d + m log m)) per batch instead of O(tb n d).
    Cached per static configuration.
    """
    from repro.kernels.ann import full_mean_sq_dist, topm_candidates
    from repro.kernels.stream_kernels import (
        make_approx_values,
        scatter_point_update,
    )

    values_fn = make_approx_values(method, k, opts=dict(method_static))
    probe_fn = _probe_stats(probe, probe_k)
    n, m, window = int(n), int(m), int(window)

    def step(vec, xb, yb, mask, x_train, y_train, tables):
        cand, d2m, valid = topm_candidates(xb, x_train, tables, m, window)
        match = (y_train[cand] == yb[:, None]).astype(jnp.float32)
        sigma2 = full_mean_sq_dist(xb, tables)
        vals = values_fn(d2m, match, valid, mask, sigma2)
        vec = scatter_point_update(vec, cand, vals, valid)
        prefix, recall = probe_fn(cand, xb, x_train)
        return vec, prefix, recall

    return jax.jit(step, donate_argnums=(0,) if donate else ())


@functools.lru_cache(maxsize=None)
def make_approx_interaction_step(
    mode: InteractionMode,
    k: int,
    n: int,
    m: int,
    window: int,
    probe: int = 0,
    probe_k: int = 0,
    donate: bool = True,
) -> Callable:
    """Build the jitted approx step for "sti"/"sii" interactions:

        step(diag, xb, yb, mask, x_train, y_train, tables)
            -> (diag, rows, cols, vals, prefix, recall)

    The DIAGONAL (paper Eq. 4: mean of u, a label comparison only) is
    accumulated exactly and densely -- it needs no distances at all. The
    off-diagonal pairs run the truncated recurrence
    (`repro.core.sti_knn.superdiagonal_g_topm`) on the (tb, m) candidate
    vector and come back as flattened (tb m^2,) COO triplets: pair value
    g[max(pos_a, pos_b)] gathered over candidate positions, with padded
    rows, invalid slots and the diagonal redirected to row index n (the
    host accumulator drops them). Peak step memory is O(tb m^2), so m
    bounds the quadratic term instead of n. Cached per static config.
    """
    from repro.core.sti_knn import superdiagonal_g_topm
    from repro.kernels.ann import topm_candidates

    probe_fn = _probe_stats(probe, probe_k)
    n, m, window = int(n), int(m), int(window)

    def step(diag, xb, yb, mask, x_train, y_train, tables):
        cand, d2m, valid = topm_candidates(xb, x_train, tables, m, window)
        match = (y_train[cand] == yb[:, None]).astype(jnp.float32)
        u = match * valid * (mask / k)[:, None]
        g = superdiagonal_g_topm(u, k, n, mode=mode)       # (tb, m)
        pos = jnp.arange(m)
        gm = g[:, jnp.maximum(pos[:, None], pos[None, :])]  # (tb, m, m)
        ok = (
            (valid[:, :, None] > 0)
            & (valid[:, None, :] > 0)
            & (pos[:, None] != pos[None, :])[None, :, :]
            & (mask > 0)[:, None, None]
        )
        rows = jnp.where(ok, cand[:, :, None], n)
        cols = jnp.where(ok, cand[:, None, :], n)
        vals = jnp.where(ok, gm, 0.0)
        # exact dense diagonal: mean-of-u main terms need only the labels
        dm = (y_train[None, :] == yb[:, None]).astype(jnp.float32)
        diag = diag + jnp.sum(dm * (mask / k)[:, None], axis=0)
        prefix, recall = probe_fn(cand, xb, x_train)
        return (
            diag,
            rows.reshape(-1).astype(jnp.int32),
            cols.reshape(-1).astype(jnp.int32),
            vals.reshape(-1),
            prefix,
            recall,
        )

    return jax.jit(step, donate_argnums=(0,) if donate else ())


class ApproxPairAccumulator:
    """Host-side deterministic COO accumulator for approx interactions.

    Each approx interaction step emits (tb m^2,) flattened (row, col, val)
    triplets; this class merges them into a sorted unique key list
    (key = row * n + col, int64) with `np.unique` + `np.add.at` -- a
    sequential, order-stable reduction, so two identical runs (and a
    checkpoint/restore) produce bit-identical sparse states regardless of
    device scatter ordering. Memory is O(pairs that ever co-occur in a
    candidate set), the whole point of the sparse approx path: STI at
    n=10^6 never materializes an (n, n) accumulator.
    """

    def __init__(self, n: int):
        """Empty accumulator for an n-point training set."""
        import numpy as np

        self.n = int(n)
        self._keys = np.zeros((0,), np.int64)
        self._vals = np.zeros((0,), np.float32)

    @property
    def nnz(self) -> int:
        """Number of distinct off-diagonal pairs stored so far."""
        return int(self._keys.shape[0])

    def add(self, rows, cols, vals) -> None:
        """Merge one step's flattened triplets; entries with row >= n (the
        step's invalid/diagonal redirect) are dropped."""
        import numpy as np

        rows = np.asarray(rows)
        cols = np.asarray(cols)
        vals = np.asarray(vals, np.float32)
        keep = rows < self.n
        new = rows[keep].astype(np.int64) * self.n + cols[keep].astype(
            np.int64
        )
        keys = np.concatenate([self._keys, new])
        allv = np.concatenate([self._vals, vals[keep]])
        uniq, inv = np.unique(keys, return_inverse=True)
        acc = np.zeros(uniq.shape[0], np.float32)
        np.add.at(acc, inv.reshape(-1), allv)
        self._keys, self._vals = uniq, acc

    def state(self) -> tuple:
        """(keys, vals) checkpoint arrays (sorted int64 keys, f32 sums)."""
        return self._keys.copy(), self._vals.copy()

    def load(self, keys, vals) -> None:
        """Restore from `state()` arrays (checkpoint resume)."""
        import numpy as np

        self._keys = np.asarray(keys, np.int64).copy()
        self._vals = np.asarray(vals, np.float32).copy()

    def to_dense(self, diag, t: int):
        """Densify into the (n, n) f32 interaction matrix: off-diagonal
        sums / t with the exactly-accumulated diagonal / t on the main
        diagonal -- the same finalize rule as
        `AccumulatorSpec.result_arrays`."""
        import numpy as np

        phi = np.zeros((self.n, self.n), np.float32)
        phi[self._keys // self.n, self._keys % self.n] = self._vals / t
        np.fill_diagonal(phi, np.asarray(diag, np.float32) / t)
        return jnp.asarray(phi)


# ------------------------------------------------------------------ sharded
@functools.lru_cache(maxsize=None)
def make_sharded_step(
    mesh,
    k: int,
    mode: InteractionMode = "sti",
    fill: str = "chunked",
    fill_static: tuple = (),
    distance: str = "xla",
    distance_static: tuple = (),
    axis: str = "shards",
    donate: bool = True,
) -> Callable:
    """Build the jitted multi-device step over a 1-D `mesh` (axis `axis`,
    D devices). GLOBAL contract identical to the fused step:

        step(acc, diag, xb, yb, mask, x_train, y_train) -> (acc, diag)

    but acc (n, n) is sharded P(axis, None) — each device OWNS an (n/D, n)
    row block and never materializes more — diag (n,) is sharded P(axis),
    and the (tb, d) test batch is row-sharded P(axis) (tb must be a multiple
    of D; `prepare_sharded_step` rounds it up and `pad_test_batch` masks the
    padding). Per device and step:

      1. distance/rank/g on the LOCAL (tb/D, n) test shard;
      2. all-gather of the small (tb, n) g / rank tables over `axis` plus a
         reduce-scatter of the (n,) diag partial (the only per-step
         collectives — O(tb n) bytes, never O(n^2));
      3. rectangular fill of the local row block with ALL tb test points,
         through the rect fill registry: `fill`/`fill_static` name a
         rectangular variant (the Pallas accumulate kernel on TPU, the XLA
         block scan as the universal fallback — `prepare_sharded_step`
         resolves them).

    Row blocks are therefore complete sums over every test point seen: no
    collective is needed at finalize, which copies each block to the host
    (`ValuationSession._host_state`). Accumulators
    are donated exactly like the fused step. Like `make_fused_step`
    this is a thin instantiation of the generic `_stream_body`, with the
    interaction kernel's shard_map-local update variant (`axis=` bound).

    `fill="megakernel"` keeps the step at exactly ONE `pallas_call` per
    device: the local body all-gathers the small (tb, d) test batch --
    O(tb d) collective bytes instead of the three-stage path's O(tb n)
    g/rank gather -- and runs the full fused kernel on its own (n/D, n)
    row block, passing `axis_index * n/D` as the kernel's rect row-index
    base (`row_offset`). Each device redundantly re-ranks the batch; that
    trade (t n d / D extra FLOPs for n-free collectives and single-kernel
    locality) is the Sec. 17 design argument.
    """
    if fill == "megakernel":
        from repro.kernels.sti_megakernel import sti_megakernel

        params = dict(fill_static)

        def step(acc, diag, xb, yb, mask, x_train, y_train):
            # local views: acc (nl, n), diag (nl,), xb (tb/D, d)
            nl = acc.shape[0]
            with jax.named_scope("collective"):
                xb_all = jax.lax.all_gather(xb, axis, axis=0, tiled=True)
                yb_all = jax.lax.all_gather(yb, axis, axis=0, tiled=True)
                mask_all = jax.lax.all_gather(mask, axis, axis=0, tiled=True)
            return sti_megakernel(
                acc, diag, xb_all, yb_all, mask_all, x_train, y_train,
                k=int(k), mode=mode,
                row_offset=jax.lax.axis_index(axis) * nl, **params,
            )
    else:
        body = _stream_body(
            make_update_kernel(mode, k, fill=fill, fill_static=fill_static,
                               axis=axis),
            int(k), _distance_fn(distance, distance_static),
        )

        def step(acc, diag, xb, yb, mask, x_train, y_train):
            # local views: acc (nl, n), diag (nl,), xb (tb/D, d), mask
            # (tb/D,)
            return body((acc, diag), xb, yb, mask, x_train, y_train)

    from jax.sharding import PartitionSpec as P

    from repro import compat

    # the step program is `jit_step`, as the single-device steps' are
    step = compat.shard_map(
        step,
        mesh=mesh,
        in_specs=(
            P(axis, None),   # acc row blocks
            P(axis),         # diag rows
            P(axis, None),   # test batch rows
            P(axis),         # test labels
            P(axis),         # validity mask
            P(None, None),   # x_train replicated
            P(None),         # y_train replicated
        ),
        out_specs=(P(axis, None), P(axis)),
        check_vma=False,
    )
    return jax.jit(step, donate_argnums=(0, 1) if donate else ())


@functools.lru_cache(maxsize=None)
def make_sharded_point_step(
    mesh,
    method: str,
    k: int,
    method_static: tuple = (),
    distance: str = "xla",
    distance_static: tuple = (),
    axis: str = "shards",
    donate: bool = True,
    fill: Optional[str] = None,
    fill_static: tuple = (),
) -> Callable:
    """Multi-device form of `make_point_step` over a 1-D `mesh`:

        step(vec, xb, yb, mask, x_train, y_train) -> vec

    with vec (n,) sharded P(axis) -- each device owns an (n/D,) row block,
    exactly the layout the interaction diagonal always used -- and the test
    batch row-sharded P(axis). Per device and step: distance/rank/values on
    the LOCAL (tb/D, n) slice, then ONE O(n) psum_scatter of the per-train
    partial sum (tiled block i lands on device i's rows). No O(n^2) state,
    no O(tb n) gather: point methods need no cross-device rank tables.

    `fill="megakernel"` mirrors the sharded interaction megakernel: gather
    the (tb, d) test batch, run ONE fused `pallas_call` per device against
    its (n/D,) vector rows with `axis_index * n/D` as the row base -- the
    psum_scatter disappears because every device folds the full batch.
    """
    if fill == "megakernel":
        from repro.kernels.sti_megakernel import point_megakernel

        params = dict(fill_static)
        opts = dict(method_static)

        def step(vec, xb, yb, mask, x_train, y_train):
            nl = vec.shape[0]
            with jax.named_scope("collective"):
                xb_all = jax.lax.all_gather(xb, axis, axis=0, tiled=True)
                yb_all = jax.lax.all_gather(yb, axis, axis=0, tiled=True)
                mask_all = jax.lax.all_gather(mask, axis, axis=0, tiled=True)
            return point_megakernel(
                vec, xb_all, yb_all, mask_all, x_train, y_train,
                method=method, k=int(k), opts=opts,
                row_offset=jax.lax.axis_index(axis) * nl, **params,
            )
    else:
        body = _stream_body(
            make_update_kernel(method, k, opts=dict(method_static),
                               axis=axis),
            int(k), _distance_fn(distance, distance_static),
        )

        def step(vec, xb, yb, mask, x_train, y_train):
            # local views: vec (n/D,), xb (tb/D, d), mask (tb/D,)
            return body((vec,), xb, yb, mask, x_train, y_train)[0]

    from jax.sharding import PartitionSpec as P

    from repro import compat

    # the step program is `jit_step`, as the single-device steps' are
    step = compat.shard_map(
        step,
        mesh=mesh,
        in_specs=(
            P(axis),         # vec rows
            P(axis, None),   # test batch rows
            P(axis),         # test labels
            P(axis),         # validity mask
            P(None, None),   # x_train replicated
            P(None),         # y_train replicated
        ),
        out_specs=P(axis),
        check_vma=False,
    )
    return jax.jit(step, donate_argnums=(0,) if donate else ())


def prepare_sharded_step(
    n: int,
    d: int,
    k: int,
    *,
    mesh=None,
    shards: Optional[int] = None,
    mode: InteractionMode = "sti",
    test_batch: int = 256,
    fill: str = "auto",
    fill_params: Optional[dict] = None,
    distance: str = "auto",
    distance_params: Optional[dict] = None,
    autotune: bool = False,
) -> tuple[Callable, dict, "jax.sharding.Mesh"]:
    """Resolve mesh/fill/distance for the sharded engine and return
    `(step, resolved, mesh)` where `resolved` records the concrete
    implementations plus {"shards", "test_batch"} (test_batch rounded UP to
    a multiple of the shard count so every device gets an equal test slice;
    the mask absorbs the difference).

    The local row-block update resolves against the RECTANGULAR fill
    registry (`core.sti_knn.resolve_rect_fill`): "auto" picks the Pallas
    accumulate kernel on TPU and the XLA block scan elsewhere (a Pallas
    request on a build without the kernels falls back to the scan), and the
    autotune lookup runs at the per-device (n/D, n) block shape under the
    `rows{R}`-segmented, device-count-keyed cache key, so sharded shapes
    tune independently of single-device ones."""
    from repro.distributed.sharding import shard_count, valuation_mesh

    if mesh is None:
        mesh = valuation_mesh(shard_count(n, shards))
    axis = mesh.axis_names[0]
    num = mesh.shape[axis]
    if n % num:
        raise ValueError(
            f"n={n} must divide evenly into {num} row shards "
            f"(per-device blocks are exactly (n/D, n))"
        )
    tb = max(1, int(test_batch))
    tb = -(-tb // num) * num
    tbl = tb // num
    if fill == "megakernel":
        from repro.kernels.sti_megakernel import megakernel_static

        mega = megakernel_static(fill_params)
        step = make_sharded_step(
            mesh, int(k), mode, "megakernel", mega, axis=axis,
        )
        resolved = {
            # NOT rect_-prefixed: "megakernel" is its own resolvable name
            # (session restore passes it straight back through here)
            "fill": "megakernel",
            "fill_params": dict(mega),
            "distance": "fused",
            "shards": int(num),
            "test_batch": int(tb),
        }
        return step, resolved, mesh
    # the local fill sees the per-device (n/D, n) row block and ALL tb
    # gathered test points; the distance stage runs on (tb/D, n) slices
    fill_name, fill_static = resolve_rect_fill(
        fill, n // num, n, tb, fill_params=fill_params, autotune=autotune
    )
    dist_name, dist_static = resolve_distance(
        distance, tbl, n, d, distance_params=distance_params, autotune=autotune
    )
    step = make_sharded_step(
        mesh, int(k), mode, fill_name, fill_static, dist_name, dist_static,
        axis=axis,
    )
    resolved = {
        # rect_ prefix: the name lives in the rectangular fill registry,
        # not the square one (session restore re-resolves such names)
        "fill": f"rect_{fill_name}",
        "fill_params": dict(fill_static),
        "distance": dist_name,
        "shards": int(num),
        "test_batch": int(tb),
    }
    return step, resolved, mesh


def prepare_sharded_stream_step(
    method: str,
    n: int,
    d: int,
    k: int,
    *,
    mesh=None,
    shards: Optional[int] = None,
    test_batch: int = 256,
    fill: str = "auto",
    fill_params: Optional[dict] = None,
    distance: str = "auto",
    distance_params: Optional[dict] = None,
    autotune: bool = False,
    method_opts: Optional[dict] = None,
) -> tuple[Callable, dict, "jax.sharding.Mesh", "AccumulatorSpec"]:
    """Method-generic form of `prepare_sharded_step`: resolve mesh plus
    concrete implementations for ANY streaming method and return
    `(step, resolved, mesh, spec)` with the tuple-state contract of
    `prepare_stream_step`. Interaction methods route through the
    rectangular fill registry exactly as before; point-value methods build
    the O(n)-collective vector step (`make_sharded_point_step`) and report
    resolved["fill"] = None. Both require n to divide evenly into the shard
    count (the per-device row blocks are exact) and round `test_batch` UP
    to a multiple of it (the validity mask absorbs the difference).
    """
    spec = accumulator_spec(method)
    if spec.kind == "interaction":
        inner, resolved, mesh = prepare_sharded_step(
            n, d, k, mesh=mesh, shards=shards, mode=method,
            test_batch=test_batch, fill=fill, fill_params=fill_params,
            distance=distance, distance_params=distance_params,
            autotune=autotune,
        )
        return _tuple_state(inner), resolved, mesh, spec
    from repro.distributed.sharding import shard_count, valuation_mesh

    if mesh is None:
        mesh = valuation_mesh(shard_count(n, shards))
    axis = mesh.axis_names[0]
    num = mesh.shape[axis]
    if n % num:
        raise ValueError(
            f"n={n} must divide evenly into {num} row shards "
            f"(per-device blocks are exactly (n/D,))"
        )
    tb = -(-max(1, int(test_batch)) // num) * num
    if fill == "megakernel":
        from repro.kernels.sti_megakernel import megakernel_static

        inner = make_sharded_point_step(
            mesh, method, int(k), _method_static(method_opts), axis=axis,
            fill="megakernel", fill_static=megakernel_static(fill_params),
        )
        resolved = {
            "fill": "megakernel",
            "distance": "fused",
            "shards": int(num),
            "test_batch": int(tb),
        }
        return _vector_state(inner), resolved, mesh, spec
    dist_name, dist_static = resolve_distance(
        distance, tb // num, n, d, distance_params=distance_params,
        autotune=autotune,
    )
    inner = make_sharded_point_step(
        mesh, method, int(k), _method_static(method_opts),
        dist_name, dist_static, axis=axis,
    )
    resolved = {
        "fill": None,
        "distance": dist_name,
        "shards": int(num),
        "test_batch": int(tb),
    }
    return _vector_state(inner), resolved, mesh, spec


def step_gather_bytes(spec: "AccumulatorSpec", resolved: dict, n: int,
                      d: int) -> int:
    """Bytes each device receives through one sharded step's all-gathers,
    from shapes, for a step `prepare_sharded_stream_step` resolved: every
    device gets the other D-1 devices' (tb/D)-row slices of what is
    gathered -- g (f32) and ranks (i32), 8 n bytes a test point, for the
    interaction methods; the test rows (f32 features, i32 label, f32 mask)
    for the megakernel; nothing for the point methods, whose one exchange
    is a reduce-scatter of an (n,) partial."""
    shards = int(resolved["shards"])
    per = int(resolved["test_batch"]) // shards
    if resolved["fill"] == "megakernel":
        row = 4 * d + 8
    elif spec.kind == "interaction":
        row = 8 * n
    else:
        return 0
    return (shards - 1) * per * row


def sharded_sti_knn_interactions(
    x_train: jnp.ndarray,
    y_train: jnp.ndarray,
    x_test: jnp.ndarray,
    y_test: jnp.ndarray,
    k: int,
    *,
    mode: InteractionMode = "sti",
    test_batch: int = 256,
    shards: Optional[int] = None,
    mesh=None,
    fill: str = "auto",
    fill_params: Optional[dict] = None,
    distance: str = "auto",
    distance_params: Optional[dict] = None,
    autotune: bool = False,
    return_info: bool = False,
):
    """STI-KNN on the sharded fused pipeline; same result contract as
    `sti_knn_interactions`. Falls back to the single-device fused pipeline
    when only one shard is usable (1 device, or shards=1). With
    `return_info=True` returns `(phi, info)` where info names the resolved
    implementations and shard count.

    Thin wrapper: drives a `ShardedValuationSession` over the whole test
    set, so device placement / padding / finalize logic lives in exactly
    one place (the session).
    """
    if x_train.ndim != 2 or x_test.ndim != 2:
        raise ValueError("features must be (num_points, dim)")
    if k < 1:
        raise ValueError("k must be >= 1")
    t = x_test.shape[0]
    if t < 1:
        raise ValueError("need at least one test point")
    from repro.core.session import ShardedValuationSession

    sess = ShardedValuationSession(
        x_train, y_train, shards=shards, mesh=mesh, k=k, mode=mode,
        test_batch=max(1, min(int(test_batch), t)), fill=fill,
        fill_params=fill_params, distance=distance,
        distance_params=distance_params, autotune=autotune,
    )
    phi = sess.update(x_test, y_test).finalize().phi
    if return_info:
        info = dict(sess._resolved)
        info.setdefault("shards", sess.shards)
        info.setdefault("test_batch", sess.test_batch)
        return phi, info
    return phi
