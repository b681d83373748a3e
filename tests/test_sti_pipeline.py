"""Fused streaming pipeline + extended fill registry: parity and autotuner."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import repro  # noqa: F401  (package import registers the Pallas fills)
from repro.core.sti_knn import (
    _FILL_FNS,
    _RECT_FILL_FNS,
    accumulate_fill,
    accumulate_rect_fill,
    ranks_from_distances,
    ranks_from_order,
    resolve_fill,
    resolve_rect_fill,
    sti_knn_interactions,
    sti_knn_matrix_one_test,
    superdiagonal_g,
)
from repro.core.sti_baseline import brute_force_sti
from repro.kernels import autotune as at
from repro.kernels.sti_pipeline import (
    fused_sti_knn_interactions,
    make_fused_step,
    pad_test_batch,
)


def _rand_problem(rng, n, t, dim=3, classes=2):
    return (
        jnp.asarray(rng.normal(size=(n, dim)).astype(np.float32)),
        jnp.asarray(rng.integers(0, classes, n).astype(np.int32)),
        jnp.asarray(rng.normal(size=(t, dim)).astype(np.float32)),
        jnp.asarray(rng.integers(0, classes, t).astype(np.int32)),
    )


def _rand_fill_inputs(rng, t, n):
    g = jnp.asarray(rng.normal(size=(t, n)).astype(np.float32))
    ranks = jnp.asarray(
        np.stack([rng.permutation(n) for _ in range(t)]).astype(np.int32)
    )
    return g, ranks


# ------------------------------------------------------------ fill registry
def test_registry_has_all_variants_at_package_import():
    """`import repro` alone must register the Pallas fills (satellite:
    fill="pallas" works out of the box)."""
    assert {"xla", "chunked", "onehot", "pallas", "pallas_interpret"} <= set(
        _FILL_FNS
    )


@pytest.mark.parametrize("fill,params", [
    ("chunked", {"chunk": 1}),
    ("chunked", {"chunk": 3}),      # t % chunk != 0 exercises padding
    ("chunked", {"chunk": 8}),
    ("onehot", {"chunk": 1}),
    ("onehot", {"chunk": 2}),
    ("pallas", {}),                 # auto-interprets off-TPU
    ("pallas_interpret", {"block_n": 16, "block_t": 2}),
])
@pytest.mark.parametrize("t,n", [(1, 16), (5, 37), (8, 64)])
def test_fill_variants_match_xla_reference(fill, params, t, n):
    rng = np.random.default_rng(t * 1000 + n)
    g, ranks = _rand_fill_inputs(rng, t, n)
    want = np.asarray(_FILL_FNS["xla"](g, ranks))
    got = np.asarray(_FILL_FNS[fill](g, ranks, **params))
    np.testing.assert_allclose(got, want, atol=1e-5)


def test_pallas_fill_through_core_matches_xla():
    rng = np.random.default_rng(2)
    x, y, xt, yt = _rand_problem(rng, 24, 9)
    a = sti_knn_interactions(x, y, xt, yt, 3, fill="xla")
    b = sti_knn_interactions(x, y, xt, yt, 3, fill="pallas")
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_resolve_fill_rejects_unknown():
    with pytest.raises(ValueError, match="unknown fill"):
        resolve_fill("nope", 8, 4)


def test_rank_helpers_agree():
    rng = np.random.default_rng(0)
    d2 = jnp.asarray(rng.random(size=(4, 11)).astype(np.float32))
    order = jnp.argsort(d2, axis=-1, stable=True)
    r1 = ranks_from_distances(d2)
    r2 = ranks_from_order(order)
    np.testing.assert_array_equal(np.asarray(r1), np.asarray(r2))
    # ranks invert the order permutation
    np.testing.assert_array_equal(
        np.take_along_axis(np.asarray(r1), np.asarray(order), 1),
        np.broadcast_to(np.arange(11), (4, 11)),
    )


# ------------------------------------------------------------ fused pipeline
@pytest.mark.parametrize("mode", ["sti", "sii"])
@pytest.mark.parametrize("n,t,tb", [
    (33, 17, 4),    # non-divisible t/tb and ragged n
    (16, 8, 8),     # single full batch
    (10, 3, 256),   # tb > t
])
def test_fused_matches_scan_engine(mode, n, t, tb):
    rng = np.random.default_rng(n * 10 + t)
    x, y, xt, yt = _rand_problem(rng, n, t, classes=3)
    want = sti_knn_interactions(x, y, xt, yt, 4, mode=mode, fill="xla")
    got = fused_sti_knn_interactions(
        x, y, xt, yt, 4, mode=mode, test_batch=tb
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


@pytest.mark.parametrize("fill,params", [
    ("chunked", {"chunk": 2}),
    ("onehot", {}),
    ("pallas", {}),
])
def test_fused_fill_variants(fill, params):
    rng = np.random.default_rng(7)
    x, y, xt, yt = _rand_problem(rng, 21, 11)
    want = sti_knn_interactions(x, y, xt, yt, 3, fill="xla")
    got = fused_sti_knn_interactions(
        x, y, xt, yt, 3, test_batch=4, fill=fill, fill_params=params
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


@pytest.mark.parametrize("n,t,k", [(8, 3, 2), (7, 2, 3)])
def test_fused_matches_bruteforce(n, t, k):
    rng = np.random.default_rng(n * 100 + t * 10 + k)
    x, y, xt, yt = _rand_problem(rng, n, t, dim=2)
    want = brute_force_sti(
        np.asarray(x), np.asarray(y), np.asarray(xt), np.asarray(yt), k
    )
    got = np.asarray(fused_sti_knn_interactions(x, y, xt, yt, k, test_batch=2))
    np.testing.assert_allclose(got, want, atol=1e-5)


def test_fused_single_test_point_matches_alg1():
    """For t=1 the off-diagonal of the fused output is Alg. 1's one-test
    matrix in train coordinates."""
    rng = np.random.default_rng(5)
    n, k = 12, 3
    x, y, xt, yt = _rand_problem(rng, n, 1)
    d2 = jnp.sum((x - xt[0]) ** 2, axis=-1)[None, :]
    order = np.asarray(jnp.argsort(d2, axis=-1, stable=True))[0]
    u_sorted = (np.asarray(y)[order] == int(yt[0])).astype(np.float32) / k
    m_sorted = np.asarray(sti_knn_matrix_one_test(jnp.asarray(u_sorted), k))
    want = np.zeros((n, n), np.float32)
    want[np.ix_(order, order)] = m_sorted
    got = np.asarray(fused_sti_knn_interactions(x, y, xt, yt, k))
    off = ~np.eye(n, dtype=bool)
    np.testing.assert_allclose(got[off], want[off], atol=1e-5)


def test_make_fused_step_streaming_accumulates():
    """Driving the donated step by hand over two half-batches equals the
    one-shot matrix (the serving-engine streaming contract)."""
    rng = np.random.default_rng(9)
    n, t, k = 18, 8, 3
    x, y, xt, yt = _rand_problem(rng, n, t)
    step = make_fused_step(k, "sti", "chunked", (("chunk", 1),))
    acc = jnp.zeros((n, n), jnp.float32)
    diag = jnp.zeros((n,), jnp.float32)
    ones = jnp.ones((4,), jnp.float32)
    for s in range(0, t, 4):
        acc, diag = step(acc, diag, xt[s:s + 4], yt[s:s + 4], ones, x, y)
    phi = jnp.fill_diagonal(acc / t, diag / t, inplace=False)
    want = sti_knn_interactions(x, y, xt, yt, k, fill="xla")
    np.testing.assert_allclose(np.asarray(phi), np.asarray(want), atol=1e-5)


def test_pad_test_batch_mask_contract():
    """pad_test_batch pads to the compiled shape; the zero mask makes padded
    points contribute exactly nothing through the step."""
    rng = np.random.default_rng(12)
    n, t, k, tb = 15, 3, 2, 8
    x, y, xt, yt = _rand_problem(rng, n, t)
    xb, yb, mask = pad_test_batch(xt, yt, tb)
    assert xb.shape == (tb, xt.shape[1]) and yb.shape == (tb,)
    np.testing.assert_array_equal(np.asarray(mask), [1, 1, 1, 0, 0, 0, 0, 0])
    step = make_fused_step(k, "sti", "chunked", (("chunk", 1),))
    acc, diag = step(
        jnp.zeros((n, n), jnp.float32), jnp.zeros((n,), jnp.float32),
        xb, yb, mask, x, y,
    )
    phi = jnp.fill_diagonal(acc / t, diag / t, inplace=False)
    want = sti_knn_interactions(x, y, xt, yt, k, fill="xla")
    np.testing.assert_allclose(np.asarray(phi), np.asarray(want), atol=1e-5)
    with pytest.raises(ValueError, match="exceeds test_batch"):
        pad_test_batch(xt, yt, 2)


def test_fused_single_executable_across_ragged_batches():
    """One compiled step serves full and trailing-partial batches: the
    trace cache of make_fused_step must not grow when t % tb != 0."""
    rng = np.random.default_rng(13)
    x, y, xt, yt = _rand_problem(rng, 20, 11)
    make_fused_step.cache_clear()
    want = sti_knn_interactions(x, y, xt, yt, 3, fill="xla")
    got = fused_sti_knn_interactions(
        x, y, xt, yt, 3, test_batch=4, fill="chunked", distance="xla"
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    assert make_fused_step.cache_info().currsize == 1
    step = make_fused_step(3, "sti", "chunked", (), "xla", ())
    # 11 = 2 full batches of 4 + one padded ragged batch through ONE jit
    assert step._cache_size() == 1


# ------------------------------------------------- permutations by sorting
STREAM_METHODS = ["sti", "sii", "knn_shapley", "wknn", "loo"]


def _scatter_ranks(order):
    """The inverse permutation by scatter, as the step computed it before
    it sorted instead."""
    t, n = order.shape
    return jnp.zeros_like(order).at[jnp.arange(t)[:, None], order].set(
        jnp.broadcast_to(jnp.arange(n, dtype=order.dtype), order.shape))


def _tied_problem(n=21, tb=6, dim=2, seed=4):
    """Integer coordinates from a small grid, so many squared distances tie
    exactly, two sentinel train slots (a removed point's coordinates and
    label), and a padded last test row."""
    from repro.kernels.stream_kernels import SENTINEL_COORD, SENTINEL_LABEL

    rng = np.random.default_rng(seed)
    x = rng.integers(-2, 3, size=(n, dim)).astype(np.float32)
    y = rng.integers(0, 3, size=n).astype(np.int32)
    for slot in (3, n - 5):
        x[slot], y[slot] = SENTINEL_COORD, SENTINEL_LABEL
    xt = rng.integers(-2, 3, size=(tb, dim)).astype(np.float32)
    yt = rng.integers(0, 3, size=tb).astype(np.int32)
    mask = np.ones(tb, np.float32)
    mask[-1] = 0.0
    return tuple(map(jnp.asarray, (x, y, xt, yt, mask)))


def _kernel(method, k, n, tb):
    from repro.kernels.stream_kernels import make_update_kernel

    fill, static = resolve_fill("auto", n, tb)
    return make_update_kernel(method, k, fill=fill, fill_static=static)


@pytest.mark.parametrize("method", STREAM_METHODS)
def test_step_permutes_by_sort_exactly(method, monkeypatch):
    """The step's sort carries the train indices and labels, and `to_train`
    sorts back by `order`: over exact ties and sentinel slots, `order` is
    the stable argsort, its `to_train` inverse the scatter's ranks, every
    `to_train` the gather through those ranks, and the state one step
    folds matches the gather-and-scatter step to 1 f32 ulp."""
    import dataclasses

    from repro.core.sti_knn import pairwise_sq_dists, to_train
    from repro.kernels import sti_pipeline, stream_kernels

    k, (x, y, xt, yt, mask) = 3, _tied_problem()
    n, tb = x.shape[0], xt.shape[0]
    kernel = _kernel(method, k, n, tb)
    spy = dataclasses.replace(kernel, update=lambda state, u, g, order, m: (
        kernel.update(state, u, g, order, m), order))
    body = sti_pipeline._stream_body(spy, k, pairwise_sq_dists)
    state0 = tuple(
        jnp.asarray(np.random.default_rng(1).normal(size=s), jnp.float32)
        for s in kernel.spec.shapes(n))
    got, order = jax.jit(body)(state0, xt, yt, mask, x, y)

    d2 = pairwise_sq_dists(xt, x)
    want_order = jnp.argsort(d2, axis=-1, stable=True)
    assert len(np.unique(np.asarray(d2))) < d2.size // 2   # many exact ties
    np.testing.assert_array_equal(np.asarray(order), np.asarray(want_order))
    ranks = _scatter_ranks(order)
    np.testing.assert_array_equal(np.asarray(ranks_from_order(order)),
                                  np.asarray(ranks))
    vals = jnp.asarray(np.random.default_rng(2).normal(size=order.shape),
                       jnp.float32)
    for xs in (vals, order):
        np.testing.assert_array_equal(
            np.asarray(to_train(order, xs)[0]),
            np.asarray(jnp.take_along_axis(xs, ranks, axis=-1)))

    # the same step the gather-and-scatter way: labels gathered through
    # `order`, values gathered back through the scattered ranks
    monkeypatch.setattr(stream_kernels, "to_train", lambda o, *xs: tuple(
        jnp.take_along_axis(v, _scatter_ranks(o), axis=-1) for v in xs))
    old_kernel = _kernel(method, k, n, tb)

    def old_body(state, xb, yb, m, x_train, y_train):
        d2 = pairwise_sq_dists(xb, x_train)
        order = jnp.argsort(d2, axis=-1, stable=True)
        match = (y_train[order] == yb[:, None]).astype(jnp.float32)
        u = old_kernel.contrib(d2, order, match, m)
        g = (superdiagonal_g(u, k, mode=old_kernel.g_mode)
             if old_kernel.needs_g else None)
        return old_kernel.update(state, u, g, order, m)

    want = jax.jit(old_body)(state0, xt, yt, mask, x, y)
    for a, b in zip(got, want):
        a, b = np.asarray(a), np.asarray(b)
        assert np.isfinite(a).all()
        assert np.abs(a - b).max() <= np.spacing(np.abs(b).max()), method


# ------------------------------------------------------- accumulate fills
@pytest.mark.parametrize("fill,static", [
    ("chunked", (("chunk", 2),)),
    ("onehot", (("chunk", 1),)),
    ("xla", ()),
    ("pallas", ()),
    ("pallas_interpret", (("block_n", 16), ("block_t", 2))),
])
def test_accumulate_fill_matches_additive(fill, static):
    """Every in-place accumulate form equals acc + fill(g, ranks) -- the
    aliased Pallas variant included."""
    rng = np.random.default_rng(21)
    g, ranks = _rand_fill_inputs(rng, 5, 37)
    acc = jnp.asarray(rng.normal(size=(37, 37)).astype(np.float32))
    want = np.asarray(acc) + np.asarray(_FILL_FNS["xla"](g, ranks))
    got = np.asarray(accumulate_fill(acc, g, ranks, fill, static))
    np.testing.assert_allclose(got, want, atol=1e-4)


# ------------------------------------------------------- rectangular fills
def _rect_window(ranks, off, count):
    from repro.kernels.sti_fill import rect_row_view

    return rect_row_view(ranks, off, count)


def test_rect_registry_has_all_variants_at_package_import():
    """`import repro` alone must register the rect Pallas fills (the sharded
    engine resolves fill="pallas" against this registry)."""
    assert {"xla", "chunked", "pallas", "pallas_interpret"} <= set(
        _RECT_FILL_FNS
    )


@pytest.mark.parametrize("fill,params", [
    ("chunked", {"chunk": 1}),
    ("chunked", {"chunk": 3}),      # t % chunk != 0 exercises padding
    ("pallas", {}),                 # auto-interprets off-TPU
    # block_rows=3 does not divide row_count=row window; block_cols=10 does
    # not divide n: both padded-block paths
    ("pallas_interpret", {"block_rows": 3, "block_cols": 10, "block_t": 2}),
])
@pytest.mark.parametrize("t,n,off,rows", [
    (5, 37, 8, 16),    # interior window, ragged n
    (4, 64, 56, 8),    # trailing window (off + rows == n)
    (3, 24, 0, 24),    # full-width window: rect == square
])
def test_rect_fill_variants_match_xla_reference(fill, params, t, n, off, rows):
    """Every rect fill equals the dense (t, rows, n)-materializing oracle on
    a row window of the global rank space, including non-divisible
    block_rows/row_count and ragged t."""
    rng = np.random.default_rng(t * 1000 + n + off)
    g, ranks = _rand_fill_inputs(rng, t, n)
    r_rows = _rect_window(ranks, off, rows)
    want = np.asarray(_RECT_FILL_FNS["xla"](g, r_rows, ranks))
    got = np.asarray(_RECT_FILL_FNS[fill](g, r_rows, ranks, **params))
    np.testing.assert_allclose(got, want, atol=1e-5)
    # and the row window of the square fill is the rect fill
    square = np.asarray(_FILL_FNS["xla"](g, ranks))
    np.testing.assert_allclose(want, square[off:off + rows], atol=1e-5)


@pytest.mark.parametrize("fill,static", [
    ("chunked", (("chunk", 2),)),
    ("xla", ()),
    ("pallas", ()),
    ("pallas_interpret", (("block_rows", 8), ("block_cols", 16))),
])
def test_accumulate_rect_fill_matches_additive(fill, static):
    """Every in-place rect accumulate form equals acc + rect_fill(...) --
    the aliased Pallas variant included."""
    rng = np.random.default_rng(31)
    g, ranks = _rand_fill_inputs(rng, 5, 37)
    r_rows = _rect_window(ranks, 5, 24)
    acc = jnp.asarray(rng.normal(size=(24, 37)).astype(np.float32))
    want = np.asarray(acc) + np.asarray(
        _RECT_FILL_FNS["xla"](g, r_rows, ranks)
    )
    got = np.asarray(accumulate_rect_fill(acc, g, r_rows, ranks, fill, static))
    np.testing.assert_allclose(got, want, atol=1e-4)


def test_resolve_rect_fill_contract():
    # unknown names raise (an unregistered Pallas name included)
    with pytest.raises(ValueError, match="unknown rect fill"):
        resolve_rect_fill("nope", 8, 64, 4)
    name, static = resolve_rect_fill("chunked", 8, 64, 4,
                                     fill_params={"chunk": 2})
    assert name == "chunked" and dict(static) == {"chunk": 2}
    with pytest.raises(ValueError, match="does not accept"):
        resolve_rect_fill("chunked", 8, 64, 4, fill_params={"block_rows": 8})
    # the heuristic default off-TPU is the XLA block scan
    name, _ = resolve_rect_fill("auto", 8, 64, 4)
    assert name in _RECT_FILL_FNS


def test_resolve_rect_fill_square_name_falls_back_with_warning():
    """A square-registry fill with no rect twin (e.g. "onehot" restored
    from a single-device checkpoint) must keep the sharded engine running
    on the XLA block scan, not raise."""
    with pytest.warns(UserWarning, match="no rectangular variant"):
        name, static = resolve_rect_fill("onehot", 8, 64, 4,
                                         fill_params={"chunk": 2})
    assert name == "chunked" and dict(static) == {"chunk": 2}


def test_rect_fill_candidates_preserve_aliasing():
    """TPU block candidates must keep the in-place path: every proposed
    block either divides its extent or clamps to it (rows=192 must NOT get
    block_rows=128, which would pad-copy the donated accumulator on every
    step)."""
    from repro.kernels.autotune import rect_fill_candidates

    for rows, n in ((192, 1536), (256, 2048), (96, 768)):
        for name, params in rect_fill_candidates(rows, n, 64, "tpu"):
            if name != "pallas":
                continue
            br, bc = params["block_rows"], params["block_cols"]
            assert rows % min(br, rows) == 0, (rows, params)
            assert n % min(bc, n) == 0, (n, params)
    # rows=192: 128 rejected (192 % 128 != 0), 256 clamps to 192 -> kept
    pal = [p for f, p in rect_fill_candidates(192, 1536, 64, "tpu")
           if f == "pallas"]
    assert pal and all(p["block_rows"] != 128 for p in pal)


def test_rect_autotune_key_carries_rows_segment(tmp_path):
    """Rect winners persist under rows{R}-segmented keys: an (8, 64) block
    must not share an entry with a (32, 64) block at the same n/t bucket."""
    from repro.kernels import autotune as at

    cache = str(tmp_path / "rect.json")
    name, params = at.autotune_rect_fill(8, 64, 6, path=cache)
    assert name in _RECT_FILL_FNS
    data = at._load(cache)
    (key,) = data
    assert key.startswith("rectfill:") and ":rows8:" in key
    assert at.lookup_rect_fill(8, 64, 6, path=cache) == (name, params)
    assert at.lookup_rect_fill(32, 64, 6, path=cache) is None
    assert at.best_rect_fill(8, 64, 6, path=cache) == (name, params)


# ---------------------------------------------------------------- autotuner
def test_autotune_fill_caches_and_resolves(tmp_path):
    cache = str(tmp_path / "autotune.json")
    name, params = at.autotune_fill(32, 6, path=cache)
    assert name in _FILL_FNS
    data = at._load(cache)
    assert len(data) == 1
    (key,) = data
    assert key.startswith("fill:")
    assert data[key]["fill"] == name
    assert data[key]["candidates"]
    # bucketed lookup: nearby sizes hit the same entry
    assert at.lookup_fill(30, 5, path=cache) == (name, params)
    assert at.best_fill(30, 5, path=cache) == (name, params)
    assert at.lookup_fill(300, 5, path=cache) is None


def test_best_fill_heuristic_on_miss(tmp_path):
    cache = str(tmp_path / "empty.json")
    name, params = at.best_fill(64, 4, path=cache)
    assert name in _FILL_FNS  # heuristic default, no tuning side effects
    assert not (tmp_path / "empty.json").exists()


def test_auto_fill_matches_reference(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "c.json"))
    rng = np.random.default_rng(3)
    x, y, xt, yt = _rand_problem(rng, 20, 7)
    want = sti_knn_interactions(x, y, xt, yt, 3, fill="xla")
    got = sti_knn_interactions(x, y, xt, yt, 3, fill="auto", autotune=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    assert (tmp_path / "c.json").exists()


def test_bucket_is_pow2_envelope():
    assert [at._bucket(x) for x in (1, 2, 3, 64, 65, 2048)] == [
        1, 2, 4, 64, 128, 2048,
    ]
