#!/usr/bin/env python3
"""Smoke run of the valuation path on TPU chips, in one process.

    python chip_smoke.py [--seed S]                 # one chip: phases A-C
    python chip_smoke.py --four-chips [--seed S]    # four chips: sharded sti

One chip:
  A  sti interactions through a `ValuationSession`: n=32,768, d=768, k=5,
     test_batch=256, four batches. Checks that the diagonal is finite and
     64 rows of the accumulator against the literal max-gather at full
     size, to <= 1e-5; prints the efficiency gap (see `_efficiency_gap`
     for why it is held to 1e-5 only in C).
  B  knn_shapley point values through a `ValuationSession`: n=50,000,
     d=2,048 (the CIFAR-10 shape), 10 classes, k=5. Checks finiteness and
     the Shapley efficiency gap.
  C  references on the chip: the main path against the `xla` fill with the
     same distances at n=2,048, and against the O(2^n) oracle at n=12, both
     to <= 1e-5, as is the efficiency gap at n=2,048; ranks equal to a
     stable `jnp.argsort` of exactly computed distances; one donated
     update, then `finalize()`, `checkpoint()` and `restore()`.

--four-chips (and nothing else): sti through a `ShardedValuationSession`
over 4 chips at the sizes of the benchmark's `sti-tinyimagenet`
configuration (n=100,000, d=768, 200 classes, k=5, data from
`bench/data.py`): every chip must hold an (n/4, n) row block. Two batches
are folded and `finalize()` assembles phi on the host: no chip's
`peak_bytes_in_use` may rise during it, and its 32 sampled rows and
diagonal must match the benchmark's plain reference divided by t within
the configuration's `rows_gap` and `diag_gap` limits. The host's peak RSS
is printed. The same session at n=8,192 is compared against the fused
single-chip step to <= 1e-5.

Every session resolves `fill="auto"`, `distance="auto"` with
`autotune=False` and no autotune cache, and the run checks that they
resolved to the TPU defaults. Any failed check or error ends the run with
a non-zero exit code; the last line of standard output is the JSON result
only when every phase passed. Without a TPU the run fails at once.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
TOL = 1e-5


def _check(ok, what: str) -> None:
    if not ok:
        raise RuntimeError(f"check failed: {what}")


def _log(msg: str) -> None:
    print(msg, flush=True)


def _peak_gib(dev) -> str:
    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "not reported" if peak is None else f"{peak / 2**30:.3f} GiB"


# ------------------------------------------------------------------ data
def _gaussian(seed: int, n: int, d: int, classes: int):
    """(n, d) f32 features and (n,) int32 labels, made on the device."""
    import jax

    kx, ky = jax.random.split(jax.random.key(seed))
    x = jax.random.normal(kx, (n, d), jax.numpy.float32)
    y = jax.random.randint(ky, (n,), 0, classes, jax.numpy.int32)
    return x, y


def _integer(seed: int, n: int, d: int, classes: int, bound: int = 400):
    """Integer features in [-bound, bound] (host numpy, f32).

    With d=16 and bound=400 every squared distance and partial sum is an
    integer below 2^24, so f32 computes it exactly in any summation order,
    while bf16 (8 significant bits) cannot hold the features: a distance
    that is not f32-exact changes the ranks."""
    import numpy as np

    rng = np.random.default_rng(seed)
    x = rng.integers(-bound, bound + 1, size=(n, d)).astype(np.float32)
    y = rng.integers(0, classes, size=(n,)).astype(np.int32)
    return x, y


def _v_full(x, y, xt, yt, k: int, dist, batch: int = 256) -> float:
    """v(N): the mean over test points of (label matches among the k
    nearest train points) / k, ranked by the session's own distance stage
    `dist` (name, static params) so both see the same neighbours."""
    import jax.numpy as jnp

    from repro.kernels.sti_pipeline import make_rank_step

    rank = make_rank_step(*dist)
    hits = 0.0
    for s in range(0, xt.shape[0], batch):
        near = rank(xt[s:s + batch], x)[1][:, :k]
        hits += float(jnp.sum(y[near] == yt[s:s + batch, None]))
    return hits / k / xt.shape[0]


# ------------------------------------------------------------- resolving
def _expect_defaults(n: int, d: int, tb: int, *, rows: int | None = None):
    """Resolve fill/distance as `fill="auto"`, `distance="auto"`,
    `autotune=False` do, and check they are the platform's defaults (the
    TPU ones: `main` runs only on a TPU)."""
    import jax

    from repro.core.sti_knn import resolve_fill, resolve_rect_fill
    from repro.kernels import autotune
    from repro.kernels.sti_pipeline import resolve_distance

    backend = jax.default_backend()
    if rows is None:
        fill = resolve_fill("auto", n, tb, autotune=False)
        want_fill = autotune.default_fill(backend)
    else:
        fill = resolve_rect_fill("auto", rows, n, tb, autotune=False)
        want_fill = autotune.default_rect_fill(backend)
    dist = resolve_distance("auto", tb if rows is None else tb // (n // rows),
                            n, d, autotune=False)
    want_dist = autotune.default_distance(backend)
    _check((fill[0], dict(fill[1])) == want_fill,
           f"fill resolved to {fill}, TPU default is {want_fill}")
    _check((dist[0], dict(dist[1])) == want_dist,
           f"distance resolved to {dist}, TPU default is {want_dist}")
    _log(f"resolved n={n} d={d} tb={tb}"
         f"{'' if rows is None else f' rows={rows}'}: fill={fill[0]} "
         f"{dict(fill[1])} distance={dist[0]} {dict(dist[1])}")
    return fill, dist


def _efficiency_gap(label: str, acc, diag, t: int, v_n: float) -> float:
    """|(sum(diag) + sum(upper pairs of acc)) / t - v(N)|: the STI
    efficiency gap `repro.launch.valuate` prints (`analysis.
    efficiency_gap` over phi), with rows reduced on the device and the n
    row sums added on the host in f64.

    In f32 the identity is ill-conditioned at large n: it weights each g[j]
    by its j pairs, so g's f32 rounding (about 4e-9 absolute) is amplified
    by up to n^2 / 2. On the CPU, f32 g tables miss it by 1.2e-2 per test
    point at n=32,768 where f64 tables hit it to 1.5e-11; the gap is
    therefore printed at every size but held to TOL only at small n."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    @jax.jit
    def rows(a, g):
        return jnp.sum(jnp.triu(a, 1), axis=1) + g

    gap = abs(np.asarray(rows(acc, diag), np.float64).sum() / t - v_n)
    _log(f"{label} efficiency gap |sum(phi)-v(N)| = {gap:.3e} "
         f"(v(N)={v_n:.6f})")
    return gap


def _check_rows(label: str, acc, rows, x, y, xt, yt, k: int, dist,
                tb: int, sub: int = 16) -> None:
    """acc[rows] / t against a reference for those rows at full size: the
    session's own distance stage (`make_rank_step`) ranks each batch, and
    the `xla` rect fill (the literal max-gather) sums g[max(r_a, r_b)] over
    every test point, `sub` test points at a time."""
    import jax
    import jax.numpy as jnp

    from repro.core.sti_knn import (
        accumulate_rect_fill,
        ranks_from_order,
        superdiagonal_g,
    )
    from repro.kernels.sti_pipeline import make_rank_step

    rank = make_rank_step(*dist)
    rows = jnp.asarray(rows, jnp.int32)

    @jax.jit
    def fold(ref, xb, yb):
        order = rank(xb, x)[1]
        ranks = ranks_from_order(order)
        g = superdiagonal_g((y[order] == yb[:, None]) / k, k, mode="sti")
        for s0 in range(0, xb.shape[0], sub):
            r = ranks[s0:s0 + sub]
            ref = accumulate_rect_fill(ref, g[s0:s0 + sub], r[:, rows], r,
                                       "xla")
        return ref

    ref = jnp.zeros((rows.shape[0], x.shape[0]), jnp.float32)
    for s0 in range(0, xt.shape[0], tb):
        ref = fold(ref, xt[s0:s0 + tb], yt[s0:s0 + tb])
    t = xt.shape[0]
    err = float(jnp.max(jnp.abs(jnp.take(acc, rows, axis=0) - ref))) / t
    _log(f"{label} {rows.shape[0]} rows of acc vs the xla max-gather "
         f"reference at full size: max |diff| / t = {err:.3e}")
    _check(err <= TOL, f"rows vs reference {err:.3e} > {TOL}")


def _timed_updates(sess, xt, yt, tb: int, label: str) -> None:
    """Feed xt/yt in test_batch slices; print the first update (compile +
    one step) and the steady per-step time, both waited on."""
    import jax

    t0 = time.perf_counter()
    sess.update(xt[:tb], yt[:tb])
    jax.block_until_ready(sess._state)
    first = time.perf_counter() - t0
    steps = (xt.shape[0] - tb) // tb
    t0 = time.perf_counter()
    for s in range(tb, xt.shape[0], tb):
        sess.update(xt[s:s + tb], yt[s:s + tb])
    jax.block_until_ready(sess._state)
    steady = (time.perf_counter() - t0) / max(steps, 1)
    _log(f"{label}: first update (compile + 1 step) {first:.3f} s; "
         f"steady step {steady:.4f} s over {steps} steps "
         f"({tb / steady:.1f} test points/s); compile ~ "
         f"{max(first - steady, 0.0):.3f} s")


# ---------------------------------------------------------------- phases
def phase_interactions(seed: int, n: int = 32768, d: int = 768, k: int = 5,
                       tb: int = 256, batches: int = 4) -> None:
    """Phase A: sti through a ValuationSession at one-chip scale."""
    import jax
    import jax.numpy as jnp

    from repro.core.session import ValuationSession

    fill, dist = _expect_defaults(n, d, tb)
    x, y = _gaussian(seed, n, d, classes=10)
    xt, yt = _gaussian(seed + 1, tb * batches, d, classes=10)
    sess = ValuationSession(x, y, k=k, mode="sti", test_batch=tb,
                            fill="auto", distance="auto", autotune=False)
    _timed_updates(sess, xt, yt, tb, f"A sti n={n} d={d}")
    res = sess.finalize()
    _check(res.meta["fill"] == fill[0] and res.meta["distance"] == dist[0],
           f"session ran {res.meta['fill']}/{res.meta['distance']}")
    _check(res.phi.shape == (n, n), f"phi shape {res.phi.shape}")
    _check(bool(jnp.all(jnp.isfinite(jnp.diagonal(res.phi)))),
           "finite diagonal")
    del res
    acc, diag = sess._state
    _efficiency_gap("A", acc, diag, sess.t_seen,
                    _v_full(x, y, xt, yt, k, dist))
    _check_rows("A", acc, range(0, n, n // 64), x, y, xt, yt, k, dist, tb)
    _log(f"A peak device memory {_peak_gib(jax.devices()[0])}")


def phase_point_values(seed: int, n: int = 50000, d: int = 2048, k: int = 5,
                       tb: int = 256, batches: int = 4) -> None:
    """Phase B: knn_shapley through a ValuationSession at CIFAR-10 shape."""
    import jax
    import jax.numpy as jnp

    from repro.core.session import ValuationSession
    from repro.kernels.sti_pipeline import resolve_distance
    from repro.kernels import autotune

    dist = resolve_distance("auto", tb, n, d, autotune=False)
    want = autotune.default_distance(jax.default_backend())
    _check((dist[0], dict(dist[1])) == want,
           f"distance resolved to {dist}")
    _log(f"resolved n={n} d={d} tb={tb}: distance={dist[0]} {dict(dist[1])}")
    x, y = _gaussian(seed + 2, n, d, classes=10)
    xt, yt = _gaussian(seed + 3, tb * batches, d, classes=10)
    sess = ValuationSession(x, y, k=k, mode="knn_shapley", test_batch=tb,
                            distance="auto", autotune=False)
    _timed_updates(sess, xt, yt, tb, f"B knn_shapley n={n} d={d}")
    res = sess.finalize()
    _check(res.meta["distance"] == dist[0],
           f"session ran distance {res.meta['distance']}")
    vals = res.point_values
    _check(vals.shape == (n,), f"values shape {vals.shape}")
    _check(bool(jnp.all(jnp.isfinite(vals))), "finite point values")
    v_n = _v_full(x, y, xt, yt, k, dist)
    gap = float(res.efficiency_gap(v_n))
    _log(f"B efficiency gap |sum(values)-v(N)| = {gap:.3e} "
         f"(v(N)={v_n:.6f})")
    _check(gap <= TOL, f"efficiency gap {gap:.3e} > {TOL}")
    _log(f"B peak device memory {_peak_gib(jax.devices()[0])}")


def phase_references(seed: int, n: int = 2048, d: int = 16, k: int = 5,
                     tb: int = 256, t: int = 512) -> None:
    """Phase C: the main path against references, on the chip."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import get_method
    from repro.core.session import ValuationSession
    from repro.core.sti_baseline import brute_force_sti
    from repro.kernels.sti_pipeline import make_rank_step

    _, (dist_name, dist_static) = _expect_defaults(n, d, tb)
    x, y = _integer(seed + 4, n, d, classes=10)
    xt, yt = _integer(seed + 5, t, d, classes=10)

    # ranks of the main path's distance stage vs exact distances
    rank = make_rank_step(dist_name, dist_static)
    for s in range(0, t, tb):
        d2, order = rank(jnp.asarray(xt[s:s + tb]), jnp.asarray(x))
        diff = xt[s:s + tb, None, :].astype(np.int64) - x[None].astype(
            np.int64)
        exact = np.sum(diff * diff, axis=-1)
        _check(int(exact.max()) < 2**24, "exact distances fit f32")
        want = jnp.argsort(jnp.asarray(exact, jnp.float32), axis=-1,
                           stable=True)
        _check(bool(jnp.all(d2 == jnp.asarray(exact, jnp.float32))),
               "distances are f32-exact")
        _check(bool(jnp.all(order == want)), "ranks equal exact argsort")
    _log(f"C ranks: {t} test rows x {n} train points equal a stable "
         f"argsort of exact distances")

    # main path (donated step) vs the xla fill with the same distances
    main = ValuationSession(x, y, k=k, mode="sti", test_batch=tb,
                            fill="auto", distance="auto", autotune=False)
    main.update(xt, yt)
    phi = main.finalize().phi
    ref = ValuationSession(x, y, k=k, mode="sti", test_batch=64, fill="xla",
                           distance=dist_name,
                           distance_params=dict(dist_static))
    ref.update(xt, yt)
    err = float(jnp.max(jnp.abs(phi - ref.finalize().phi)))
    _log(f"C main path vs xla fill, n={n}: max |diff| = {err:.3e}")
    _check(err <= TOL, f"main vs xla fill {err:.3e} > {TOL}")
    gap = _efficiency_gap("C", *main._state, main.t_seen,
                          _v_full(x, y, xt, yt, k, (dist_name, dist_static)))
    _check(gap <= TOL, f"efficiency gap {gap:.3e} > {TOL}")

    # the donated state survives finalize -> checkpoint -> restore
    with tempfile.TemporaryDirectory(dir=ROOT) as tmp:
        path = main.checkpoint(Path(tmp) / "smoke")
        back = ValuationSession.restore(path, x, y, fill="auto",
                                        distance="auto")
    _check(back.t_seen == main.t_seen, "restored t_seen")
    _check(bool(jnp.all(back.finalize().phi == phi)),
           "restored state is bit-identical")
    main.update(xt[:tb], yt[:tb])
    back.update(xt[:tb], yt[:tb])
    _check(bool(jnp.all(main.finalize().phi == back.finalize().phi)),
           "restored session continues bit-identically")
    _log("C donated update -> finalize -> checkpoint -> restore: "
         "bit-identical")

    # the O(2^n) oracles at n=12
    xs, ys = _integer(seed + 6, 12, d, classes=3)
    xq, yq = _integer(seed + 7, 8, d, classes=3)
    got = get_method("sti")(xs, ys, xq, yq, k=k).phi
    want = brute_force_sti(xs, ys, xq, yq, k)
    err = float(np.max(np.abs(np.asarray(got) - want)))
    _log(f"C sti vs O(2^n) oracle, n=12: max |diff| = {err:.3e}")
    _check(err <= TOL, f"sti vs oracle {err:.3e} > {TOL}")
    knn = get_method("knn_shapley")
    err = float(jnp.max(jnp.abs(
        knn(xs, ys, xq, yq, k=k, distance="auto").point_values
        - knn(xs, ys, xq, yq, k=k, engine="oracle").point_values)))
    _log(f"C knn_shapley vs O(2^n) oracle, n=12: max |diff| = {err:.3e}")
    _check(err <= TOL, f"knn_shapley vs oracle {err:.3e} > {TOL}")
    _log(f"C peak device memory {_peak_gib(jax.devices()[0])}")


def phase_four_chips(seed: int, config: str = "sti-tinyimagenet",
                     tb: int = 256, batches: int = 2,
                     n_ref: int = 8192) -> None:
    """Sharded sti over four chips at a benchmark configuration's sizes,
    finalized on the host, and its reference at n_ref."""
    import resource

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.session import ShardedValuationSession, ValuationSession

    devs = jax.devices()
    _check(len(devs) == 4, f"--four-chips needs 4 devices, found {len(devs)}")

    sys.path.insert(0, str(ROOT / "bench"))
    import catalog
    import data

    cat = catalog.Catalog(ROOT)
    cfg = cat.config(config)
    n, d, k, shards = (int(cfg[key]) for key in ("n", "d", "k", "shards"))
    _check(shards == 4, f"{config} is sharded over {shards}, not 4")

    # reference: the same session at n_ref against the fused 1-chip step
    _expect_defaults(n_ref, d, tb, rows=n_ref // 4)
    x, y = _gaussian(seed + 8, n_ref, d, classes=10)
    xt, yt = _gaussian(seed + 9, 2 * tb, d, classes=10)
    sharded = ShardedValuationSession(x, y, shards=4, k=k, mode="sti",
                                      test_batch=tb, fill="auto",
                                      distance="auto", autotune=False)
    fused = ValuationSession(x, y, k=k, mode="sti", test_batch=tb,
                             fill="auto", distance="auto", autotune=False)
    _check(sharded.shards == 4, f"sharded over {sharded.shards}")
    err = float(jnp.max(jnp.abs(
        sharded.update(xt, yt).finalize().phi
        - fused.update(xt, yt).finalize().phi)))
    _log(f"4 sharded (4 chips) vs fused (1 chip), n={n_ref}: "
         f"max |diff| = {err:.3e}")
    _check(err <= TOL, f"sharded vs fused {err:.3e} > {TOL}")
    del sharded, fused, x, y, xt, yt

    # the configuration's size: (n/4, n) f32 row blocks, one per chip
    _expect_defaults(n, d, tb, rows=n // 4)
    x, y, xb, yb = data.mixture(seed, n=n, pool=cfg["test_pool"], d=d,
                                classes=cfg["classes"],
                                sep=cfg["class_sep"], tb=tb)
    sess = ShardedValuationSession(x, y, shards=4, k=k, mode="sti",
                                   test_batch=tb, fill="auto",
                                   distance="auto", autotune=False)
    _check(sess.shards == 4, f"sharded over {sess.shards}")
    _log(f"4 resolved session: {sess._resolved}")
    t0 = time.perf_counter()
    for b in range(batches):
        sess.update(xb[b], yb[b])
    jax.block_until_ready(sess._state)
    _log(f"4 sharded sti n={n} d={d}: {batches} batches of {tb} "
         f"(compile included) in {time.perf_counter() - t0:.3f} s")
    acc = sess._state[0]
    blocks = acc.addressable_shards
    shapes = sorted({tuple(s.data.shape) for s in blocks})
    owners = {s.device for s in blocks}
    _log(f"4 acc {acc.shape}: {len(blocks)} shards of {shapes} on "
         f"{len(owners)} devices")
    _check(len(owners) == 4 and shapes == [(n // 4, n)],
           f"each chip holds one ({n // 4}, {n}) block")
    del acc, blocks
    check = cat.method("sti").Check(cfg, seed, 4)
    want = check.reference(range(batches), x, y, xb, yb)

    # finalize: phi is assembled on the host, block by block
    def peaks():
        return [int((dev.memory_stats() or {}).get("peak_bytes_in_use", 0))
                for dev in devs]

    before = peaks()
    t0 = time.perf_counter()
    phi = sess.finalize().phi
    secs = time.perf_counter() - t0
    after = peaks()
    rss = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024
    _log(f"4 finalize: phi {phi.shape} {type(phi).__name__} in {secs:.3f} s; "
         f"host peak RSS {rss / 2**30:.3f} GiB")
    for dev, lo, hi in zip(devs, before, after):
        _log(f"4 peak device memory {dev}: {lo / 2**30:.4f} GiB before "
             f"finalize, {hi / 2**30:.4f} GiB after")
    _check(all(hi <= lo for lo, hi in zip(before, after)),
           "finalize raised a chip's peak_bytes_in_use")
    t = sess.t_seen
    got = {"rows": check._offdiag(phi[check.rows]),
           "diag": np.diagonal(phi).astype(np.float64)}
    numbers, _ = check.numbers(got, {key: v / t for key, v in want.items()})
    limits = cfg["check"]["limits"]
    for name, value in numbers.items():
        _log(f"4 finalize {name} vs the reference / t: {value:.3e} "
             f"(limit {limits[name]})")
        _check(value <= float(limits[name]),
               f"{name} {value:.3e} > {limits[name]}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded phase over four chips")
    args = ap.parse_args()

    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (jax found {dev.platform!r}); this run "
              f"needs one", file=sys.stderr)
        return 1
    # resolve with no autotune cache: nothing outside the checkout may
    # choose what runs
    tune = ROOT / ".jax_cache" / "no-autotune-cache.json"
    os.environ["REPRO_AUTOTUNE_CACHE"] = str(tune)
    _check(not tune.exists(), f"{tune} must not exist")
    _log(f"jax {jax.__version__}; {len(jax.devices())} x {dev.device_kind}; "
         f"compile cache {cache}")

    t0 = time.perf_counter()
    if args.four_chips:
        phase_four_chips(args.seed)
    else:
        phase_interactions(args.seed)
        phase_point_values(args.seed)
        phase_references(args.seed)
    _log(f"all phases passed in {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices()),
    }}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
