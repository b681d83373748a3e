"""Benchmark harness -- one function per paper table/claim.

Prints ``name,us_per_call,derived`` CSV rows (us_per_call measured on this
host's CPU; `derived` carries the table's scientific quantity). `--json`
additionally writes BENCH_sti_knn.json so the perf trajectory is tracked
across PRs (EXPERIMENTS.md records the history); each JSON row carries the
valuation `method` and `engine` it measured, so trajectories are comparable
per method/engine pair.

  PYTHONPATH=src python -m benchmarks.run            # all
  PYTHONPATH=src python -m benchmarks.run --only baselines --json
"""

from __future__ import annotations

import argparse
import json
import platform
import time

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import (
    sti_knn_interactions, knn_shapley_values, loo_values, analysis)
from repro.core.sti_baseline import brute_force_sti
from repro.data import make_circles, make_moons, flip_labels
from repro.launch.compile_cache import enable_compile_cache


def _time(fn, *args, reps=3, **kw):
    out = fn(*args, **kw)  # compile/warmup
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args, **kw)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e6  # us


def _problem(n, t, d=16, seed=0):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.normal(size=(n, d)).astype(np.float32)),
            jnp.asarray(rng.integers(0, 2, n).astype(np.int32)),
            jnp.asarray(rng.normal(size=(t, d)).astype(np.float32)),
            jnp.asarray(rng.integers(0, 2, t).astype(np.int32)))


# ---------------------------------------------------------------- table 1:
# the headline claim -- exact pair interactions O(2^n) -> O(t n^2)
def bench_speedup_vs_bruteforce():
    rows = []
    for n in (8, 10, 12):
        x, y, xt, yt = _problem(n, 4)

        def brute():
            return brute_force_sti(np.asarray(x), np.asarray(y),
                                   np.asarray(xt), np.asarray(yt), 3)

        t0 = time.perf_counter()
        brute()
        t_brute = (time.perf_counter() - t0) * 1e6
        t_fast = _time(sti_knn_interactions, x, y, xt, yt, 3)
        rows.append((f"speedup_bruteforce_n{n}", t_fast,
                     f"brute_us={t_brute:.0f};speedup={t_brute / t_fast:.1f}x"))
    return rows


# ------------------------------------------------------- complexity scaling:
# time grows ~n^2 in train size and ~linearly in t (paper Sec. 3.2)
def bench_complexity_scaling():
    rows = []
    times = {}
    for n in (512, 1024, 2048):
        x, y, xt, yt = _problem(n, 64)
        times[n] = _time(sti_knn_interactions, x, y, xt, yt, 5)
        rows.append((f"scaling_n{n}", times[n], ""))
    exp_n = np.log(times[2048] / times[512]) / np.log(4)
    rows.append(("scaling_exponent_n", 0.0, f"alpha={exp_n:.2f} (expect ~2)"))
    tt = {}
    for t in (32, 128, 512):
        x, y, xt, yt = _problem(1024, t)
        tt[t] = _time(sti_knn_interactions, x, y, xt, yt, 5, test_batch=32)
        rows.append((f"scaling_t{t}", tt[t], ""))
    exp_t = np.log(tt[512] / tt[32]) / np.log(16)
    rows.append(("scaling_exponent_t", 0.0, f"alpha={exp_t:.2f} (expect ~1)"))
    return rows


# ------------------------------------------------------------ baselines:
def bench_baselines():
    from repro.core.sti_knn import _FILL_FNS
    from repro.core.wknn import wknn_shapley_values
    from repro.kernels.sti_pipeline import fused_sti_knn_interactions

    x, y, xt, yt = _problem(2048, 256)
    rows = [
        ("knn_shapley_n2048_t256", _time(knn_shapley_values, x, y, xt, yt, 5),
         "", {"method": "knn_shapley"}),
        ("wknn_n2048_t256",
         _time(lambda: wknn_shapley_values(x, y, xt, yt, 5, weights="rbf")),
         "weights=rbf", {"method": "wknn"}),
        ("loo_n2048_t256", _time(loo_values, x, y, xt, yt, 5), "",
         {"method": "loo"}),
        ("sti_knn_n2048_t256", _time(sti_knn_interactions, x, y, xt, yt, 5),
         "", {"method": "sti", "engine": "scan"}),
        ("sti_knn_sii_n2048_t256",
         _time(lambda: sti_knn_interactions(x, y, xt, yt, 5, mode="sii")), "",
         {"method": "sii", "engine": "scan"}),
        # fill/distance pinned (not "auto") so rows are comparable across
        # hosts regardless of what a user's autotune cache contains
        ("sti_knn_fused_n2048_t256",
         _time(fused_sti_knn_interactions, x, y, xt, yt, 5, test_batch=64,
               fill="chunked", fill_params={"chunk": 1}, distance="xla"),
         "fill=chunked1;distance=xla",
         {"method": "sti", "engine": "fused", "fill": "chunked"}),
    ]
    # The PR-1 perf claim: the chunked scan fill vs the seed (t, n, n)-
    # materializing XLA fill at the acceptance size (t=64, n=2048). The
    # chunked fill's peak memory is O(chunk * n^2) (constant in t).
    from repro.kernels.autotune import _synthetic_fill_problem

    t, n = 64, 2048
    g, ranks = _synthetic_fill_problem(n, t)
    fill_xla = jax.jit(_FILL_FNS["xla"])
    fill_chunked = jax.jit(lambda g, r: _FILL_FNS["chunked"](g, r, chunk=1))
    us_seed = _time(fill_xla, g, ranks, reps=2)
    us_chunked = _time(fill_chunked, g, ranks, reps=2)
    rows += [
        ("fill_xla_seed_t64_n2048", us_seed, "peak_mem=O(t*n^2)"),
        ("fill_chunked_t64_n2048", us_chunked,
         f"peak_mem=O(n^2);speedup_vs_seed={us_seed / us_chunked:.2f}x"),
    ]
    return rows


# --------------------------------------------------- point-value methods:
# the method-generic streaming engine (ISSUE 5): the exact streamed wknn
# vs its O(2^n) oracle, and streamed-session vs eager rows per point method
def bench_point_methods():
    from repro.core import get_method, wknn_shapley_values
    from repro.core.sti_baseline import brute_force_wknn_shapley

    rows = []
    # headline: exact weighted-KNN Shapley without subset enumeration.
    # n=12 is the largest size the 2^n oracle finishes in seconds.
    x, y, xt, yt = _problem(12, 4, d=4, seed=5)
    t0 = time.perf_counter()
    want = brute_force_wknn_shapley(
        np.asarray(x), np.asarray(y), np.asarray(xt), np.asarray(yt), 5)
    us_oracle = (time.perf_counter() - t0) * 1e6
    us_exact = _time(lambda: wknn_shapley_values(x, y, xt, yt, 5))
    err = float(np.abs(np.asarray(
        wknn_shapley_values(x, y, xt, yt, 5)) - want).max())
    rows.append((
        "wknn_exact_vs_oracle_n12", us_exact,
        f"oracle_us={us_oracle:.0f};speedup={us_oracle / us_exact:.0f}x;"
        f"max_err={err:.1e}",
        {"method": "wknn", "engine": "streamed"},
    ))
    # streamed (session-driven) vs eager (direct call of the same generic
    # step) at production size -- tracks session scaffolding overhead
    x, y, xt, yt = _problem(2048, 256)
    for name in ("knn_shapley", "wknn", "loo"):
        m = get_method(name)
        us_st = _time(lambda: m(x, y, xt, yt, k=5, engine="streamed",
                                distance="xla").point_values)
        us_ea = _time(lambda: m(x, y, xt, yt, k=5,
                                engine="eager").point_values)
        rows.append((
            f"{name}_streamed_n2048_t256", us_st,
            f"eager_us={us_ea:.0f};session_overhead="
            f"{(us_st - us_ea) / max(us_ea, 1e-9) * 100:+.0f}%",
            {"method": name, "engine": "streamed"},
        ))
    return rows


# ------------------------------------------------------------ resilience:
# the fault-tolerant session runtime's price: a guarded + checkpointed
# streamed run vs the bare streaming session on the identical fold
# (ISSUE 6 acceptance: overhead < 10% at n=2048 t=256)
def bench_resilience():
    import shutil
    import tempfile

    from repro.core.resilient import ResilientValuationSession
    from repro.core.session import ValuationSession

    n, t, k, tb = 2048, 256, 5, 64
    x, y, xt, yt = _problem(n, t)
    batches = [(xt[i:i + tb], yt[i:i + tb]) for i in range(0, t, tb)]
    pinned = dict(fill="chunked", fill_params={"chunk": 1}, distance="xla")

    def bare():
        s = ValuationSession(x, y, k=k, mode="sti", test_batch=tb, **pinned)
        for xb, yb in batches:
            s.update(xb, yb)
        jax.block_until_ready(s._state)

    def guarded():
        d = tempfile.mkdtemp(prefix="repro-bench-ckpt-")
        try:
            s = ResilientValuationSession(
                x, y, ckpt_dir=d, mode="sti", k=k, test_batch=tb,
                ckpt_every=2, **pinned)
            for xb, yb in batches:
                s.update(xb, yb)
            s._ckpt.wait()
            jax.block_until_ready(s._inner._state)
        finally:
            shutil.rmtree(d, ignore_errors=True)

    reps = 5
    for fn in (bare, guarded):  # compile/warmup outside the timed region
        fn()
    # INTERLEAVED pairs + median: host-load drift between two back-to-back
    # blocks easily exceeds the ~10% effect being measured
    samples: dict = {"bare": [], "guarded": []}
    for _ in range(reps):
        for name, fn in (("bare", bare), ("guarded", guarded)):
            t0 = time.perf_counter()
            fn()
            samples[name].append((time.perf_counter() - t0) * 1e6)
    us = {name: float(np.median(v)) for name, v in samples.items()}
    overhead = (us["guarded"] - us["bare"]) / us["bare"] * 100
    return [
        ("sti_streamed_bare_n2048_t256", us["bare"],
         "bare ValuationSession fold (no guard/checkpoint)",
         {"method": "sti", "engine": "session"}),
        ("resilience_overhead", us["guarded"],
         f"bare_us={us['bare']:.0f};guard+ckpt_overhead={overhead:+.1f}% "
         f"(target <10%); ckpt_every=2, async sha256 checkpoints, NaN "
         f"guard every batch",
         {"method": "sti", "engine": "resilient"}),
    ]


# ----------------------------------------------------- paper Appendix B:
# k-invariance of the interaction matrix (Pearson > 0.99)
def bench_k_invariance():
    rows = []
    for name, maker in (("circle", make_circles), ("moon", make_moons)):
        x, y = maker(150, noise=0.08, seed=3)
        xt, yt = maker(50, noise=0.08, seed=4)
        ks = (3, 5, 9, 15, 20)
        phis = {k: sti_knn_interactions(x, y, xt, yt, k) for k in ks}
        cmin = min(
            float(analysis.k_invariance_correlation(phis[a], phis[b]))
            for i, a in enumerate(ks) for b in ks[i + 1:])
        rows.append((f"k_invariance_{name}", 0.0,
                     f"min_pearson={cmin:.4f} (paper: >0.99)"))
    return rows


# --------------------------------------------------------- paper Fig. 5:
# mislabel detection via interaction patterns
def bench_mislabel_detection():
    rows = []
    for frac in (0.05, 0.1, 0.2):
        x, y_clean = make_circles(300, noise=0.08, seed=0)
        y, flipped = flip_labels(y_clean, frac, 2, seed=1)
        xt, yt = make_circles(100, noise=0.08, seed=2)
        t0 = time.perf_counter()
        phi = sti_knn_interactions(x, y, xt, yt, 5)
        scores = analysis.mislabel_scores(phi, y, 2)
        jax.block_until_ready(scores)
        us = (time.perf_counter() - t0) * 1e6
        order = np.argsort(-np.asarray(scores))
        nf = int(np.asarray(flipped).sum())
        prec = float(np.asarray(flipped)[order[:nf]].mean())
        rows.append((f"mislabel_frac{frac}", us, f"precision@k={prec:.2f}"))
    return rows


# --------------------------------------------------------- paper Fig. 3/4:
# in-class vs out-of-class interaction; redundancy effect
def bench_interaction_structure():
    x, y = make_circles(300, noise=0.08, seed=0)
    xt, yt = make_circles(100, noise=0.08, seed=2)
    phi = sti_knn_interactions(x, y, xt, yt, 5)
    s = analysis.class_block_summary(phi, y, 2)
    rows = [("in_vs_out_class", 0.0,
             f"in={float(jnp.mean(s.in_class_mean)):.2e};"
             f"out={float(s.out_class_mean):.2e}")]
    # redundancy (Fig. 4): halving class-0 points strengthens the
    # surviving points' per-pair share
    x2 = jnp.concatenate([x[:150], x[300:]])
    y2 = jnp.concatenate([y[:150], y[300:]])
    phi2 = sti_knn_interactions(x2, y2, xt, yt, 5)
    s2 = analysis.class_block_summary(phi2, y2, 2)
    rows.append(("redundancy_effect", 0.0,
                 f"balanced_in0={float(s.in_class_mean[0]):.2e};"
                 f"halved_in0={float(s2.in_class_mean[0]):.2e}"))
    return rows


# ------------------------------------------------------------ kernels:
def bench_kernels():
    from repro.kernels import ref
    from repro.kernels.sti_fill import sti_fill_pallas
    rng = np.random.default_rng(0)
    t, n = 16, 512
    g = jnp.asarray(rng.normal(size=(t, n)).astype(np.float32))
    ranks = jnp.asarray(
        np.stack([rng.permutation(n) for _ in range(t)]).astype(np.int32))
    rows = [
        ("sti_fill_xla_t16_n512", _time(ref.sti_fill_ref, g, ranks), ""),
        ("sti_fill_pallas_interp_t16_n512",
         _time(sti_fill_pallas, g, ranks, interpret=True, reps=1),
         "interpret-mode (correctness only; perf target is TPU)"),
    ]
    rows += bench_diag_hoist()
    return rows


def bench_diag_hoist():
    """Satellite micro-bench: the fused step's diagonal term now reuses the
    fill stage's u (gathered back to train coordinates) instead of
    re-broadcasting the (tb, n) label comparison. Times one full fused-style
    step body with each diag formulation and reports the delta."""
    from repro.core.sti_knn import (
        pairwise_sq_dists, ranks_from_order, superdiagonal_g, _fill_chunked)

    t, n, d, k = 64, 1024, 16, 5
    x, y, xt, yt = _problem(n, t, d)
    mask = jnp.ones((t,), jnp.float32)

    def step_body(diag_fn):
        def step(xb, yb, mask):
            d2 = pairwise_sq_dists(xb, x)
            order = jnp.argsort(d2, axis=-1, stable=True)
            ranks = ranks_from_order(order)
            u = (y[order] == yb[:, None]).astype(jnp.float32) * (
                mask / k)[:, None]
            g = superdiagonal_g(u, k)
            return _fill_chunked(g, ranks), diag_fn(u, ranks, yb, mask)
        return jax.jit(step)

    def diag_legacy(u, ranks, yb, mask):   # re-broadcasts the label match
        return jnp.sum(
            (y[None, :] == yb[:, None]).astype(jnp.float32)
            * (mask / k)[:, None], axis=0)

    def diag_hoisted(u, ranks, yb, mask):  # rides on the fill stage's u
        return jnp.sum(jnp.take_along_axis(u, ranks, axis=-1), axis=0)

    us_legacy = _time(step_body(diag_legacy), xt, yt, mask)
    us_hoisted = _time(step_body(diag_hoisted), xt, yt, mask)
    return [
        ("fused_step_diag_legacy_t64_n1024", us_legacy,
         "diag=fresh_label_broadcast"),
        ("fused_step_diag_hoisted_t64_n1024", us_hoisted,
         f"diag=fill_stage_u;step_delta={us_legacy - us_hoisted:+.0f}us"),
    ]


# ------------------------------------------------------------ sharded:
# the multi-device fused pipeline. On an accelerator the phase runs in this
# process over the local devices (a child process could not get the chip
# this process already holds); on CPU it runs in a child with forced host
# devices, so the scaling rows exist on CPU-only hosts too (what CPU rows
# track is overhead + the n^2/D memory split, not speed).
_SHARDED_SHAPE = dict(n=512, t=64, k=5, tb=32)


def _sharded_measure(n, t, k, tb):
    """Time the fused and sharded steps at (n, t, k, tb) and, off-TPU, the
    rect XLA block scan against the rect Pallas kernel in interpret mode
    (on TPU Mosaic refuses that kernel's gather -- ROADMAP S1 -- so the row
    is not offered). Returns a dict of the measured numbers."""
    from repro.kernels.sti_pipeline import (
        fused_sti_knn_interactions, sharded_sti_knn_interactions)

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(n, 16)).astype(np.float32))
    y = jnp.asarray(rng.integers(0, 2, n).astype(np.int32))
    xt = jnp.asarray(rng.normal(size=(t, 16)).astype(np.float32))
    yt = jnp.asarray(rng.integers(0, 2, t).astype(np.int32))
    out = {"devices": jax.device_count()}
    kw = dict(test_batch=tb, fill="chunked", fill_params={"chunk": 1},
              distance="xla")
    out["us_fused"] = _time(lambda: fused_sti_knn_interactions(
        x, y, xt, yt, k, **kw))
    out["us_shard"] = _time(lambda: sharded_sti_knn_interactions(
        x, y, xt, yt, k, **kw))
    out["err"] = float(jnp.max(jnp.abs(
        fused_sti_knn_interactions(x, y, xt, yt, k, test_batch=tb)
        - sharded_sti_knn_interactions(x, y, xt, yt, k, test_batch=tb))))
    if jax.default_backend() == "tpu":
        return out
    # small shape: the Pallas row runs in INTERPRET mode (Python-speed)
    nr, tr, tbr = 256, 16, 16
    xr = jnp.asarray(rng.normal(size=(nr, 16)).astype(np.float32))
    yr = jnp.asarray(rng.integers(0, 2, nr).astype(np.int32))
    xtr = jnp.asarray(rng.normal(size=(tr, 16)).astype(np.float32))
    ytr = jnp.asarray(rng.integers(0, 2, tr).astype(np.int32))

    def rect(fill):
        return sharded_sti_knn_interactions(
            xr, yr, xtr, ytr, k, test_batch=tbr, fill=fill, distance="xla")

    out.update(nr=nr, tr=tr, us_rect_scan=_time(lambda: rect("chunked")),
               us_rect_pal=_time(lambda: rect("pallas")),
               err_rect=float(jnp.max(jnp.abs(rect("chunked")
                                              - rect("pallas")))))
    return out


def bench_sharded():
    import os
    import subprocess
    import sys
    from pathlib import Path

    shape = _SHARDED_SHAPE
    n, t = shape["n"], shape["t"]
    if jax.default_backend() != "cpu":
        m = _sharded_measure(**shape)
    else:
        root = Path(__file__).resolve().parents[1]
        env = dict(
            os.environ,
            XLA_FLAGS="--xla_force_host_platform_device_count=8",
            PYTHONPATH=os.pathsep.join([str(root / "src"), str(root)]),
        )
        code = ("import json; from benchmarks.run import _sharded_measure, "
                "_SHARDED_SHAPE as s; "
                "print('RESULT', json.dumps(_sharded_measure(**s)))")
        p = subprocess.run([sys.executable, "-c", code], env=env, cwd=root,
                           capture_output=True, text=True, timeout=900)
        if p.returncode != 0:
            raise RuntimeError(
                f"sharded bench child failed (rc={p.returncode}):\n"
                f"{p.stderr[-4000:]}"
            )
        (line,) = [ln for ln in p.stdout.splitlines()
                   if ln.startswith("RESULT ")]
        m = json.loads(line[len("RESULT "):])
    dev = int(m["devices"])
    per_dev_mb = n * n * 4 / dev / 2**20
    rows = [
        (f"sti_fused_1dev_n{n}_t{t}", float(m["us_fused"]),
         f"acc_mem={n*n*4/2**20:.1f}MiB",
         {"method": "sti", "engine": "fused"}),
        (f"sti_sharded_{dev}dev_n{n}_t{t}", float(m["us_shard"]),
         f"acc_mem_per_dev={per_dev_mb:.2f}MiB;"
         f"max_err_vs_fused={m['err']:.2e};devices={dev}",
         {"method": "sti", "engine": "sharded"}),
    ]
    if "us_rect_pal" in m:
        nr, tr = m["nr"], m["tr"]
        rows += [
            (f"sti_sharded_{dev}dev_xla_scan_fill_n{nr}_t{tr}",
             float(m["us_rect_scan"]), "fill=rect_chunked(XLA block scan)",
             {"method": "sti", "engine": "sharded", "fill": "chunked"}),
            (f"sti_sharded_{dev}dev_pallas_fill_n{nr}_t{tr}",
             float(m["us_rect_pal"]),
             f"fill=rect_pallas(interpret; correctness only);"
             f"max_err_vs_scan={m['err_rect']:.2e}",
             {"method": "sti", "engine": "sharded", "fill": "pallas"}),
        ]
    return rows


# ------------------------------------------------------------- service:
# the online valuation service (ISSUE 8): request latency through the
# admission/coalescing path, and the incremental remove_points (warm rank
# caches, masked refold only) vs the cache_policy="off" full recompute at
# n=2048 -- the speedup that justifies carrying the caches at all
def bench_service():
    from repro.serving.valuation_service import ValuationService

    n, t, d, k, tb = 2048, 256, 64, 5, 64
    rng = np.random.default_rng(0)
    x = rng.normal(size=(n, d)).astype(np.float32)
    y = rng.integers(0, 2, n).astype(np.int32)
    xt = rng.normal(size=(t, d)).astype(np.float32)
    yt = rng.integers(0, 2, t).astype(np.int32)

    def build(policy):
        svc = ValuationService(
            x, y, method="knn_shapley", k=k, capacity=n + 64,
            test_batch=tb, cache_policy=policy, seed=0, distance="xla")
        for i in range(0, t, tb):
            svc.value_query(xt[i:i + tb], yt[i:i + tb])
        return svc

    svc = build("lazy")
    h = svc.health()
    rows = [("service_query_n2048_t256", h["latency_p50_s"] * 1e6,
             f"p99_us={h['latency_p99_s'] * 1e6:.0f};"
             f"query_batches={t // tb}",
             {"method": "knn_shapley", "engine": "service"})]

    reps = 5
    svc.remove_points([n - 1])    # warms the lazy rank caches + compiles
    t0 = time.perf_counter()
    for i in range(reps):
        svc.remove_points([i])
    us_inc = (time.perf_counter() - t0) / reps * 1e6

    ref = build("off")
    ref.remove_points([n - 1])    # compile parity with the warm run
    t0 = time.perf_counter()
    for i in range(reps):
        ref.remove_points([i])
    us_full = (time.perf_counter() - t0) / reps * 1e6

    # the incremental path's exactness AT benchmark scale: both services
    # removed the identical ids, values must agree bit-for-bit
    a = np.asarray(svc.get_values().payload["values"])
    b = np.asarray(ref.get_values().payload["values"])
    exact = bool(np.array_equal(a, b))
    svc.close()
    ref.close()
    rows += [
        ("service_remove_full_recompute_n2048", us_full,
         "cache_policy=off: rank recompute per batch + refold",
         {"method": "knn_shapley", "engine": "service"}),
        ("service_remove_incremental_n2048", us_inc,
         f"cache_policy=lazy warm: masked refold only;"
         f"speedup_vs_full={us_full / max(us_inc, 1e-9):.2f}x;"
         f"bit_exact={exact}",
         {"method": "knn_shapley", "engine": "service"}),
    ]
    return rows


# -------------------------------------------------------------- approx:
# the approximate top-m engine (ISSUE 9): exact vs approx wall time, true
# max error vs the certified bound, and the measured candidate recall, on
# clustered (blob) data where nearest neighbors are locally concentrated --
# the regime LSH preselection is built for. The n=16384 row is the
# acceptance claim: >= 5x over the exact streamed engine at recall >= 0.95.
def bench_approx():
    from repro.core import get_method

    def blobs(n, t, d, classes, seed=0):
        rng = np.random.default_rng(seed)
        centers = rng.normal(scale=8.0, size=(classes, d)).astype(np.float32)
        ytr = rng.integers(0, classes, n).astype(np.int32)
        yte = rng.integers(0, classes, t).astype(np.int32)
        xtr = centers[ytr] + rng.normal(size=(n, d)).astype(np.float32)
        xte = centers[yte] + rng.normal(size=(t, d)).astype(np.float32)
        return (jnp.asarray(xtr), jnp.asarray(ytr),
                jnp.asarray(xte), jnp.asarray(yte))

    def once(fn):
        fn()  # compile/warmup (jitted steps are lru-cached across calls)
        t0 = time.perf_counter()
        out = fn()
        jax.block_until_ready(
            out.phi if out.phi is not None else out.point_values)
        return out, (time.perf_counter() - t0) * 1e6

    rows = []
    k, t, d = 5, 256, 32
    ap_kw = dict(n_tables=8, recall_sample=8)

    # n=2048: interaction + point parity rows (exact comparison affordable;
    # at this size the exact streamed engine wins on CPU -- the rows track
    # the certified-error story, the speedup claim lives at n=16384)
    x, y, xt, yt = blobs(2048, t, d, classes=16)
    for method, exact_engine, m in (("sti", "fused", 256),
                                    ("knn_shapley", "streamed", 256)):
        meth = get_method(method)
        r_ex, us_ex = once(lambda: meth(x, y, xt, yt, k=k,
                                        engine=exact_engine, test_batch=64))
        r_ap, us_ap = once(lambda: meth(
            x, y, xt, yt, k=k, engine="approx", test_batch=64, top_m=m,
            approx_params=dict(window=2 * m, **ap_kw)))
        a = np.asarray(r_ex.phi if r_ex.phi is not None
                       else r_ex.point_values)
        b = np.asarray(r_ap.phi if r_ap.phi is not None
                       else r_ap.point_values)
        err = float(np.max(np.abs(a - b)))
        rows.append((
            f"{method}_approx_m{m}_n2048_t{t}", us_ap,
            f"exact_us={us_ex:.0f};speedup={us_ex / us_ap:.2f}x;"
            f"max_err={err:.2e};bound={r_ap.meta['error_bound']:.2e};"
            f"recall={r_ap.meta['recall_estimate']:.3f}",
            {"method": method, "engine": "approx"},
        ))

    # n=16384: the acceptance row -- >= 5x at recall >= 0.95. 64 clusters
    # of ~256 points: one 256-wide code window per table covers a query's
    # whole cluster, so the pool (8*256 = 2048 of 16384) stays small while
    # the true top-k are all in it
    x, y, xt, yt = blobs(16384, t, d, classes=64)
    m = 512
    meth = get_method("knn_shapley")
    r_ex, us_ex = once(lambda: meth(x, y, xt, yt, k=k, engine="streamed",
                                    test_batch=64))
    r_ap, us_ap = once(lambda: meth(
        x, y, xt, yt, k=k, engine="approx", test_batch=64, top_m=m,
        recall_target=0.95, approx_params=dict(window=256, **ap_kw)))
    err = float(np.max(np.abs(np.asarray(r_ex.point_values)
                              - np.asarray(r_ap.point_values))))
    rows.append((
        f"knn_shapley_approx_m{m}_n16384_t{t}", us_ap,
        f"exact_us={us_ex:.0f};speedup={us_ex / us_ap:.2f}x "
        f"(target >=5x);max_err={err:.2e};"
        f"bound={r_ap.meta['error_bound']:.2e};"
        f"recall={r_ap.meta['recall_estimate']:.3f} (target >=0.95);"
        f"recall_target_met={r_ap.meta['recall_target_met']}",
        {"method": "knn_shapley", "engine": "approx"},
    ))
    return rows


# --------------------------------------------------------- megakernel:
# the fused single-pallas_call step (ISSUE 10) vs the three-stage step with
# the chunked and onehot fills at the paper sizes. `derived` carries the
# achieved-vs-matmul-FLOPs ratio: time of a pure (tb, d) x (d, n) distance
# matmul of the same FLOPs over the step time (the ROADMAP target is a
# megakernel step within 2x of the matmul ON TPU; interpret-mode CPU rows
# track correctness-path overhead only).
def bench_megakernel():
    from repro.kernels.sti_pipeline import fused_sti_knn_interactions

    k, t, d, tb = 5, 64, 16, 16
    rows = []
    for n in (1024, 2048):
        x, y, xt, yt = _problem(n, t, d)
        xb = xt[:tb]
        matmul = jax.jit(lambda a, b: a @ b.T)
        us_mm_step = _time(matmul, xb, x)   # one step's distance FLOPs
        us_mm = us_mm_step * (t // tb)      # whole-fold matmul equivalent
        variants = (
            ("megakernel", "megakernel", None, 2),
            ("chunked", "chunked", {"chunk": 1}, 2),
            ("onehot", "onehot", {"chunk": 1}, 1),  # O(t n^3): 1 rep
        )
        for label, fill, params, reps in variants:
            us = _time(
                fused_sti_knn_interactions, x, y, xt, yt, k,
                test_batch=tb, fill=fill, fill_params=params,
                distance="xla", reps=reps,
            )
            note = ("interpret" if label == "megakernel"
                    and jax.default_backend() != "tpu" else "compiled")
            rows.append((
                f"megakernel_vs_{label}_n{n}_t{t}", us,
                f"fill={label}({note});matmul_us={us_mm:.0f};"
                f"matmul_flops_ratio={us_mm / us:.4f}",
                {"method": "sti", "engine": "fused", "fill": label},
            ))
    return rows


# ----------------------------------------------------- autotune campaign:
# `--autotune` mode: populate the platform-keyed cache at the paper sizes
# (single-device fill + distance + megastep, and the dev{D}/rows{R} rect
# key for the sharded row blocks) BEFORE the timing benches run, and emit
# one row per tuned entry so BENCH_sti_knn.json records which fill won
# under which platform key.
def bench_autotune_campaign():
    from repro.kernels import autotune as at

    backend = jax.default_backend()
    plat = at.device_platform(backend)
    devs = jax.device_count()
    d, k = 16, 5
    rows = []
    for n, t in ((1024, 64), (2048, 64), (2048, 256)):
        name, params = at.autotune_fill(n, t, backend=backend)
        entry = at._load(None).get(at._key("fill", backend, n, t)) or {}
        rows.append((
            f"autotune_fill_n{n}_t{t}", float(entry.get("us", 0.0)),
            f"winner={name};params={json.dumps(params, sort_keys=True)};"
            f"platform={plat}",
            {"method": "sti", "engine": "fused", "fill": name},
        ))
        rows_r = max(1, n // devs)
        rname, rparams = at.autotune_rect_fill(rows_r, n, t, backend=backend)
        rentry = at._load(None).get(
            at._key("rectfill", backend, n, t, rows=rows_r)) or {}
        rows.append((
            f"autotune_rectfill_rows{rows_r}_n{n}_t{t}",
            float(rentry.get("us", 0.0)),
            f"winner={rname};params={json.dumps(rparams, sort_keys=True)};"
            f"platform={plat};devices={devs}",
            {"method": "sti", "engine": "sharded", "fill": rname},
        ))
        dname, dparams = at.autotune_distance(t, n, d, backend=backend)
        rows.append((
            f"autotune_distance_n{n}_t{t}_d{d}", 0.0,
            f"winner={dname};params={json.dumps(dparams, sort_keys=True)};"
            f"platform={plat}",
            {"method": "sti", "engine": "fused", "fill": None},
        ))
        sname, sparams = at.autotune_megastep(n, d, k, t, backend=backend)
        sentry = at._load(None).get(
            at._key(f"megastep_d{d}", backend, n, t)) or {}
        rows.append((
            f"autotune_megastep_n{n}_t{t}_d{d}",
            float(sentry.get("us", 0.0)),
            f"winner={sname};params={json.dumps(sparams, sort_keys=True)};"
            f"platform={plat}",
            {"method": "sti", "engine": "fused",
             "fill": "megakernel" if sname == "megakernel" else sname},
        ))
    return rows


# ------------------------------------------------------------ lint gate:
# the reprolint CI job's own cost (DESIGN.md Sec. 14) -- the full-tree AST
# lint plus the abstract-eval contract checks must stay well under a
# minute or the "fails in seconds" pitch of the gate stops being true
def bench_lint():
    import time as _time_mod

    from repro.analysis import lint_tree, load_baseline
    from repro.analysis.baseline import split_baselined
    from repro.analysis.contracts import check_contracts

    t0 = _time_mod.perf_counter()
    findings = lint_tree()
    us_ast = (_time_mod.perf_counter() - t0) * 1e6
    new, baselined = split_baselined(findings, load_baseline())
    t0 = _time_mod.perf_counter()
    contract = check_contracts()
    us_contract = (_time_mod.perf_counter() - t0) * 1e6
    return [
        ("reprolint_full_tree", us_ast + us_contract,
         f"ast_us={us_ast:.0f};contracts_us={us_contract:.0f};"
         f"new={len(new)};baselined={len(baselined)};"
         f"contract_findings={len(contract)}"),
    ]


BENCHES = {
    "speedup": bench_speedup_vs_bruteforce,
    "complexity": bench_complexity_scaling,
    "baselines": bench_baselines,
    "point_methods": bench_point_methods,
    "resilience": bench_resilience,
    "k_invariance": bench_k_invariance,
    "mislabel": bench_mislabel_detection,
    "structure": bench_interaction_structure,
    "kernels": bench_kernels,
    "sharded": bench_sharded,
    "service": bench_service,
    "approx": bench_approx,
    "megakernel": bench_megakernel,
    "autotune": bench_autotune_campaign,
    "lint": bench_lint,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, choices=list(BENCHES))
    ap.add_argument("--json", action="store_true",
                    help="also write BENCH_sti_knn.json (perf trajectory "
                         "tracked across PRs)")
    ap.add_argument("--json-path", default=None,
                    help="output path for the JSON report (implies --json)")
    ap.add_argument("--autotune", action="store_true",
                    help="campaign mode: tune the paper sizes into the "
                         "platform-keyed autotune cache BEFORE timing, and "
                         "emit one row per tuned winner")
    args = ap.parse_args()
    enable_compile_cache()
    if args.json_path:
        args.json = True
    args.json_path = args.json_path or "BENCH_sti_knn.json"
    # the campaign only runs when asked for: a default run must not spend
    # minutes tuning nor write to the user's cache
    names = [args.only] if args.only else [
        nm for nm in BENCHES if nm != "autotune"
    ]
    if args.autotune and "autotune" not in names:
        names = ["autotune"] + names
    print("name,us_per_call,derived")
    all_rows = []
    # per-bench default provenance; rows may override (or extend) it with an
    # optional 4th tuple element, e.g. {"method": "sti", "engine": "fused"}
    bench_prov = {
        "speedup": {"method": "sti", "engine": "scan"},
        "complexity": {"method": "sti", "engine": "scan"},
        "baselines": {"method": None, "engine": None},
        "point_methods": {"method": None, "engine": None},
        "resilience": {"method": "sti", "engine": "resilient"},
        "k_invariance": {"method": "sti", "engine": "scan"},
        "mislabel": {"method": "sti", "engine": "scan"},
        "structure": {"method": "sti", "engine": "scan"},
        "kernels": {"method": "sti", "engine": "kernel"},
        "sharded": {"method": "sti", "engine": "sharded"},
        "service": {"method": "knn_shapley", "engine": "service"},
        "approx": {"method": None, "engine": "approx"},
        "megakernel": {"method": "sti", "engine": "fused"},
        "autotune": {"method": None, "engine": None},
        "lint": {"method": None, "engine": None},
    }
    for nm in names:
        for row in BENCHES[nm]():
            print(f"{row[0]},{row[1]:.1f},{row[2]}", flush=True)
            prov = dict(bench_prov.get(nm, {}))
            if len(row) > 3:
                prov.update(row[3])
            all_rows.append(
                {"bench": nm, "name": row[0],
                 "us_per_call": round(float(row[1]), 1), "derived": row[2],
                 "method": prov.get("method"), "engine": prov.get("engine"),
                 # rows carry the resolved fill (None when the bench has no
                 # fill stage) and their own backend: merge-on-write mixes
                 # runs from different hosts, so file-level fields are not
                 # enough
                 "fill": prov.get("fill"),
                 "backend": jax.default_backend()})
    if args.json:
        # merge-on-write: a partial run (--only sharded) APPENDS its rows to
        # the existing report (matching (bench, name) rows are replaced), so
        # per-engine trajectories accumulate instead of clobbering the file
        old_rows = []
        try:
            with open(args.json_path) as f:
                old_rows = json.load(f).get("rows", [])
        except (OSError, ValueError):
            pass
        # a re-run bench replaces ALL of its old rows (not just matching
        # names): stale rows -- a recorded subprocess failure, rows whose
        # parameterized names no longer appear -- must not outlive a rerun
        rows = [r for r in old_rows if r.get("bench") not in names] + all_rows
        payload = {
            "backend": jax.default_backend(),
            "jax": jax.__version__,
            "platform": platform.platform(),
            "benches": sorted({r["bench"] for r in rows}),
            "rows": rows,
        }
        with open(args.json_path, "w") as f:
            json.dump(payload, f, indent=1)
        print(f"# wrote {args.json_path} "
              f"({len(all_rows)} new rows, {len(rows)} total)")


if __name__ == "__main__":
    main()
