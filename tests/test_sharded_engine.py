"""Sharded fused STI engine: exact parity against the single-device fused
pipeline and the `sti_knn_interactions` oracle under 8 forced host devices.

Multi-device cases run in SUBPROCESSES (jax locks the device count at first
init; the main pytest process must stay single-device for the smoke tests).
The single-shard fallback cases run in-process.
"""

import os
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest
import jax.numpy as jnp

import repro  # noqa: F401
from repro.core.session import ShardedValuationSession
from repro.core.sti_knn import sti_knn_interactions
from repro.kernels.sti_pipeline import sharded_sti_knn_interactions

REPO = Path(__file__).resolve().parents[1]


def run_py(code: str, devices: int = 8, timeout: int = 900):
    env = dict(os.environ,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               PYTHONPATH=str(REPO / "src"))
    p = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       env=env, capture_output=True, text=True,
                       timeout=timeout)
    assert p.returncode == 0, f"stdout:\n{p.stdout}\nstderr:\n{p.stderr}"
    return p.stdout


_PROBLEM = """
    import jax, numpy as np, jax.numpy as jnp
    import repro
    from repro.core.sti_knn import sti_knn_interactions
    from repro.kernels.sti_pipeline import (
        fused_sti_knn_interactions, sharded_sti_knn_interactions)

    def problem(n, t, seed, dim=3, classes=2):
        rng = np.random.default_rng(seed)
        return (
            jnp.asarray(rng.normal(size=(n, dim)).astype(np.float32)),
            jnp.asarray(rng.integers(0, classes, n).astype(np.int32)),
            jnp.asarray(rng.normal(size=(t, dim)).astype(np.float32)),
            jnp.asarray(rng.integers(0, classes, t).astype(np.int32)),
        )
"""


def test_sharded_parity_suite():
    """Acceptance: sharded == fused == oracle within 1e-5 at n in {64, 256},
    k in {1, 5}, on 8 forced host devices, with (n/D, n) per-device shards."""
    run_py(_PROBLEM + """
    assert jax.device_count() == 8
    for n in (64, 256):
        for k in (1, 5):
            t = 40
            x, y, xt, yt = problem(n, t, seed=n + k)
            oracle = np.asarray(
                sti_knn_interactions(x, y, xt, yt, k, fill="xla"))
            fused = np.asarray(fused_sti_knn_interactions(
                x, y, xt, yt, k, test_batch=16))
            phi, info = sharded_sti_knn_interactions(
                x, y, xt, yt, k, test_batch=16, return_info=True)
            assert info["shards"] == 8, info
            np.testing.assert_allclose(fused, oracle, atol=1e-5)
            np.testing.assert_allclose(np.asarray(phi), oracle, atol=1e-5)
            print("ok", n, k,
                  float(np.abs(np.asarray(phi) - oracle).max()))
    """)


def test_sharded_accumulator_is_row_sharded():
    """Per-device accumulator arrays are exactly (n / num_devices, n)."""
    run_py(_PROBLEM + """
    from repro.core.session import ShardedValuationSession

    n = 64
    x, y, xt, yt = problem(n, 8, seed=0)
    sess = ShardedValuationSession(x, y, k=3, test_batch=8)
    assert sess.shards == 8
    sess.update(xt, yt)
    shard_shape = sess._acc.sharding.shard_shape(sess._acc.shape)
    assert shard_shape == (n // 8, n), shard_shape
    assert len(sess._acc.sharding.device_set) == 8
    diag_shape = sess._diag.sharding.shard_shape(sess._diag.shape)
    assert diag_shape == (n // 8,), diag_shape
    print("ok", shard_shape)
    """)


def test_sharded_ragged_stream_and_checkpoint_restore():
    """t NOT divisible by (devices * tb) + checkpoint/restore mid-stream."""
    run_py(_PROBLEM + """
    import tempfile, os
    from repro.core.session import ShardedValuationSession

    n, k = 64, 5
    t = 45            # 45 = 2 * (8 * 2) + 13: ragged over devices * tb
    x, y, xt, yt = problem(n, t, seed=7, classes=3)
    oracle = np.asarray(sti_knn_interactions(x, y, xt, yt, k, fill="xla"))

    sess = ShardedValuationSession(x, y, k=k, test_batch=16)
    assert sess.test_batch % 8 == 0
    sess.update(xt[:20], yt[:20])
    with tempfile.TemporaryDirectory() as td:
        ck = sess.checkpoint(os.path.join(td, "mid"))
        restored = ShardedValuationSession.restore(ck, x, y)
        assert restored.shards == 8 and restored.t_seen == 20
        restored.update(xt[20:], yt[20:])
        res = restored.finalize()
    assert res.meta["engine"] == "sharded" and res.meta["shards"] == 8
    assert res.meta["t"] == t
    np.testing.assert_allclose(np.asarray(res.phi), oracle, atol=1e-5)
    print("ok", float(np.abs(np.asarray(res.phi) - oracle).max()))
    """)


def test_sharded_matches_fused_over_ties_and_sentinels():
    """Every stream method sharded over 4 devices against the fused step,
    on a train set with many exact distance ties and two sentinel slots:
    each device's sort carries the labels and sorts back by `order` as the
    fused step does, so the two agree to float summation order."""
    run_py(_PROBLEM + """
    from repro.core.session import ShardedValuationSession, ValuationSession
    from repro.kernels.stream_kernels import SENTINEL_COORD, SENTINEL_LABEL

    rng = np.random.default_rng(4)
    n, t = 24, 13
    x = rng.integers(-2, 3, size=(n, 2)).astype(np.float32)
    y = rng.integers(0, 3, size=n).astype(np.int32)
    for slot in (3, n - 5):
        x[slot], y[slot] = SENTINEL_COORD, SENTINEL_LABEL
    xt = rng.integers(-2, 3, size=(t, 2)).astype(np.float32)
    yt = rng.integers(0, 3, size=t).astype(np.int32)
    for method in ("sti", "sii", "knn_shapley", "wknn", "loo"):
        kw = dict(k=3, mode=method, test_batch=8)
        sharded = ShardedValuationSession(x, y, shards=4, **kw)
        assert sharded.shards == 4
        a = sharded.update(xt, yt).finalize()
        b = ValuationSession(x, y, **kw).update(xt, yt).finalize()
        got, want = (np.asarray(r.phi if r.phi is not None
                                else r.point_values) for r in (a, b))
        assert np.isfinite(want).all()
        np.testing.assert_allclose(got, want, atol=1e-6)
        print("ok", method, float(np.abs(got - want).max()))
    """, devices=4)


def test_sharded_engine_via_method_registry():
    """get_method("sti")(..., engine="sharded") matches the fused engine and
    carries shard provenance in the result metadata."""
    run_py(_PROBLEM + """
    from repro.core import get_method

    x, y, xt, yt = problem(64, 24, seed=3)
    a = get_method("sti")(x, y, xt, yt, k=5, engine="sharded", test_batch=8)
    b = get_method("sti")(x, y, xt, yt, k=5, engine="fused", test_batch=8)
    assert a.meta["engine"] == "sharded" and a.meta["shards"] == 8
    np.testing.assert_allclose(
        np.asarray(a.phi), np.asarray(b.phi), atol=1e-5)
    print("ok")
    """)


def test_sharded_sii_mode():
    run_py(_PROBLEM + """
    x, y, xt, yt = problem(64, 17, seed=11)
    oracle = np.asarray(
        sti_knn_interactions(x, y, xt, yt, 4, mode="sii", fill="xla"))
    phi = sharded_sti_knn_interactions(x, y, xt, yt, 4, mode="sii",
                                       test_batch=8)
    np.testing.assert_allclose(np.asarray(phi), oracle, atol=1e-5)
    print("ok")
    """)


def test_sharded_pallas_rect_fill_parity_suite():
    """Acceptance (PR 4): the sharded engine with the RECTANGULAR Pallas
    accumulate-fill (interpret mode on CPU) == the XLA block scan == the
    dense oracle within 1e-5 at n in {64, 256}, k in {1, 5}, under 8 forced
    host devices, including a ragged trailing batch (t=40 over tb=16) and a
    block_rows that does not divide the (n/D) row count."""
    run_py(_PROBLEM + """
    assert jax.device_count() == 8
    for n in (64, 256):
        for k in (1, 5):
            t = 40    # 40 = 2*16 + 8: ragged trailing batch
            x, y, xt, yt = problem(n, t, seed=2 * n + k)
            oracle = np.asarray(
                sti_knn_interactions(x, y, xt, yt, k, fill="xla"))
            scan, scan_info = sharded_sti_knn_interactions(
                x, y, xt, yt, k, test_batch=16, fill="chunked",
                return_info=True)
            # block_rows=3 does not divide n/D (8 or 32): padded-block path
            pal, pal_info = sharded_sti_knn_interactions(
                x, y, xt, yt, k, test_batch=16, fill="pallas",
                fill_params={"block_rows": 3}, return_info=True)
            assert scan_info["fill"] == "rect_chunked", scan_info
            assert pal_info["fill"] == "rect_pallas", pal_info
            assert pal_info["shards"] == 8, pal_info
            np.testing.assert_allclose(np.asarray(scan), oracle, atol=1e-5)
            np.testing.assert_allclose(np.asarray(pal), oracle, atol=1e-5)
            np.testing.assert_allclose(
                np.asarray(pal), np.asarray(scan), atol=1e-5)
            print("ok", n, k,
                  float(np.abs(np.asarray(pal) - oracle).max()))
    """)


def test_sharded_session_pallas_fill_checkpoint_restore():
    """ShardedValuationSession with the rect Pallas fill survives a
    mid-stream checkpoint/restore and still matches the oracle."""
    run_py(_PROBLEM + """
    import tempfile, os
    from repro.core.session import ShardedValuationSession

    n, k, t = 64, 3, 29
    x, y, xt, yt = problem(n, t, seed=17, classes=3)
    oracle = np.asarray(sti_knn_interactions(x, y, xt, yt, k, fill="xla"))
    sess = ShardedValuationSession(x, y, k=k, test_batch=8, fill="pallas")
    assert sess._resolved["fill"] == "rect_pallas"
    sess.update(xt[:13], yt[:13])
    with tempfile.TemporaryDirectory() as td:
        ck = sess.checkpoint(os.path.join(td, "mid"))
        # restore re-resolves the rect_ fill name (not a square registry
        # entry); pin pallas again explicitly
        restored = ShardedValuationSession.restore(ck, x, y, fill="pallas")
        assert restored._resolved["fill"] == "rect_pallas"
        restored.update(xt[13:], yt[13:])
        res = restored.finalize()
    np.testing.assert_allclose(np.asarray(res.phi), oracle, atol=1e-5)
    print("ok", float(np.abs(np.asarray(res.phi) - oracle).max()))
    """)


# ---------------------------------------------------- single-device fallback
def test_single_device_fallback_matches_oracle():
    rng = np.random.default_rng(0)
    n, t, k = 32, 13, 3
    x = jnp.asarray(rng.normal(size=(n, 3)).astype(np.float32))
    y = jnp.asarray(rng.integers(0, 2, n).astype(np.int32))
    xt = jnp.asarray(rng.normal(size=(t, 3)).astype(np.float32))
    yt = jnp.asarray(rng.integers(0, 2, t).astype(np.int32))
    want = np.asarray(sti_knn_interactions(x, y, xt, yt, k, fill="xla"))
    phi, info = sharded_sti_knn_interactions(
        x, y, xt, yt, k, test_batch=4, shards=1, return_info=True
    )
    assert info["shards"] == 1
    np.testing.assert_allclose(np.asarray(phi), want, atol=1e-5)


def test_single_device_fallback_drops_rect_fill_params():
    """A sharded invocation carrying rect-registry hints (block_rows) must
    run unchanged on a 1-device host: the fallback drops what the square
    fill cannot accept instead of raising."""
    rng = np.random.default_rng(6)
    n, t, k = 32, 9, 3
    x = jnp.asarray(rng.normal(size=(n, 3)).astype(np.float32))
    y = jnp.asarray(rng.integers(0, 2, n).astype(np.int32))
    xt = jnp.asarray(rng.normal(size=(t, 3)).astype(np.float32))
    yt = jnp.asarray(rng.integers(0, 2, t).astype(np.int32))
    want = np.asarray(sti_knn_interactions(x, y, xt, yt, k, fill="xla"))
    phi, info = sharded_sti_knn_interactions(
        x, y, xt, yt, k, test_batch=4, shards=1, fill="pallas",
        fill_params={"block_rows": 8, "block_t": 2}, return_info=True
    )
    assert info["shards"] == 1
    np.testing.assert_allclose(np.asarray(phi), want, atol=1e-5)


def test_single_device_session_fallback_checkpoint_roundtrip(tmp_path):
    rng = np.random.default_rng(4)
    n, t, k = 24, 9, 3
    x = jnp.asarray(rng.normal(size=(n, 2)).astype(np.float32))
    y = jnp.asarray(rng.integers(0, 2, n).astype(np.int32))
    xt = jnp.asarray(rng.normal(size=(t, 2)).astype(np.float32))
    yt = jnp.asarray(rng.integers(0, 2, t).astype(np.int32))
    # shards=1 forces the fused fallback even when the process has many
    # devices (the multi-device CI job runs this file under 8)
    sess = ShardedValuationSession(x, y, k=k, test_batch=4, shards=1)
    assert sess.shards == 1
    sess.update(xt[:5], yt[:5])
    ck = sess.checkpoint(tmp_path / "ck")
    restored = ShardedValuationSession.restore(ck, x, y)
    restored.update(xt[5:], yt[5:])
    res = restored.finalize()
    assert res.meta["shards"] == 1 and res.meta["engine"] == "sharded"
    want = np.asarray(sti_knn_interactions(x, y, xt, yt, k, fill="xla"))
    np.testing.assert_allclose(np.asarray(res.phi), want, atol=1e-5)


def test_shard_count_largest_divisor():
    """shard_count picks the LARGEST divisor of n within the device budget
    (not a gcd, which under-shards non-power-of-two n)."""
    run_py("""
    from repro.distributed.sharding import shard_count
    assert shard_count(64) == 8
    assert shard_count(18) == 6      # gcd(18, 8) would give only 2
    assert shard_count(100) == 5
    assert shard_count(13) == 1      # prime > devices: single shard
    assert shard_count(64, 4) == 4   # explicit request respected
    assert shard_count(64, 999) == 8 # clamped to available devices
    print("ok")
    """)


class _FakeMesh:
    """Minimal 2-shard stand-in: n % D validation fires before any device
    work, so the check is testable on a single-device host."""

    axis_names = ("shards",)
    shape = {"shards": 2}


def test_sharded_rejects_indivisible_n():
    from repro.kernels.sti_pipeline import prepare_sharded_step

    with pytest.raises(ValueError, match="row shards"):
        prepare_sharded_step(7, 3, 2, mesh=_FakeMesh())


# --------------------------------------------- results that stay row-sharded
_HOST_RESULTS = _PROBLEM + """
    import tempfile, os
    from repro.core.session import ShardedValuationSession, ValuationSession

    assert jax.device_count() == 4
    n, k, tb = 64, 5, 4
    # one test point per shard and step: both engines add the same values
    # in the same order (with more, the sharded diagonal's reduce-scatter
    # sums the test points in another order than the fused sum)
    x, y, xt, yt = problem(n, 10, seed=11, classes=3)

    puts = []
    real_put = jax.device_put

    def spy(a, *args, **kw):
        puts.append(tuple(np.shape(a)))
        return real_put(a, *args, **kw)

    jax.device_put = spy

    def on_host(call, sess):
        # no device array of a state array's full shape is placed or left
        # behind by `call`, besides the state the session steps to: the
        # state leaves the devices block by block
        shapes = {tuple(a.shape) for a in sess._state}
        before = {id(a) for a in jax.live_arrays()}
        puts.clear()
        out = call()
        placed = [p for p in puts if p in shapes]
        made = [tuple(a.shape) for a in jax.live_arrays()
                if id(a) not in before and tuple(a.shape) in shapes
                and not any(a is s for s in sess._state)]
        assert not placed and not made, (placed, made)
        return out

    def bits(res):
        a = res.phi if res.phi is not None else res.point_values
        assert isinstance(a, np.ndarray), type(a)
        return a.view(np.uint32)

    def same(a, b):
        assert np.array_equal(bits(a), bits(b))
"""


@pytest.mark.parametrize("method", ["sti", "sii", "knn_shapley"])
def test_sharded_finalize_and_checkpoint_stay_on_the_host(method):
    """A 4-shard session's finalize() and checkpoint -> restore ->
    finalize() equal the fused session's bit for bit, and neither places
    nor leaves on a device an array of the state's full shape."""
    run_py(_HOST_RESULTS + f"""
    method = {method!r}
    fused = ValuationSession(x, y, k=k, mode=method, test_batch=tb)
    sharded = ShardedValuationSession(x, y, k=k, mode=method,
                                      test_batch=tb, shards=4)
    assert sharded.shards == 4
    for sess in (fused, sharded):
        sess.update(xt[:6], yt[:6]).update(xt[6:], yt[6:])
    want = fused.finalize()
    same(on_host(sharded.finalize, sharded), want)
    with tempfile.TemporaryDirectory() as td:
        ck = on_host(lambda: sharded.checkpoint(os.path.join(td, "ck")),
                     sharded)
        back = ShardedValuationSession.restore(ck, x, y)
        assert back.shards == 4 and back.t_seen == 10
        same(on_host(back.finalize, back), want)
        same(ValuationSession.restore(ck, x, y).finalize(), want)
    # finalize is a snapshot: the sessions fold on and agree again
    for sess in (fused, sharded, back):
        sess.update(xt[:4], yt[:4])
    want = fused.finalize()
    same(sharded.finalize(), want)
    same(back.finalize(), want)
    print("ok", method)
    """, devices=4)


def test_resilient_sharded_session_checkpoints_on_the_host():
    """A ResilientValuationSession over 4 shards checkpoints every batch
    and finalizes with no full-shape device array, and its result and the
    one restored from its checkpoints equal the fused session's bit for
    bit."""
    run_py(_HOST_RESULTS + """
    from repro.core.resilient import ResilientValuationSession

    fused = ValuationSession(x, y, k=k, mode="sti", test_batch=tb)
    fused.update(xt[:6], yt[:6]).update(xt[6:], yt[6:])
    want = fused.finalize()
    with tempfile.TemporaryDirectory() as td:
        rs = ResilientValuationSession(
            x, y, ckpt_dir=td, mode="sti", k=k, test_batch=tb, shards=4,
            ckpt_every=1, async_checkpoint=False)
        assert rs.shards == 4
        inner = rs.inner
        on_host(lambda: rs.update(xt[:6], yt[:6]).update(xt[6:], yt[6:]),
                inner)
        assert rs.resilience_summary()["checkpoint_steps"] == [1, 2]
        same(on_host(rs.finalize, inner), want)
        back = ResilientValuationSession.restore(td, x, y, shards=4)
        assert back.shards == 4 and back.t_seen == 10
        same(back.finalize(checkpoint=False), want)
    print("ok")
    """, devices=4)
