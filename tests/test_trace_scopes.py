"""Stage names the benchmark's scope reading takes from a trace
(bench/scopes.json, bench/scope_trace.py).

Each stage of the streaming step runs under a `jax.named_scope`, so every
device op carries its stage in its `op_name` metadata; `session.update`
writes host spans with its counters into a profiler trace. Both are read by
name from a trace of the chip, so a refactor that drops or renames one
would silently empty a per-layer metric: these tests pin them on the CPU at
a tiny n."""

import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.session import ValuationSession
from repro.kernels.sti_pipeline import prepare_stream_step

N, D, K, TB = 24, 4, 3, 8

# point methods bring their values back to train coordinates with one sort
# (`to_train`) and never need the ranks; sti sorts the ranks out with u
POINT_SCOPES = {"distance", "sort", "label_gather", "contrib",
                "recurrence", "to_train", "update"}
METHOD_SCOPES = {
    "knn_shapley": POINT_SCOPES,
    "sti": POINT_SCOPES | {"rank", "fill"},
}


def _data(n=N, seed=0):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(n, D)), jnp.float32)
    y = jnp.asarray(rng.integers(0, 2, size=(n,)), jnp.int32)
    return x, y


def _scopes_in(hlo_text: str) -> set:
    """Every name-stack component of the ops' `op_name` metadata (the
    last component is the op itself, not a scope)."""
    out = set()
    for path in re.findall(r'op_name="([^"]*)"', hlo_text):
        out.update(path.split("/")[:-1])
    return out


@pytest.mark.parametrize("method", sorted(METHOD_SCOPES))
def test_default_step_carries_every_stage_scope(method):
    step, _, spec = prepare_stream_step(method, N, D, K, test_batch=TB)
    x, y = _data()
    args = (*spec.init(N), jnp.zeros((TB, D), jnp.float32),
            jnp.zeros((TB,), jnp.int32), jnp.ones((TB,), jnp.float32), x, y)
    text = step.inner.lower(*args).compile().as_text()
    missing = METHOD_SCOPES[method] - _scopes_in(text)
    assert not missing, f"{method}: no op carries the scopes {missing}"


def test_rank_step_carries_distance_and_sort():
    from repro.kernels.sti_pipeline import make_rank_step

    x, _ = _data()
    text = make_rank_step().lower(jnp.zeros((TB, D), jnp.float32),
                                  x).compile().as_text()
    assert {"distance", "sort"} <= _scopes_in(text)


def _trace_updates(tmp_path, sess, batches):
    """Host events named session.* from a profiler trace of the updates:
    a list of (name, start_ns, end_ns, stats)."""
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        for xb, yb in batches:
            sess.update(xb, yb)
        jax.block_until_ready(sess._state)
    finally:
        jax.profiler.stop_trace()
    (path,) = Path(tmp_path).rglob("*.xplane.pb")
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("session."):
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns,
                                dict(ev.stats)))
    return sorted(out, key=lambda e: e[1])


def test_update_spans_count_points_and_slices(tmp_path):
    x, y = _data()
    sess = ValuationSession(x, y, k=K, mode="knn_shapley", test_batch=TB)
    xt, yt = _data(n=2 * TB + 3, seed=1)
    # two calls: 2 full slices and a ragged one, then one ragged slice
    batches = [(xt, yt), (xt[:5], yt[:5])]
    sess.update(*batches[0])  # compile outside the trace
    events = _trace_updates(tmp_path, sess, batches)

    updates = [e for e in events if e[0] == "session.update"]
    assert [(e[3]["points"], e[3]["slices"]) for e in updates] == [
        (2 * TB + 3, 3), (5, 1)]
    for name in ("session.pad", "session.dispatch"):
        children = [e for e in events if e[0] == name]
        assert len(children) == 4, name
        for _, s, e, _ in children:
            assert any(u[1] <= s and e <= u[2] for u in updates), (
                f"{name} span not nested in a session.update span")
    # per slice, the padding comes before its dispatch
    kinds = [e[0] for e in events if e[0] != "session.update"]
    assert kinds == ["session.pad", "session.dispatch"] * 4


def test_ragged_update_counts_real_points_not_padding(tmp_path):
    x, y = _data()
    sess = ValuationSession(x, y, k=K, mode="sti", test_batch=TB)
    xt, yt = _data(n=3, seed=2)
    sess.update(xt, yt)
    events = _trace_updates(tmp_path, sess, [(xt, yt), (xt[0], yt[0])])
    counts = [(e[3]["points"], e[3]["slices"]) for e in events
              if e[0] == "session.update"]
    assert counts == [(3, 1), (1, 1)]
    assert sess.t_seen == 3 + 3 + 1
