"""`ValuationSession`: constant-memory streaming valuation over unbounded t.

The method-generic pipeline's donated-accumulator step makes EVERY
registered valuation method a pure fold over test batches:
state <- step(state, xb, yb, mask, ...). A session owns that fold so test
points can arrive incrementally (online valuation, a test set that does not
fit in memory, or a service endpoint):

    sess = ValuationSession(x_train, y_train, k=5)            # mode="sti"
    sess = ValuationSession(x_train, y_train, mode="knn_shapley")
    for xb, yb in test_stream:
        sess.update(xb, yb)
    result = sess.finalize()          # ValuationResult, averaged over t

`mode` is any method with a registered streaming kernel
(`repro.kernels.stream_kernels`): "sti"/"sii" fold an (n, n) accumulator +
(n,) diagonal; "knn_shapley"/"wknn"/"loo" fold a single (n,) vector --
the state layout lives in the method's `AccumulatorSpec`, so the session
code is one fold for all of them. `method_opts` carries method statics
(e.g. {"weights": "inverse"} for wknn).

Every batch is padded to the compiled `test_batch` shape with a validity
mask (`pad_test_batch`), so ONE executable serves full and ragged batches
alike. Peak device memory is O(state + test_batch * n) regardless of how
many updates arrive. `finalize()` is a snapshot -- the session keeps
accepting updates afterwards. `checkpoint()` / `ValuationSession.restore()`
persist the partial sums (npz) so a long-running valuation survives
preemption: the accumulators are plain sums, so a restored session
continues exactly where the saved one stopped.

`ShardedValuationSession` is the multi-device form (DESIGN.md Sec. 10/12):
the test stream is row-sharded over a 1-D device mesh and the state is
sharded per its spec layout -- (n/D, n) row blocks for the interaction
matrix, (n/D,) row shards for vectors -- gathered only at `finalize()`.
Checkpoints are written as the dense host arrays, so a stream checkpointed
under D devices restores under any device count (including 1: the session
silently falls back to the single-device step when only one shard is
usable).
"""

from __future__ import annotations

import json
import os
from pathlib import Path
from typing import Callable, Optional

import numpy as np
import jax
import jax.numpy as jnp

from repro.core.results import ValuationResult

__all__ = [
    "ValuationSession",
    "ShardedValuationSession",
    "ApproxValuationSession",
]


class ValuationSession:
    """Streaming valuation of any registered method against a fixed
    training set (see module docstring)."""

    _ENGINE = "session"

    def __init__(self, x_train, y_train, *, k: int = 5, mode: str = "sti",
                 test_batch: int = 256, fill: str = "auto",
                 fill_params: Optional[dict] = None, distance: str = "auto",
                 distance_params: Optional[dict] = None,
                 autotune: bool = False,
                 method_opts: Optional[dict] = None,
                 embed_fn: Optional[Callable] = None):
        from repro.kernels.stream_kernels import stream_methods

        if mode not in stream_methods():
            raise ValueError(
                f"unknown mode {mode!r}; choose from {stream_methods()}"
            )
        if k < 1:
            raise ValueError("k must be >= 1")
        self._embed = embed_fn or (lambda x: x)
        self.x_train = jnp.asarray(self._embed(jnp.asarray(x_train)))
        self.y_train = jnp.asarray(y_train)
        if self.x_train.ndim != 2:
            raise ValueError("train features must be (num_points, dim)")
        self.k = int(k)
        self.mode = mode
        self.test_batch = max(1, int(test_batch))
        self.method_opts = dict(method_opts or {})
        self._t = 0
        # hook: subclasses build their own step/accumulators (sharded)
        self._build(fill, fill_params, distance, distance_params, autotune)

    def _build(self, fill, fill_params, distance, distance_params, autotune):
        from repro.kernels.sti_pipeline import prepare_stream_step

        n, d = self.x_train.shape
        self._step, self._resolved, self._spec = prepare_stream_step(
            self.mode, n, d, self.k, test_batch=self.test_batch,
            fill=fill, fill_params=fill_params, distance=distance,
            distance_params=distance_params, autotune=autotune,
            method_opts=self.method_opts,
        )
        self._state = self._spec.init(n)

    # ------------------------------------------------------ legacy accessors
    @property
    def _acc(self):
        """First state array (the (n, n) accumulator for interaction modes;
        kept for callers/tests that predate the generic state tuple)."""
        return self._state[0]

    @property
    def _diag(self):
        """Interaction modes' (n,) diagonal accumulator (legacy accessor)."""
        return self._state[1]

    # -------------------------------------------------------------- updates
    @property
    def t_seen(self) -> int:
        """Number of test points consumed so far."""
        return self._t

    def update(self, x_test_batch, y_test_batch) -> "ValuationSession":
        """Fold one batch of test points into the accumulator state.

        Batches of any size: the batch is consumed in `test_batch` slices,
        each padded to the compiled shape with a zero validity mask, so the
        ONE cached executable serves every slice (a stream of tiny updates
        pays the full test_batch step cost per update -- size `test_batch`
        to the arrival granularity). Returns self (chainable).

        Host spans (`jax.profiler.TraceAnnotation`, written only while a
        profiler trace is on): `session.update` around the call, with the
        counters `points` (real test points) and `slices` (compiled steps
        dispatched); per slice `session.pad` (slicing and padding) and
        `session.dispatch` (placement and the step call).
        """
        from repro.kernels.sti_pipeline import pad_test_batch

        with jax.profiler.TraceAnnotation("session.update") as span:
            xb = jnp.asarray(self._embed(jnp.asarray(x_test_batch)))
            yb = jnp.asarray(y_test_batch)
            if xb.ndim == 1:  # a single test point
                xb = xb[None, :]
                yb = jnp.reshape(yb, (1,))
            if xb.ndim != 2 or xb.shape[1] != self.x_train.shape[1]:
                raise ValueError(
                    f"test batch must be (b, {self.x_train.shape[1]}), "
                    f"got {xb.shape}"
                )
            b = xb.shape[0]
            span.set_metadata(points=b, slices=-(-b // self.test_batch))
            for start in range(0, b, self.test_batch):
                with jax.profiler.TraceAnnotation("session.pad"):
                    sl = slice(start, min(start + self.test_batch, b))
                    xs, ys, mask = pad_test_batch(
                        xb[sl], yb[sl], self.test_batch
                    )
                with jax.profiler.TraceAnnotation("session.dispatch"):
                    self._state = self._step(
                        self._state, *self._place_batch(xs, ys, mask),
                        self.x_train, self.y_train,
                    )
            self._t += b
        return self

    def _place_batch(self, xs, ys, mask):
        """Hook: device placement of one padded batch (sharded override)."""
        return xs, ys, mask

    def set_train(self, x_train, y_train) -> None:
        """Replace the training arrays IN PLACE, same (n, d) shape.

        The compiled step and the accumulator state are shape-keyed, so
        only a same-shape replacement is legal -- this is the hook the
        online valuation service's fixed-capacity mutation scheme uses
        (removed/free slots carry `stream_kernels.SENTINEL_COORD` /
        `SENTINEL_LABEL`, so they rank last and contribute exactly zero).
        Raw features: `embed_fn` is applied exactly as in the constructor.
        """
        x = jnp.asarray(self._embed(jnp.asarray(x_train)))
        y = jnp.asarray(y_train)
        if x.shape != self.x_train.shape:
            raise ValueError(
                f"set_train must keep the train shape {self.x_train.shape}, "
                f"got {x.shape} (the step and state are shape-keyed)"
            )
        self.x_train = x
        self.y_train = y

    # ------------------------------------------------------------- results
    def _gathered_state(self) -> tuple:
        """Hook: the state as whole host-addressable arrays (sharded
        sessions re-place their shards as replicated)."""
        return self._state

    def _finalize_arrays(self) -> dict:
        """Hook: the finalized `ValuationResult` array kwargs (the approx
        session densifies its sparse pair accumulator here)."""
        return self._spec.result_arrays(self._gathered_state(), self._t)

    def finalize(self) -> ValuationResult:
        """Snapshot the running mean as a `ValuationResult` (the session
        remains live; later updates refine the next finalize)."""
        if self._t == 0:
            raise ValueError("no test points seen: call update() first")
        arrays = self._finalize_arrays()
        meta = {
            "method": self.mode,
            "mode": self.mode,
            "engine": self._ENGINE,
            "streamed": True,
            "k": self.k,
            "n": int(self.x_train.shape[0]),
            "t": self._t,
            "d": int(self.x_train.shape[1]),
            "test_batch": self.test_batch,
            "backend": jax.default_backend(),
            **{f"opt_{k_}": v for k_, v in self.method_opts.items()},
            **self._resolved,
        }
        meta["resolved_fill"] = self._resolved.get("fill")
        return ValuationResult(method=self.mode, meta=meta, **arrays)

    # --------------------------------------------------------- persistence
    def _extra_config(self) -> dict:
        """Hook: subclass additions to the checkpoint config blob."""
        return {}

    def checkpoint(self, path) -> Path:
        """Persist the partial sums + config to `<path>.npz`.

        State is saved as dense host arrays under the spec's stable names
        ("acc"/"diag" for interaction modes, "vec" for point-value modes;
        sharded sessions gather their shards first), so a checkpoint
        restores under any device count.

        The write is ATOMIC: bytes go to a `.tmp` sibling which is fsync'd
        and then renamed over the final path, so a preemption mid-write can
        never leave a truncated `.npz` that `restore()` half-loads -- the
        previous checkpoint (if any) stays intact until the new one is
        fully on disk.
        """
        base = Path(path)
        if base.suffix == ".npz":
            base = base.with_suffix("")
        base.parent.mkdir(parents=True, exist_ok=True)
        cfg = {
            "k": self.k, "mode": self.mode, "test_batch": self.test_batch,
            "t": self._t, "resolved": self._resolved,
            "method_opts": self.method_opts,
            **self._extra_config(),
        }
        arrays = self._checkpoint_arrays()
        out = base.with_suffix(".npz")
        tmp = base.with_suffix(".npz.tmp")
        try:
            with open(tmp, "wb") as f:
                np.savez_compressed(
                    f, config=np.asarray(json.dumps(cfg)), **arrays
                )
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, out)
        finally:
            tmp.unlink(missing_ok=True)
        return out

    def _checkpoint_arrays(self) -> dict:
        """Hook: the named host arrays a checkpoint persists (the approx
        session appends its sparse pair-accumulator arrays)."""
        return {
            name: np.asarray(a)
            for name, a in zip(self._spec.names, self._gathered_state())
        }

    @classmethod
    def _restore_opts(cls, cfg: dict) -> dict:
        """Hook: constructor kwargs a subclass recovers from the config."""
        return {}

    @classmethod
    def _state_names(cls, cfg: dict) -> tuple:
        """Hook: the checkpoint array names to load for this config (the
        spec's stable names by default; the approx interaction session adds
        its sparse pair arrays)."""
        from repro.kernels.stream_kernels import accumulator_spec

        return accumulator_spec(cfg["mode"]).names

    def _restore_extra(self, cfg: dict) -> None:
        """Hook: reinstall non-array checkpoint state after the accumulator
        arrays are placed (e.g. the approx session's probe statistics)."""

    @classmethod
    def restore(cls, path, x_train, y_train, *,
                embed_fn: Optional[Callable] = None,
                **session_opts) -> "ValuationSession":
        """Rebuild a session from `checkpoint()` output plus the (fixed)
        training set; continues exactly where the saved session stopped."""
        base = Path(path)
        if base.suffix != ".npz":
            base = base.with_suffix(".npz")
        with np.load(base) as z:
            cfg = json.loads(str(z["config"]))
            arrays = tuple(z[name] for name in cls._state_names(cfg))
        # default to the checkpoint's RESOLVED fill/distance so the restored
        # session runs the same (possibly autotuned) implementations; the
        # caller may override, e.g. when restoring on a different backend.
        # (The sharded engine reports its fill under a rect_-prefixed name
        # from the rectangular registry -- leave those to re-resolve, or
        # pass fill= explicitly to pin a rect variant; point-value modes
        # have no fill at all. "megakernel" is a whole-step fill outside
        # the square registry -- the prepare_* paths branch on it before
        # resolve_fill, so it round-trips as-is.)
        from repro.core.sti_knn import _FILL_FNS

        for opt in ("fill", "distance"):
            value = cfg.get("resolved", {}).get(opt)
            if value is None or (
                opt == "fill"
                and value != "megakernel"
                and value not in _FILL_FNS
            ):
                continue
            session_opts.setdefault(opt, value)
        if cfg.get("method_opts"):
            session_opts.setdefault("method_opts", cfg["method_opts"])
        for opt, value in cls._restore_opts(cfg).items():
            session_opts.setdefault(opt, value)
        sess = cls(
            x_train, y_train, k=cfg["k"], mode=cfg["mode"],
            test_batch=cfg["test_batch"], embed_fn=embed_fn, **session_opts,
        )
        if arrays[0].shape[0] != sess.x_train.shape[0]:
            raise ValueError(
                f"checkpoint is for n={arrays[0].shape[0]} train points, "
                f"got n={sess.x_train.shape[0]}"
            )
        sess._place_state(arrays)
        sess._t = int(cfg["t"])
        sess._restore_extra(cfg)
        return sess

    def _place_state(self, arrays) -> None:
        """Hook: install restored accumulator arrays (sharded sessions
        re-place them with their spec shardings)."""
        self._state = tuple(jnp.asarray(a) for a in arrays)


class ShardedValuationSession(ValuationSession):
    """Multi-device streaming valuation: test stream row-sharded over a 1-D
    mesh, accumulator state sharded per its spec layout ((n/D, n) row blocks
    for the interaction matrix, (n/D,) rows for vectors), gathered only at
    finalize/checkpoint.

    `shards=None` uses every local device (clamped to a divisor of n via
    `repro.distributed.sharding.shard_count`); `shards=1` -- or a single-
    device host -- falls back to the plain single-device step, so the same
    code path runs everywhere. `test_batch` is rounded UP to a multiple of
    the shard count (the validity mask absorbs ragged input).
    """

    _ENGINE = "sharded"

    def __init__(self, x_train, y_train, *, shards: Optional[int] = None,
                 mesh=None, **opts):
        self._requested_shards = shards
        self._requested_mesh = mesh
        self.mesh = None
        self.shards = 1
        super().__init__(x_train, y_train, **opts)

    def _build(self, fill, fill_params, distance, distance_params, autotune):
        from repro.distributed.sharding import shard_count
        from repro.kernels.stream_kernels import accumulator_spec

        n = int(self.x_train.shape[0])
        spec = accumulator_spec(self.mode)
        if self._requested_mesh is not None:
            m = self._requested_mesh
            self.shards = int(m.shape[m.axis_names[0]])
        else:
            self.shards = shard_count(n, self._requested_shards)
        if self.shards <= 1:
            # single-host fallback: the single-device step IS the 1-shard
            # layout. Rect-registry hints (block_rows/block_cols) are layout
            # hints for the sharded interaction fill -- drop whatever the
            # square fill cannot accept so a sharded invocation runs
            # unchanged on a 1-device host instead of raising.
            if spec.kind == "interaction" and fill_params and fill != "auto":
                from repro.core.sti_knn import _FILL_FNS, _accepted_params

                if fill in _FILL_FNS:
                    fill_params = _accepted_params(
                        _FILL_FNS[fill], fill_params
                    )
            super()._build(fill, fill_params, distance, distance_params,
                           autotune)
            self._resolved = dict(self._resolved, shards=1)
            return
        from repro.kernels.sti_pipeline import prepare_sharded_stream_step

        d = int(self.x_train.shape[1])
        self._step, self._resolved, self.mesh, self._spec = (
            prepare_sharded_stream_step(
                self.mode, n, d, self.k, mesh=self._requested_mesh,
                shards=self.shards, test_batch=self.test_batch, fill=fill,
                fill_params=fill_params, distance=distance,
                distance_params=distance_params, autotune=autotune,
                method_opts=self.method_opts,
            )
        )
        self.test_batch = int(self._resolved["test_batch"])
        self._state = self._spec.init(
            n, self._spec.shardings(self.mesh, self.mesh.axis_names[0])
        )
        from repro.distributed.sharding import replicated_sharding

        rep = replicated_sharding(self.mesh)
        self.x_train = jax.device_put(self.x_train, rep)
        self.y_train = jax.device_put(self.y_train, rep)

    def set_train(self, x_train, y_train) -> None:
        """Same-shape train replacement, re-placed replicated on the mesh
        (see `ValuationSession.set_train`)."""
        super().set_train(x_train, y_train)
        if self.mesh is not None:
            from repro.distributed.sharding import replicated_sharding

            rep = replicated_sharding(self.mesh)
            self.x_train = jax.device_put(self.x_train, rep)
            self.y_train = jax.device_put(self.y_train, rep)

    def _place_batch(self, xs, ys, mask):
        if self.mesh is None:
            return xs, ys, mask
        from repro.distributed.sharding import (
            row_vector_sharding,
            stream_sharding,
        )

        axis = self.mesh.axis_names[0]
        vec = row_vector_sharding(self.mesh, axis=axis)
        return (
            jax.device_put(xs, stream_sharding(self.mesh, axis=axis)),
            jax.device_put(ys, vec),
            jax.device_put(mask, vec),
        )

    def _place_state(self, arrays) -> None:
        if self.mesh is None:
            super()._place_state(arrays)
            return
        axis = self.mesh.axis_names[0]
        shardings = self._spec.shardings(self.mesh, axis)
        # host arrays go straight to their shards: jnp.asarray first would
        # put the whole (n, n) array on one device
        self._state = tuple(
            jax.device_put(a, s) for a, s in zip(arrays, shardings)
        )

    def _gathered_state(self) -> tuple:
        if self.mesh is None:
            return self._state
        from repro.distributed.sharding import replicated_sharding

        rep = replicated_sharding(self.mesh)
        return tuple(jax.device_put(a, rep) for a in self._state)

    def _extra_config(self) -> dict:
        return {"shards": self.shards}

    @classmethod
    def _restore_opts(cls, cfg: dict) -> dict:
        # request the checkpoint's shard count; shard_count() re-clamps it
        # to whatever THIS host can satisfy (possibly 1 -> fused fallback)
        return {"shards": cfg["shards"]} if "shards" in cfg else {}


class ApproxValuationSession(ValuationSession):
    """Approximate top-m streaming valuation (`engine="approx"`).

    Same fold contract as `ValuationSession`, but each test point is
    compared against only the `top_m` candidates an LSH index proposes
    (`repro.kernels.ann`; DESIGN.md Sec. 16) -- O(t (L log n + L W d +
    m log m)) instead of O(t n d + t n log n), with point values landing
    via O(m) scatter-adds and STI pairs in a host-side COO accumulator
    that stores only pairs that ever co-occur in a candidate set.

    The error knob is CERTIFIED, not heuristic: every step probes its
    first `recall_sample` rows against an exact distance row, and
    `finalize()` reports the measured candidate recall plus the matched-
    prefix-derived bound from `repro.core.approx` in
    meta["recall_estimate"] / meta["error_bound"]. `recall_target` adds
    meta["recall_target_met"] so callers can reject a run whose index was
    too weak.

    Determinism: LSH tables are built from `jax.random.key(seed)`, the
    COO merge is a stable host-side reduction, and a checkpoint persists
    the probe statistics and sparse state -- two identical runs, or a
    mid-stream checkpoint/restore, are bit-identical. With `top_m >= n`
    (the default) the session dispatches to the dense exact step -- the
    SAME executable as the exact engine, so m=n is bit-identical to exact
    by construction and meta reports error_bound 0.
    """

    _ENGINE = "approx"

    def __init__(self, x_train, y_train, *, top_m: Optional[int] = None,
                 seed: int = 0, n_tables: Optional[int] = None,
                 n_bits: int = 16, window: Optional[int] = None,
                 recall_sample: int = 8, recall_k: Optional[int] = None,
                 recall_target: Optional[float] = None, **opts):
        self.top_m = None if top_m is None else int(top_m)
        self.seed = int(seed)
        self.n_bits = int(n_bits)
        self.recall_sample = int(recall_sample)
        self.recall_k = None if recall_k is None else int(recall_k)
        self.recall_target = (
            None if recall_target is None else float(recall_target)
        )
        self._requested_tables = n_tables
        self._requested_window = window
        self._prefix_min: Optional[int] = None
        self._recall_sum = 0.0
        self._recall_rows = 0
        self._probe_k = 0
        self._pairs = None
        self._approx_exact = False
        super().__init__(x_train, y_train, **opts)

    def _build(self, fill, fill_params, distance, distance_params, autotune):
        from repro.kernels.stream_kernels import AccumulatorSpec
        from repro.kernels.stream_kernels import accumulator_spec

        n, d = (int(s) for s in self.x_train.shape)
        m = n if self.top_m is None else min(self.top_m, n)
        self.m = m
        if m >= n:
            # Exact fallback: the candidate list would be the whole train
            # set, so run the dense step instead -- the SAME executable as
            # the exact engine (bit-identity at m=n is by construction, not
            # by numerical luck: a float scatter-add path could never
            # guarantee it).
            self._approx_exact = True
            super()._build(
                fill, fill_params, distance, distance_params, autotune
            )
            self._resolved = dict(
                self._resolved, top_m=m, approx_exact=True
            )
            return
        if m < self.k + 1:
            raise ValueError(
                f"top_m must be >= k+1 = {self.k + 1} (the KNN utility and "
                f"the loo window need the first k+1 neighbours), got {m}"
            )
        spec = accumulator_spec(self.mode)
        ann_l, ann_w = self._requested_tables, self._requested_window
        if ann_l is None or ann_w is None:
            from repro.kernels.autotune import best_ann

            tuned_l, tuned_w = best_ann(
                n, self.test_batch, d, m, allow_tune=autotune
            )
            ann_l = int(ann_l or tuned_l)
            ann_w = int(ann_w or tuned_w)
        ann_l, ann_w = int(ann_l), min(int(ann_w), n)
        if ann_l * ann_w < m:  # pool must be able to cover top_m
            ann_w = min(n, -(-m // ann_l))
        from repro.kernels.ann import build_tables

        self._tables = build_tables(
            self.x_train, key=jax.random.key(self.seed),
            n_tables=ann_l, n_bits=self.n_bits,
        )
        probe_k = (
            self.recall_k if self.recall_k is not None
            else min(2 * self.k + 2, m)
        )
        self._probe_k = max(1, min(int(probe_k), m))
        probe = max(0, min(self.recall_sample, self.test_batch))
        if spec.kind == "point":
            from repro.kernels.sti_pipeline import make_approx_point_step

            inner = make_approx_point_step(
                self.mode, self.k, n, m, ann_w, probe, self._probe_k,
                tuple(sorted(self.method_opts.items())),
            )
            self._spec = spec
            self._state = spec.init(n)

            def step(state, xs, ys, mask, xtr, ytr):
                vec, prefix, recall = inner(
                    state[0], xs, ys, mask, xtr, ytr, self._tables
                )
                self._fold_probe(prefix, recall, mask)
                return (vec,)
        else:
            from repro.kernels.sti_pipeline import (
                ApproxPairAccumulator,
                make_approx_interaction_step,
            )

            inner = make_approx_interaction_step(
                self.mode, self.k, n, m, ann_w, probe, self._probe_k
            )
            # sparse interaction state: a dense (n,) EXACT diagonal on
            # device plus the host COO pair accumulator
            self._spec = AccumulatorSpec("point", ("diag",), ("vector",))
            self._state = (jnp.zeros((n,), jnp.float32),)
            self._pairs = ApproxPairAccumulator(n)

            def step(state, xs, ys, mask, xtr, ytr):
                diag, rows, cols, vals, prefix, recall = inner(
                    state[0], xs, ys, mask, xtr, ytr, self._tables
                )
                self._pairs.add(
                    np.asarray(rows), np.asarray(cols), np.asarray(vals)
                )
                self._fold_probe(prefix, recall, mask)
                return (diag,)

        step.inner = inner
        self._step = step
        self._resolved = {
            "fill": None, "distance": "candidates", "top_m": m,
            "approx_exact": False, "n_tables": ann_l,
            "n_bits": self.n_bits, "window": ann_w,
        }

    # -------------------------------------------------------- probe folding
    def _fold_probe(self, prefix, recall, mask) -> None:
        """Fold one step's probe rows into the running recall statistics,
        counting only rows that correspond to REAL (unpadded) test points
        (real rows come first; see `pad_test_batch`)."""
        real = int(np.asarray(jnp.sum(mask)))
        s = min(int(np.asarray(prefix).shape[0]), real)
        if s <= 0:
            return
        p = np.asarray(prefix)[:s]
        r = np.asarray(recall)[:s]
        low = int(p.min())
        self._prefix_min = (
            low if self._prefix_min is None else min(self._prefix_min, low)
        )
        self._recall_sum += float(r.sum())
        self._recall_rows += s

    # -------------------------------------------------------------- results
    def _finalize_arrays(self) -> dict:
        if self._pairs is None:
            return super()._finalize_arrays()
        return {
            "phi": self._pairs.to_dense(np.asarray(self._state[0]), self._t)
        }

    def _approx_meta(self) -> dict:
        """The approx-specific result metadata: resolved m, measured recall
        and matched prefix, and the certified error bound they imply."""
        meta = {"top_m": self.m, "approx_exact": self._approx_exact}
        if self.recall_target is not None:
            meta["recall_target"] = self.recall_target
        if self._approx_exact:
            meta.update(
                recall_estimate=1.0, matched_prefix=self.m, error_bound=0.0
            )
            if self.recall_target is not None:
                meta["recall_target_met"] = True
            return meta
        recall = (
            self._recall_sum / self._recall_rows
            if self._recall_rows else None
        )
        meta.update(
            recall_estimate=recall,
            matched_prefix=self._prefix_min,
            probe_k=self._probe_k,
            probed_rows=self._recall_rows,
        )
        if self._prefix_min is not None:
            from repro.core.approx import error_bound

            meta["error_bound"] = error_bound(
                self.mode, n=int(self.x_train.shape[0]), k=self.k,
                m=self.m, prefix=self._prefix_min,
            )
        if self._pairs is not None:
            meta["pairs_stored"] = self._pairs.nnz
        if self.recall_target is not None and recall is not None:
            meta["recall_target_met"] = bool(recall >= self.recall_target)
        return meta

    def finalize(self) -> ValuationResult:
        """Exact-fallback or sparse finalize plus the approx metadata
        (recall estimate, matched prefix, certified error bound)."""
        return super().finalize().with_meta(**self._approx_meta())

    # ---------------------------------------------------------- persistence
    def _extra_config(self) -> dict:
        return {
            "approx": {
                "top_m": self.m,
                "seed": self.seed,
                "n_tables": self._resolved.get("n_tables"),
                "n_bits": self.n_bits,
                "window": self._resolved.get("window"),
                "recall_sample": self.recall_sample,
                "recall_k": self.recall_k,
                "recall_target": self.recall_target,
                "exact": self._approx_exact,
            },
            "probe": {
                "prefix_min": self._prefix_min,
                "recall_sum": self._recall_sum,
                "recall_rows": self._recall_rows,
            },
        }

    def _checkpoint_arrays(self) -> dict:
        arrays = super()._checkpoint_arrays()
        if self._pairs is not None:
            keys, vals = self._pairs.state()
            arrays["pair_keys"] = keys
            arrays["pair_vals"] = vals
        return arrays

    @classmethod
    def _state_names(cls, cfg: dict) -> tuple:
        from repro.kernels.stream_kernels import accumulator_spec

        approx = cfg.get("approx", {})
        if approx.get("exact", False):
            return super()._state_names(cfg)
        if accumulator_spec(cfg["mode"]).kind == "interaction":
            return ("diag", "pair_keys", "pair_vals")
        return super()._state_names(cfg)

    @classmethod
    def _restore_opts(cls, cfg: dict) -> dict:
        approx = cfg.get("approx", {})
        keys = (
            "top_m", "seed", "n_tables", "n_bits", "window",
            "recall_sample", "recall_k", "recall_target",
        )
        return {k_: approx[k_] for k_ in keys if approx.get(k_) is not None}

    def _place_state(self, arrays) -> None:
        if self._pairs is not None and len(arrays) == 3:
            diag, keys, vals = arrays
            self._state = (jnp.asarray(diag),)
            self._pairs.load(keys, vals)
            return
        super()._place_state(arrays)

    def _restore_extra(self, cfg: dict) -> None:
        probe = cfg.get("probe", {})
        low = probe.get("prefix_min")
        self._prefix_min = None if low is None else int(low)
        self._recall_sum = float(probe.get("recall_sum", 0.0))
        self._recall_rows = int(probe.get("recall_rows", 0))
