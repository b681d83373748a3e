"""`ResilientValuationSession`: preemption-safe streaming valuation.

The O(t n^2) stream is hours long once n reaches the millions-of-points
regime, and long jobs on preemptible accelerators WILL be interrupted:
devices fail, steps straggle past deadlines, collectives go NaN, writes
get torn. This module wraps the streaming engine (`ValuationSession` /
`ShardedValuationSession`) in the runtime that survives all of it, wiring
together the previously stand-alone pieces: `distributed.fault_tolerance`
(StepGuard retries with backoff + HealthLog straggler flagging),
`checkpoint.Checkpointer` (atomic, checksummed, async checkpoints), and
`distributed.fault_injection` (the deterministic failure hooks that prove
the machinery works single-host).

Guarantees (DESIGN.md Sec. 13):

  * EXACTLY-ONCE FOLD -- every incoming batch carries a sequence number;
    the checkpoint records how many batches the state contains, so after a
    restore a driver can simply replay its stream from the start and
    already-folded batches are skipped, never double-counted. A recovered
    run finalizes BIT-IDENTICAL to an uninterrupted one (same executable,
    same fold order, checkpoint arrays round-trip f32-exact).
  * TRANSACTIONAL BATCHES -- a step that dies mid-fold (device loss,
    deadline overrun) leaves half-updated accumulators; before the retry
    the state is recovered from the last good checkpoint plus an in-memory
    replay buffer of the batches since, so every retry folds the batch into
    a clean base (no per-batch state copies: the step's donated buffers are
    never referenced after the call).
  * NaN/Inf ROLLBACK -- after each fold the state is checked finite;
    silent numeric poisoning triggers the same checkpoint-rollback-replay
    cycle (bounded by `max_rollbacks`).
  * GRACEFUL DEGRADATION -- when a sharded step exhausts its retry budget
    the session rebuilds on fewer devices (next divisor of n, down to
    `min_shards`), restores the dense device-count-independent checkpoint,
    replays, and continues; a single-device session re-raises instead (a
    dead process is the driver's signal to `restore()` elsewhere).

`finalize()` surfaces the whole story -- retries, rollbacks, degradations,
straggler steps, checkpoints written -- under ``ValuationResult.meta
["resilience"]``.
"""

from __future__ import annotations

import json
from pathlib import Path
from typing import Optional

import numpy as np
import jax.numpy as jnp

from repro.checkpoint.checkpointer import Checkpointer
from repro.core.results import ValuationResult
from repro.core.session import ShardedValuationSession, ValuationSession
from repro.distributed.fault_tolerance import (
    HealthLog,
    StepGuard,
    degrade_plan,
)

__all__ = ["ResilientValuationSession"]

_CONFIG_KEY = "['config']"


def _all_finite(state: tuple) -> bool:
    """True iff every array of the accumulator state is NaN/Inf-free."""
    return all(bool(jnp.all(jnp.isfinite(a))) for a in state)


def _read_config(ck: Checkpointer, step: int) -> dict:
    """Load the JSON config leaf of checkpoint `step` (needed before the
    session -- and hence the restore tree structure -- can be built)."""
    d = ck.dir / f"step_{step:08d}"
    manifest = json.loads((d / "MANIFEST.json").read_text())
    for e in manifest["leaves"]:
        if e["key"] == _CONFIG_KEY:
            return json.loads(str(np.load(d / e["file"])))
    raise KeyError(f"checkpoint step {step} carries no config leaf")


class ResilientValuationSession:
    """Fault-tolerant wrapper around the streaming valuation sessions
    (see module docstring for the guarantees and recovery state machine).

    Parameters beyond the wrapped session's (`mode`, `k`, `test_batch`,
    `method_opts`, ...):

      * ckpt_dir / ckpt_every -- checkpoint directory and cadence in
        batches (one batch = one `update()` call). `ckpt_every=0` disables
        checkpointing AND the replay buffer: failures then raise instead
        of recovering (bare-session behaviour plus guard/health metadata).
      * sharded / shards -- wrap a `ShardedValuationSession` (shards=None:
        all usable local devices) instead of the single-device session.
      * deadline_s / max_retries / backoff_s / seed -- `StepGuard` budget:
        per-attempt deadline, retry count, exponential backoff base with
        deterministic seeded jitter.
      * nan_guard / max_rollbacks -- post-fold finiteness check and the
        rollback budget for it.
      * min_shards -- floor for graceful degradation (default 1).
      * injector -- optional `FaultInjector` whose hooks fire inside the
        fold loop (tests / chaos drills); None in production.
      * async_checkpoint -- overlap checkpoint writes with the next step
        (`Checkpointer.save_async`); the state snapshot is taken
        synchronously either way, so recovery semantics do not change.
    """

    def __init__(self, x_train, y_train, *, ckpt_dir,
                 mode: str = "sti", k: int = 5,
                 ckpt_every: int = 8, keep: int = 4,
                 async_checkpoint: bool = True,
                 sharded: bool = False, shards: Optional[int] = None,
                 deadline_s: float = float("inf"), max_retries: int = 3,
                 backoff_s: float = 0.01, seed: int = 0,
                 nan_guard: bool = True, max_rollbacks: int = 3,
                 min_shards: int = 1,
                 injector=None,
                 **session_opts):
        self._x_train = x_train
        self._y_train = y_train
        self.mode = mode
        self.k = int(k)
        self.ckpt_every = int(ckpt_every)
        self.async_checkpoint = bool(async_checkpoint)
        self._sharded = bool(sharded) or shards is not None
        self.nan_guard = bool(nan_guard)
        self.max_rollbacks = int(max_rollbacks)
        self.min_shards = max(1, int(min_shards))
        self._injector = injector
        self._session_opts = dict(session_opts, mode=mode, k=k)
        self._ckpt = Checkpointer(ckpt_dir, keep=keep)
        self._guard = StepGuard(
            deadline_s=deadline_s, max_retries=max_retries,
            backoff_s=backoff_s, seed=seed, on_retry=self._on_retry,
        )
        self._health = HealthLog()
        self._stats = {
            "retries": 0, "rollbacks": 0, "nan_detected": 0,
            "degradations": [], "replayed_skipped": 0,
            "checkpoint_steps": [],
        }
        # _folded = batches in the current state; _arrived = batches this
        # process has been offered (replay dedupe compares the two)
        self._folded = 0
        self._arrived = 0
        self._buffer: list[tuple[int, np.ndarray, np.ndarray]] = []
        self._dirty = False   # state may be half-folded (failed attempt)
        self._build_inner(shards)

    # ------------------------------------------------------------ plumbing
    def _build_inner(self, shards: Optional[int]) -> None:
        if self._sharded:
            self._inner = ShardedValuationSession(
                self._x_train, self._y_train, shards=shards,
                **self._session_opts)
        else:
            self._inner = ValuationSession(
                self._x_train, self._y_train, **self._session_opts)

    def _on_retry(self, attempt: int, err) -> None:
        self._stats["retries"] += 1

    @property
    def inner(self) -> ValuationSession:
        """The wrapped (possibly rebuilt-on-degradation) session."""
        return self._inner

    @property
    def shards(self) -> int:
        """Current device count of the wrapped session (1 = single)."""
        return self._inner.shards

    @property
    def t_seen(self) -> int:
        """Test points folded into the current state."""
        return self._inner.t_seen

    @property
    def batches_folded(self) -> int:
        """Batch sequence numbers folded so far (= next expected seq)."""
        return self._folded

    # ------------------------------------------------------------- updates
    def update(self, x_test_batch, y_test_batch) -> "ResilientValuationSession":
        """Fold one batch (one sequence number) with full fault handling.

        Batches must arrive in a deterministic order; after a restore the
        driver replays its stream from the start and the first
        `batches_folded` arrivals are skipped (exactly-once fold). Returns
        self (chainable).
        """
        seq = self._arrived
        self._arrived += 1
        if seq < self._folded:
            self._stats["replayed_skipped"] += 1
            return self
        if seq > self._folded:
            raise RuntimeError(
                f"batch gap: arrived seq {seq} but state holds "
                f"{self._folded}; the driver must replay in order")
        xb = np.asarray(x_test_batch)  # sync-point: host-staged for replay
        yb = np.asarray(y_test_batch)  # sync-point: host-staged for replay
        if self.ckpt_every > 0:
            self._buffer.append((seq, xb, yb))
        self._fold(seq, xb, yb)
        return self

    def _fold(self, seq: int, xb, yb, rollback_depth: int = 0) -> None:
        """Guarded, transactional fold of batch `seq`; on guard exhaustion
        degrade (sharded) or re-raise; on NaN/Inf roll back and refold."""

        def attempt():
            if self._dirty:
                self._recover_state(upto=seq)
                self._dirty = False
            if self._injector is not None:
                self._injector.before_step(seq)
            # dirty from here: an exception or deadline overrun below may
            # leave (or has left) a partial/duplicate fold in the state
            self._dirty = True
            self._inner.update(xb, yb)
            return self._inner._state

        try:
            _, dt = self._guard.run(attempt)
        except RuntimeError:
            if not self._try_degrade():
                raise
            # degraded topology is live and recovered up to seq; refold the
            # batch that killed the old one (fresh guard budget)
            self._fold(seq, xb, yb, rollback_depth)
            return
        self._dirty = False
        self._health.record(dt)
        if self._injector is not None:
            self._inner._state = self._injector.poison_state(
                seq, self._inner._state)
        if self.nan_guard and not _all_finite(self._inner._state):
            self._stats["nan_detected"] += 1
            if self.ckpt_every <= 0:
                raise RuntimeError(
                    f"non-finite accumulator state after batch {seq} and "
                    f"no checkpointing to roll back to (ckpt_every=0)")
            if rollback_depth >= self.max_rollbacks:
                raise RuntimeError(
                    f"non-finite state persists after {rollback_depth} "
                    f"rollbacks at batch {seq}")
            self._stats["rollbacks"] += 1
            self._recover_state(upto=seq)
            self._fold(seq, xb, yb, rollback_depth + 1)
            return
        self._folded = seq + 1
        if self.ckpt_every > 0 and self._folded % self.ckpt_every == 0:
            self._checkpoint()

    # ------------------------------------------------------------ recovery
    def _recover_state(self, upto: int) -> None:
        """Restore the last good checkpoint and refold buffered batches
        with seq < `upto`, leaving the state exactly as it was before the
        failed/poisoned batch. Raw (unguarded) refolds: a failure here
        propagates to the enclosing guard attempt, whose retry runs the
        whole recovery again from a clean base."""
        self._ckpt.wait()
        step = self._ckpt.latest_verified_step()
        if step is None:
            n = int(self._inner.x_train.shape[0])
            self._inner._place_state(
                tuple(np.zeros(s, np.float32)
                      for s in self._inner._spec.shapes(n)))
            self._inner._t = 0
            self._folded = 0
        else:
            self._load_checkpoint(step)
        for q, xb, yb in self._buffer:
            if q < self._folded:
                continue
            if q >= upto:
                break
            if q > self._folded:
                raise RuntimeError(
                    f"replay buffer gap: need batch {self._folded}, next "
                    f"buffered is {q} (checkpoint too old for the buffer)")
            self._inner.update(xb, yb)
            self._folded = q + 1

    def _try_degrade(self) -> bool:
        """Rebuild the sharded session on fewer devices (next divisor of n
        below the current count); False when no degradation is possible
        (single-device session / already at min_shards). The fresh inner is
        marked dirty, so the caller's refold recovers it from the last good
        checkpoint + replay buffer before touching the failing batch."""
        cur = self.shards
        if not isinstance(self._inner, ShardedValuationSession):
            return False
        new = degrade_plan(
            int(self._inner.x_train.shape[0]), cur, self.min_shards
        )
        if new is None:
            return False
        self._stats["degradations"].append(
            {"from": int(cur), "to": int(new)})
        self._ckpt.wait()
        self._build_inner(new)
        self._dirty = True
        return True

    # ------------------------------------------------------------ mutations
    def rebase(self, state_arrays, *, t: int, seq: Optional[int] = None,
               x_train=None, y_train=None) -> None:
        """Install an externally recomputed state as the NEW ground truth.

        This is the train-set-mutation boundary of the online valuation
        service: `add_points`/`remove_points` refold the full batch log
        against the mutated train set OUTSIDE the fold loop, then rebase.
        Three invariants make recovery safe across the boundary:

          * the replay buffer is CLEARED -- pre-mutation batches must never
            be refolded against the post-mutation train set;
          * a SYNCHRONOUS checkpoint of the rebased state is written at the
            current sequence number, so rollback/restore lands on this side
            of the mutation (overwriting any same-step pre-mutation
            checkpoint);
          * `t`/`seq` reset the fold counters to what the new state
            actually contains (`seq` defaults to whatever has arrived, so
            in-order drivers just continue).

        Older checkpoints become semantically stale (pre-mutation); walking
        back to one fails fast with a replay-buffer gap instead of silently
        mixing train-set versions -- the service's full-recompute fallback
        is the recovery path beyond this boundary.
        """
        self._ckpt.wait()
        if x_train is not None:
            self._x_train = x_train
            self._y_train = y_train
            self._inner.set_train(x_train, y_train)
        self._inner._place_state(tuple(state_arrays))
        self._inner._t = int(t)
        self._folded = int(seq) if seq is not None \
            else max(self._folded, self._arrived)
        self._arrived = self._folded
        self._buffer.clear()
        self._dirty = False
        if self.ckpt_every > 0:
            self._checkpoint(force=True)
            self._ckpt.wait()

    # --------------------------------------------------------- checkpoints
    def _config(self) -> dict:
        opts = {k_: v for k_, v in self._session_opts.items()
                if isinstance(v, (str, int, float, bool, dict, list,
                                  type(None)))}
        return {
            "mode": self.mode, "k": self.k,
            "test_batch": int(self._inner.test_batch),
            "sharded": self._sharded, "shards": int(self.shards),
            "ckpt_every": self.ckpt_every, "session_opts": opts,
        }

    def _tree_like(self) -> dict:  # sync-point: checkpoint-tree host staging
        names = self._inner._spec.names
        n = int(self._inner.x_train.shape[0])
        shapes = self._inner._spec.shapes(n)
        return {
            "config": np.asarray(""),
            "scalars": {"seq": np.int64(0), "t": np.int64(0)},
            "state": {nm: np.zeros(s, np.float32)
                      for nm, s in zip(names, shapes)},
        }

    def _state_tree(self) -> dict:  # sync-point: checkpoint snapshot is
        # synchronous BY DESIGN (recovery semantics); only the WRITE is
        # overlapped with the next step via save_async
        return {
            "config": np.asarray(json.dumps(self._config())),
            "scalars": {"seq": np.int64(self._folded),
                        "t": np.int64(self._inner._t)},
            "state": dict(zip(self._inner._spec.names,
                              self._inner._host_state())),
        }

    def checkpoint(self) -> None:
        """Write a checkpoint of the current state now (also done
        automatically every `ckpt_every` batches and at `finalize`)."""
        self._checkpoint(force=True)

    def _checkpoint(self, force: bool = False) -> None:
        steps = self._stats["checkpoint_steps"]
        if steps and steps[-1] == self._folded and not force:
            return
        tree = self._state_tree()
        if self.async_checkpoint:
            self._ckpt.save_async(self._folded, tree)
        else:
            self._ckpt.save(self._folded, tree)
        steps.append(self._folded)
        if self._injector is not None:
            self._injector.after_checkpoint(self._folded, self._ckpt)
        # trim the replay buffer with ONE checkpoint of lag, so a rollback
        # still has the batches it needs if the newest checkpoint itself
        # turns out corrupted on disk
        keep_from = steps[-2] if len(steps) >= 2 else 0
        self._buffer = [e for e in self._buffer if e[0] >= keep_from]

    def _load_checkpoint(self, step: int) -> None:
        tree, _ = self._ckpt.restore(self._tree_like(), step)
        names = self._inner._spec.names
        self._inner._place_state(
            tuple(tree["state"][nm] for nm in names))
        self._inner._t = int(tree["scalars"]["t"])
        self._folded = int(tree["scalars"]["seq"])
        self._dirty = False

    @classmethod
    def restore(cls, ckpt_dir, x_train, y_train, *,
                step: Optional[int] = None, injector=None,
                **overrides) -> "ResilientValuationSession":
        """Rebuild a session from the newest VERIFIED checkpoint in
        `ckpt_dir` (corrupted steps are skipped via the Checkpointer's
        sha256 fallback walk) plus the fixed training set.

        `overrides` replace checkpointed constructor options -- pass e.g.
        ``shards=2`` to restore a stream checkpointed under 8 devices onto
        2 (the dense checkpoint is device-count independent). The restored
        session expects its driver to replay the batch stream from the
        START: the first `batches_folded` arrivals are skipped.
        """
        ck = Checkpointer(ckpt_dir)
        use = step if step is not None else ck.latest_verified_step()
        if use is None:
            raise FileNotFoundError(
                f"no (uncorrupted) checkpoint in {ckpt_dir}")
        cfg = _read_config(ck, use)
        kwargs = dict(cfg.get("session_opts", {}))
        kwargs.update(
            mode=cfg["mode"], k=cfg["k"], test_batch=cfg["test_batch"],
            ckpt_every=cfg.get("ckpt_every", 8),
        )
        if cfg.get("sharded"):
            kwargs.setdefault("sharded", True)
            kwargs.setdefault("shards", cfg.get("shards"))
        kwargs.update(overrides)
        sess = cls(x_train, y_train, ckpt_dir=ckpt_dir, injector=injector,
                   **kwargs)
        sess._load_checkpoint(use)
        return sess

    # ------------------------------------------------------------- results
    def resilience_summary(self) -> dict:
        """JSON-able digest of everything the runtime absorbed: retries,
        rollbacks, degradations, skipped replays, checkpoints, stragglers."""
        return {
            **{k_: (list(v) if isinstance(v, list) else v)
               for k_, v in self._stats.items()},
            "shards": int(self.shards),
            "health": self._health.summary(),
        }

    def finalize(self, checkpoint: bool = True) -> ValuationResult:
        """Checkpoint (unless disabled), snapshot the running mean, and
        attach the resilience story under ``meta["resilience"]``."""
        if checkpoint and self.ckpt_every > 0 and self._folded > 0:
            self._checkpoint()
            self._ckpt.wait()
        result = self._inner.finalize()
        return result.with_meta(
            resilient=True, resilience=self.resilience_summary())
