"""Reference check of the `knn_shapley` cells: sampled steps.

The plain reference costs about as much as a step (the gathers and the
sort of every test row dominate both), so it checks a sample of the
window's steps, drawn from the seed: the first, every `snapshot_stride`-th
from a seeded offset, and the last. The (n,) state is copied before every
step (a 0.2 MB copy beside a step of tens of milliseconds), since the
window ends after whichever step passes `--seconds`; a sampled
step's contribution is the state after it less the copy before it, and
for the last step "after" is `finalize()` times t, so finalize is checked
too. `reference.knn_shapley_batch` computes each contribution again from
the train set and the batch.

The copy is made on the device, where it is ordered before the next step,
which donates the state. A kept copy (a sampled step's before and after)
starts its transfer to the host at once, and `poll()` swaps each one whose
copy has run for its host array, without waiting; the harness polls after
every step it waits for. So the device holds the kept copies of the steps
still queued, the copy of the step just issued and none of the window's
earlier snapshots when the harness reads the peak memory: the program's
peak, not one that grows with the steps in the window.

`values_gap`, the number compared: the worst over the sampled steps of
max |program - reference| over the largest entry of the reference's
contribution of that step, so that an error of one size reads the same in
the first step and the last. Each entry's gap first gives up one f32
spacing of the state after the step: the program's contribution is read
as a difference of two f32 states, and the addition into the state (and,
for the last step, finalize's division by t) rounds at that spacing, which
grows with the steps the state holds and says nothing of the step.
"""

from __future__ import annotations

import numpy as np
import jax.numpy as jnp

import reference as R


class Check:
    def __init__(self, cfg: dict, seed: int, shards: int):
        self.stride = int(cfg["check"]["snapshot_stride"])
        self.offset = int(np.random.default_rng(int(seed) + 1)
                          .integers(self.stride))
        self.k = int(cfg["k"])
        self.before, self.after = {}, {}
        self.last = None
        self.pending = []  # (dict, step) of kept copies still on the device

    def _sampled(self, step: int) -> bool:
        # step 0 is the warm step; the window's steps count from 1
        return step == 1 or (step > 1
                             and (step - 1) % self.stride == self.offset)

    def warm(self, state) -> None:
        """Compile the copy the window makes, in set-up."""
        jnp.array(state[0], copy=True).block_until_ready()

    def _keep(self, kept: dict, step: int, copy) -> None:
        copy.copy_to_host_async()
        kept[step] = copy
        self.pending.append((kept, step))

    def poll(self) -> None:
        """Bring every kept copy that has been made to the host, and drop
        its device array; wait for none."""
        waiting = []
        for kept, step in self.pending:
            if kept[step].is_ready():
                kept[step] = np.asarray(kept[step])
            else:
                waiting.append((kept, step))
        self.pending = waiting

    def before_step(self, step: int, state) -> None:
        """Copy the state before `step` is issued."""
        if step == 0:
            return
        self.poll()
        prev = jnp.array(state[0], copy=True)
        if step - 1 in self.before:
            self._keep(self.after, step - 1, prev)
        self.last, self.last_copy = step, prev
        if self._sampled(step):
            self._keep(self.before, step, prev)

    def after_window(self, session) -> None:
        """Each sampled step's contribution; the last one's through
        finalize."""
        self.before[self.last] = self.last_copy
        t = session.t_seen
        self.after[self.last] = np.asarray(
            session.finalize().point_values, np.float64) * t
        self.got, self.slack = {}, {}
        for s in sorted(self.before):
            after = np.asarray(self.after[s], np.float64)
            self.got[f"step{s}"] = after - np.asarray(self.before[s],
                                                      np.float64)
            self.slack[f"step{s}"] = np.spacing(
                np.abs(after).astype(np.float32)).astype(np.float64)
        self.before, self.after, self.last_copy = {}, {}, None
        self.pending = []

    def reference(self, fed, x, y, xb, yb, *, prec: str = "f32") -> dict:
        """Each sampled batch's contribution from the plain reference (a
        control when `prec` is not "f32")."""
        want = {}
        for key in self.got:
            b = fed[int(key[4:])]
            want[key] = np.asarray(R.knn_shapley_batch(
                xb[b], yb[b], x, y, k=self.k, prec=prec
            ).astype(jnp.float32), np.float64)
        return want

    def numbers(self, got: dict, want: dict) -> dict:
        gaps = {}
        for k in got:
            err = np.max(np.abs(got[k] - want[k]) - self.slack[k])
            scale = float(np.max(np.abs(want[k])))
            gaps[k] = (max(float(err), 0.0) / scale if scale
                       else float("inf") if err > 0 else 0.0)
        return {"values_gap": max(gaps.values())}, gaps
