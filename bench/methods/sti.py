"""Reference check of the `sti` cells: rows of the (n, n) state.

After the window, `rows` train points drawn from the seed (an equal share
from every chip's row block) are read from the accumulator, together with
the whole diagonal. The rows are read from the state, not through
`finalize()`: finalize copies the whole (n, n) state to the host (9.3 GiB
at n=50,000, 37.25 GiB over four chips at n=100,000), where the check needs
a few rows and the diagonal. The reference folds
every test batch the session was fed, in the same order, through the plain
distance, sort, rank, recurrence and literal max-gather
(`reference.sti_rows_batch`).

Two numbers are compared, each max |program - reference| over the largest
reference entry of its part:
  rows_gap  the off-diagonal entries of the rows (the fill);
  diag_gap  the diagonal (the main terms).
"""

from __future__ import annotations

import numpy as np
import jax.numpy as jnp

import reference as R


class Check:
    def __init__(self, cfg: dict, seed: int, shards: int):
        n, count = int(cfg["n"]), int(cfg["check"]["rows"])
        rng = np.random.default_rng(int(seed) + 1)
        block = n // shards
        per = max(1, count // shards)
        self.rows = np.sort(np.concatenate([
            c * block + rng.choice(block, per, replace=False)
            for c in range(shards)])).astype(np.int32)
        self.k = int(cfg["k"])

    def warm(self, state) -> None:
        """Nothing runs in the window beyond the session's own step."""

    def before_step(self, step: int, state) -> None:
        """Nothing to keep during the window: the state holds every step."""

    def poll(self) -> None:
        """Nothing kept on the device to bring to the host."""

    def _offdiag(self, rows):
        rows = np.array(rows, np.float64)
        rows[np.arange(len(self.rows)), self.rows] = 0.0
        return rows

    def after_window(self, session) -> None:
        """Read the sampled rows and the diagonal of the state."""
        acc, diag = session._state
        rows = np.asarray(jnp.take(acc, jnp.asarray(self.rows), axis=0))
        self.got = {"rows": self._offdiag(rows),
                    "diag": np.asarray(diag, np.float64)}

    def reference(self, fed, x, y, xb, yb, *, prec: str = "f32") -> dict:
        """The same parts from the plain reference, folding the batches
        `fed` in order (a control when `prec` is not "f32")."""
        n = x.shape[0]
        idx = jnp.asarray(self.rows)
        acc = jnp.zeros((len(self.rows), n), jnp.float32)
        diag = jnp.zeros((n,), jnp.float32)
        for b in fed:
            acc, diag = R.sti_rows_batch(acc, diag, xb[b], yb[b], x, y, idx,
                                         k=self.k, prec=prec)
        return {"rows": self._offdiag(acc.astype(jnp.float32)),
                "diag": np.asarray(diag.astype(jnp.float32), np.float64)}

    def numbers(self, got: dict, want: dict) -> dict:
        gaps = {k: R.rel_gap(got[k], want[k]) for k in got}
        return {"rows_gap": gaps["rows"], "diag_gap": gaps["diag"]}, gaps
