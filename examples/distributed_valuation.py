"""Distributed STI-KNN: the production shard_map step on a local mesh.

Run with several CPU placeholder devices to see real sharding:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python examples/distributed_valuation.py

Test points shard over 'data', the phi matrix over 'model' column blocks;
one psum over data combines the partial sums (DESIGN.md Sec. 4). The
result is verified against the single-host reference implementation.
"""

import numpy as np
import jax
import jax.numpy as jnp

from repro import compat
from repro.configs.sti_knn_paper import STIConfig
from repro.core import sti_knn_interactions
from repro.data import make_moons
from repro.launch.mesh import make_auto_mesh
from repro.launch.specs import sti_cell

n, t, k = 512, 128, 5
x, y = make_moons(n // 2, noise=0.08, seed=0)
xt, yt = make_moons(t // 2, noise=0.08, seed=1)

devs = len(jax.devices())
dmodel = 2 if devs % 2 == 0 else 1
mesh = make_auto_mesh((devs // dmodel, dmodel), ("data", "model"))
print(f"devices: {devs}, mesh: {dict(mesh.shape)}")

scfg = STIConfig(n_train=n, feat_dim=2, k=k, test_chunk=t)
step, _, _, _ = sti_cell(scfg, mesh)
with compat.set_mesh(mesh):
    acc, diag = jax.jit(step)(x, y, xt, yt, jnp.arange(n, dtype=jnp.int32))
phi = jnp.fill_diagonal(acc / t, diag / t, inplace=False)

ref = sti_knn_interactions(x, y, xt, yt, k)
err = float(jnp.max(jnp.abs(phi - ref)))
print(f"max |distributed - reference| = {err:.2e}")
assert err < 1e-5
print("[ok] distributed result matches the single-host algorithm")

# --- the streaming form: sharded fused pipeline (DESIGN.md Sec. 10) -------
# Row-sharded accumulators ((n/D, n) per device, n^2/D memory) fed by a
# row-sharded test stream; same contract as ValuationSession, so test
# points can arrive incrementally and the stream survives preemption via
# checkpoint()/restore().
from repro.core.session import ShardedValuationSession

sess = ShardedValuationSession(x, y, k=k, test_batch=32)
print(f"sharded session: {sess.shards} row shards, "
      f"test_batch={sess.test_batch}")
for start in range(0, t, 32):
    sess.update(xt[start:start + 32], yt[start:start + 32])
res = sess.finalize()
err = float(jnp.max(jnp.abs(res.phi - ref)))
print(f"max |sharded stream - reference| = {err:.2e}")
assert err < 1e-5
print("[ok] sharded streaming engine matches the single-host algorithm")
