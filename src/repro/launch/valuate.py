"""Data-valuation launcher: the paper's pipeline end-to-end.

  PYTHONPATH=src python -m repro.launch.valuate --n 512 --t 128 --k 5

Pipeline: (synthetic or embedded) features -> valuation method from the
registry (any of `repro.core.list_methods()`, each on any engine from its
`repro.core.methods.ENGINES` row) -> `ValuationResult` analytics
(efficiency check, mislabel detection quality). `--save` persists the
result artifact (npz + JSON metadata); `--stream` drives the same
computation through a `ValuationSession` in test-batch increments to
exercise the constant-memory online path -- for EVERY method with a
streaming kernel (interactions and per-point values alike), and
`--engine sharded --stream` opens the multi-device sharded session.
`--engine approx [--top-m M --recall-target R]` runs the LSH top-m
approximate engine (certified error bound + measured recall in result
meta; `--top-m >= n` is bit-for-bit the exact engine).
`--resilient` (implies --stream) drives the same fold through the
fault-tolerant `ResilientValuationSession`: StepGuard retries with
backoff, periodic atomic checkpoints under `--ckpt-dir` every
`--ckpt-every` batches, NaN rollback, and -- with a checkpoint already on
disk -- resume-and-replay with exactly-once fold semantics.
"""

from __future__ import annotations

import argparse
import time

import jax.numpy as jnp
import numpy as np

from repro.core import get_method, knn_shapley_values, list_methods, loo_values
from repro.core.methods import valid_engines
from repro.core.session import ShardedValuationSession, ValuationSession
from repro.data import make_circles, flip_labels
from repro.launch.compile_cache import enable_compile_cache


def main():
    """Parse CLI args, run the requested method/engine, print analytics."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=512)
    ap.add_argument("--t", type=int, default=128)
    ap.add_argument("--k", type=int, default=5)
    ap.add_argument("--noise-frac", type=float, default=0.1)
    ap.add_argument("--method", "--mode", dest="method", default="sti",
                    help=f"registered valuation method: {list_methods()}")
    ap.add_argument("--engine", default=None,
                    help="execution engine; default = the method's first "
                         "ENGINES entry (repro.core.methods.ENGINES). "
                         "Interaction methods: fused | scan | distributed "
                         "| sharded | approx. Point methods: streamed | "
                         "eager | sharded | approx | oracle (oracle: parity "
                         "only, n <= 16)")
    ap.add_argument("--top-m", type=int, default=None,
                    help="candidate-set size for --engine approx (LSH top-m "
                         "preselection; default n/4 clamped to [k+1, n]; "
                         "--top-m >= n runs the exact engine bit-for-bit)")
    ap.add_argument("--recall-target", type=float, default=None,
                    help="for --engine approx: record whether the measured "
                         "candidate recall met this target in result meta")
    ap.add_argument("--shards", type=int, default=None,
                    help="device count for --engine sharded (default: all "
                         "local devices, clamped to a divisor of n)")
    ap.add_argument("--fill", default="auto",
                    help="fill registry entry (auto|chunked|onehot|xla|"
                         "pallas) for interaction methods; --engine sharded "
                         "resolves it against the rectangular fill registry "
                         "(Pallas row-block kernel on TPU, XLA block scan "
                         "elsewhere). 'megakernel' fuses the whole step "
                         "(distance -> streaming top-k -> update) into one "
                         "Pallas kernel for ANY streaming method, point "
                         "methods included (DESIGN.md Sec. 17)")
    ap.add_argument("--weights", default="rbf",
                    help="wknn weight kind (rbf|inverse|uniform)")
    ap.add_argument("--test-batch", type=int, default=256)
    ap.add_argument("--autotune", action="store_true",
                    help="time fill/block candidates for this size once and "
                         "persist the winner in the autotune cache")
    ap.add_argument("--distributed", action="store_true",
                    help="alias for --engine distributed")
    ap.add_argument("--stream", action="store_true",
                    help="drive the valuation through a streaming "
                         "ValuationSession instead of one-shot (any method "
                         "with a streaming kernel)")
    ap.add_argument("--resilient", action="store_true",
                    help="wrap the streaming session in the fault-tolerant "
                         "runtime (guarded retries, periodic atomic "
                         "checkpoints, NaN rollback); implies --stream")
    ap.add_argument("--ckpt-dir", default=None, metavar="DIR",
                    help="checkpoint directory for --resilient (default: a "
                         "fresh temp dir); a directory holding a previous "
                         "run's checkpoint RESUMES it (replayed batches are "
                         "skipped exactly-once)")
    ap.add_argument("--ckpt-every", type=int, default=4,
                    help="checkpoint cadence in batches for --resilient "
                         "(0 disables checkpointing and rollback)")
    ap.add_argument("--save", default=None, metavar="PATH",
                    help="persist the ValuationResult to PATH.npz + PATH.json")
    args = ap.parse_args()
    enable_compile_cache()
    if args.distributed:
        args.engine = "distributed"
    if args.resilient:
        args.stream = True
    ve = valid_engines(args.method)
    if args.engine is not None and ve is not None and args.engine not in ve:
        ap.error(f"--engine {args.engine} invalid for --method "
                 f"{args.method}; valid engines: {ve}")

    x, y_clean = make_circles(args.n // 2, noise=0.08, seed=0)
    y, flipped = flip_labels(y_clean, args.noise_frac, 2, seed=1)
    xt, yt = make_circles(args.t // 2, noise=0.08, seed=2)
    # make_circles yields 2*(t//2) points per split: use the actual counts
    args.n = int(x.shape[0])
    args.t = int(xt.shape[0])

    method = get_method(args.method)
    # forward only the CLI options this method accepts (registry dispatch:
    # new methods appear here without launcher edits)
    accepted = getattr(method, "accepted_options", frozenset())
    if args.engine == "approx" and args.top_m is None:
        # a demo-friendly default: real preselection, never below k+1
        args.top_m = max(args.k + 1, args.n // 4)
    opts = {name: value for name, value in dict(
        engine=args.engine, fill=args.fill, test_batch=args.test_batch,
        autotune=args.autotune, shards=args.shards,
        weights=args.weights, top_m=args.top_m,
        recall_target=args.recall_target).items()
        if name in accepted and value is not None}
    # streaming runs through a ValuationSession (sharded when --engine
    # sharded): every built-in method has a streaming kernel; a custom
    # registered method without one falls back to one-shot with a note
    from repro.kernels.stream_kernels import has_stream_kernel

    can_stream = has_stream_kernel(args.method)
    if args.stream and not can_stream:
        print(f"note: method {args.method} has no streaming kernel; "
              f"running one-shot")
    elif args.stream and args.engine not in (None, "fused", "streamed",
                                             "sharded", "approx"):
        print(f"note: --stream folds the session step; "
              f"--engine {args.engine} ignored")
    t0 = time.time()
    if args.stream and can_stream:
        kw = dict(k=args.k, mode=args.method, test_batch=args.test_batch,
                  fill=args.fill, autotune=args.autotune)
        from repro.kernels.stream_kernels import accumulator_spec

        if accumulator_spec(args.method).kind == "point":
            # match the one-shot registry path: point engines pin
            # distance="xla" so --stream and non-stream runs of the same
            # invocation resolve the same distance kernel
            kw["distance"] = "xla"
        if args.method == "wknn":
            kw["method_opts"] = {"weights": args.weights}
        if args.resilient:
            import tempfile

            from repro.core.resilient import ResilientValuationSession

            ckpt_dir = args.ckpt_dir or tempfile.mkdtemp(
                prefix="repro-valuate-ckpt-")
            from repro.checkpoint.checkpointer import Checkpointer

            if Checkpointer(ckpt_dir).latest_step() is not None:
                sess = ResilientValuationSession.restore(ckpt_dir, x, y)
                print(f"resuming from {ckpt_dir} at batch "
                      f"{sess.batches_folded}")
            else:
                sess = ResilientValuationSession(
                    x, y, ckpt_dir=ckpt_dir, ckpt_every=args.ckpt_every,
                    sharded=args.engine == "sharded",
                    shards=args.shards if args.engine == "sharded" else None,
                    **kw)
        elif args.engine == "sharded":
            sess = ShardedValuationSession(x, y, shards=args.shards, **kw)
        elif args.engine == "approx":
            from repro.core.session import ApproxValuationSession

            sess = ApproxValuationSession(
                x, y, top_m=args.top_m, recall_target=args.recall_target,
                **kw)
        else:
            sess = ValuationSession(x, y, **kw)
        for start in range(0, args.t, args.test_batch):
            sess.update(xt[start:start + args.test_batch],
                        yt[start:start + args.test_batch])
        result = sess.finalize()
        if args.resilient:
            res = result.meta["resilience"]
            print(f"resilience: checkpoints={res['checkpoint_steps']} "
                  f"retries={res['retries']} rollbacks={res['rollbacks']} "
                  f"stragglers={res['health']['stragglers']} "
                  f"(ckpt_dir={ckpt_dir})")
    else:
        result = method(x, y, xt, yt, k=args.k, **opts)
    dt = time.time() - t0
    meta = result.meta
    print(f"{args.method} ({meta.get('engine', 'direct')}) "
          f"n={args.n} t={args.t} k={args.k}: {dt:.3f}s")

    # efficiency axiom (v(N) is the likelihood valuation, paper's v)
    from repro.core.sti_baseline import sorted_orders
    orders = sorted_orders(np.asarray(x), np.asarray(xt))
    kk = min(args.k, args.n)
    v_n = np.mean([np.sum(np.asarray(y)[orders[p, :kk]] == int(yt[p])) / args.k
                   for p in range(args.t)])
    print(f"efficiency gap |sum(phi)-v(N)| = "
          f"{float(result.efficiency_gap(v_n)):.2e}")

    # mislabel detection quality (paper Fig. 5 use case)
    scores = result.mislabel_scores(y, 2)
    order = np.argsort(-np.asarray(scores))
    n_flip = int(np.asarray(flipped).sum())
    hits = np.asarray(flipped)[order[:n_flip]].sum()
    print(f"mislabel detection: {hits}/{n_flip} flipped points in top-{n_flip}"
          f" (precision {hits/n_flip:.2f})")

    if result.phi is not None:
        sv = knn_shapley_values(x, y, xt, yt, args.k)
        lv = loo_values(x, y, xt, yt, args.k)
        # per-point aggregate of the interaction matrix (the order-2
        # Shapley-Taylor decomposition of the Shapley value)
        agg = np.asarray(result.values())
        print(f"KNN-Shapley corr with phi aggregate: "
              f"{np.corrcoef(np.asarray(sv), agg)[0, 1]:.3f}")
        print(f"LOO values range: "
              f"[{float(jnp.min(lv)):.4f}, {float(jnp.max(lv)):.4f}]")

    if args.save:
        p = result.save(args.save)
        print(f"saved {p} (+ .json metadata)")


if __name__ == "__main__":
    main()
