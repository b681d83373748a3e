"""Persistent block/chunk autotuner for the STI valuation hot loops.

Times candidate configurations of the fill registry (chunk sizes, Pallas
block shapes) and of the tiled distance kernel on synthetic data shaped like
the caller's problem, then caches the winner in a JSON file keyed by
(kind, backend, device-count, n-bucket, t-bucket) -- device count is part of
the key so the sharded engine's per-device slice shapes tune independently
of single-device runs. `sti_knn_interactions(..., fill="auto")`, the fused
pipeline, and `DataValuator` consult the cache on every call; a miss falls
back to a backend heuristic unless the caller opts into tuning
(`autotune=True`), so the first tuned run pays the measurement cost once and
every later process reuses it.

Cache location: $REPRO_AUTOTUNE_CACHE, else ~/.cache/repro/autotune.json.
Sizes are bucketed to the next power of two so nearby problem sizes share an
entry, and the fill is timed on a t-sample (fill cost is linear in t), which
keeps tuning to a few hundred ms even at n=4096.
"""

from __future__ import annotations

import functools
import json
import os
import sys
import tempfile
import threading
import time
from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp

__all__ = [
    "cache_path",
    "clear_cache",
    "device_platform",
    "fill_candidates",
    "default_fill",
    "autotune_fill",
    "lookup_fill",
    "best_fill",
    "rect_fill_candidates",
    "default_rect_fill",
    "autotune_rect_fill",
    "lookup_rect_fill",
    "best_rect_fill",
    "distance_candidates",
    "default_distance",
    "autotune_distance",
    "best_distance",
    "ann_candidates",
    "autotune_ann",
    "best_ann",
    "megakernel_candidates",
    "autotune_megastep",
    "lookup_megastep",
    "best_megastep",
]

_LOCK = threading.Lock()
# Fill timing is linear in t: measure on at most this many test rows and
# transfer the winner to the full t.
_SAMPLE_T = 16

# Cache schema version. v2 added the device-kind segment to every key
# (see `_key`): v1 entries are NOT platform-keyed, so an interpret-mode
# CPU tuning could be served to a TPU run of the same backend string --
# `_load` migrates by discarding any file with a different stamp (the
# cache is self-healing: dropped winners just re-tune or fall back to the
# heuristic).
_SCHEMA = 2
_SCHEMA_KEY = "__schema__"


def cache_path(path: Optional[str] = None) -> str:
    """Resolve the cache file path: explicit arg > $REPRO_AUTOTUNE_CACHE >
    ~/.cache/repro/autotune.json."""
    if path is not None:
        return path
    env = os.environ.get("REPRO_AUTOTUNE_CACHE")
    if env:
        return env
    return os.path.join(os.path.expanduser("~"), ".cache", "repro", "autotune.json")


# fill="auto" resolves on every valuation call: memoize the parsed cache per
# (path, mtime) so the hot path does one os.stat, not a JSON parse. External
# writers (other processes) bump the mtime and invalidate naturally.
_MEMO: dict[str, tuple[float, dict]] = {}


def _load(path: Optional[str]) -> dict:
    p = cache_path(path)
    try:
        mtime = os.stat(p).st_mtime_ns
    except OSError:
        _MEMO.pop(p, None)
        return {}
    hit = _MEMO.get(p)
    if hit is not None and hit[0] == mtime:
        return hit[1]
    try:
        with open(p) as f:
            data = json.load(f)
    except (OSError, ValueError):
        data = {}
    if data:
        if data.get(_SCHEMA_KEY) != _SCHEMA:
            # pre-platform-segment (or future) schema: invalidate wholesale
            data = {}
        else:
            # the stamp is a file-format detail: callers see entries only
            data = {k: v for k, v in data.items() if k != _SCHEMA_KEY}
    _MEMO[p] = (mtime, data)
    return data


def _save(path: Optional[str], data: dict) -> None:
    p = cache_path(path)
    os.makedirs(os.path.dirname(p) or ".", exist_ok=True)
    fd, tmp = tempfile.mkstemp(dir=os.path.dirname(p) or ".", suffix=".tmp")
    data = dict(data)
    data[_SCHEMA_KEY] = _SCHEMA
    try:
        with os.fdopen(fd, "w") as f:
            json.dump(data, f, indent=1, sort_keys=True)
        os.replace(tmp, p)
        _MEMO[p] = (
            os.stat(p).st_mtime_ns,
            {k: v for k, v in data.items() if k != _SCHEMA_KEY},
        )
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise


def clear_cache(path: Optional[str] = None) -> None:
    """Delete the cache file (and its in-process memo); next resolve falls
    back to the backend heuristic until re-tuned."""
    _MEMO.pop(cache_path(path), None)
    try:
        os.unlink(cache_path(path))
    except OSError:
        pass


def _bucket(x: int) -> int:
    """Next power of two >= x (>= 1): nearby sizes share a cache entry."""
    return 1 << max(0, int(np.ceil(np.log2(max(1, x)))))


@functools.lru_cache(maxsize=None)
def device_platform(backend: Optional[str] = None) -> str:
    """Short device-KIND slug for cache keys: "cpu", "tpuv4", "tpuv5e",
    "nvidiaa100"... -- lowercased alphanumerics of
    `jax.devices()[0].device_kind`. The backend string alone ("cpu"/"tpu")
    cannot distinguish TPU generations, and -- the case that matters in
    this repo's CI -- an interpret-mode Pallas timing taken on CPU must
    never be served to a real TPU run. Falls back to the backend name when
    no device of that backend is attached."""
    try:
        devices = jax.devices(backend) if backend else jax.devices()
        kind = str(devices[0].device_kind)
    except Exception:
        kind = str(backend or "unknown")
    slug = "".join(ch for ch in kind.lower() if ch.isalnum())
    return slug or "unknown"


def _key(kind: str, backend: str, n: int, t: int,
         devices: Optional[int] = None, rows: Optional[int] = None) -> str:
    """Cache key. Entries are keyed by the device PLATFORM slug (device
    kind, e.g. `cpu` / `tpuv4` -- see `device_platform`) and the visible
    DEVICE COUNT as well as backend and bucketed sizes: the sharded engine
    executes its stages on (t/D, n) and (n/D, n) slices, so a winner tuned
    single-device must not leak into multi-device runs (and vice versa),
    and a winner timed in interpret mode on CPU must never be served to a
    TPU run. Rectangular fills add a `rows{R}` segment (the bucketed
    per-device row-block height): a winner for an (n/8, n) block must not
    leak into (n/256, n) runs that share the same n/t buckets."""
    d = jax.device_count() if devices is None else int(devices)
    r = "" if rows is None else f"rows{_bucket(rows)}:"
    plat = device_platform(backend)
    return f"{kind}:{backend}:{plat}:dev{d}:{r}n{_bucket(n)}:t{_bucket(t)}"


def _candidate_failed(failed: dict, label: str, err: Exception) -> None:
    """Record and print a candidate that failed to compile or run.

    A refused candidate is a finding about the platform (e.g. a Pallas
    kernel Mosaic cannot lower), so it is kept in the cache entry's
    "failed" map with its error and printed, never dropped in silence."""
    msg = f"{type(err).__name__}: {err}".splitlines()[0][:400]
    failed[label] = msg
    print(f"autotune: candidate {label} failed: {msg}", file=sys.stderr)


def _time_call(fn, *args, reps: int = 2) -> float:
    out = fn(*args)  # compile + warmup
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e6  # us


def _synthetic_fill_problem(n: int, ts: int):
    rng = np.random.default_rng(0)
    g = jnp.asarray(rng.normal(size=(ts, n)).astype(np.float32))
    ranks = jnp.asarray(
        np.stack([rng.permutation(n) for _ in range(ts)]).astype(np.int32)
    )
    return g, ranks


# ----------------------------------------------------------------- fill ----
def fill_candidates(n: int, t: int, backend: str) -> list[tuple[str, dict]]:
    """Candidate (registry_name, static_params) per backend.

    Pallas block_t/block_n shapes only make sense compiled for TPU; in
    interpret mode they would be timed as Python and always lose, so they are
    TPU-only candidates. The one-hot MXU fill is O(t n^3) FLOPs -- only a
    contender at small n or on matmul-rich hardware.
    """
    cands: list[tuple[str, dict]] = [
        ("chunked", {"chunk": c}) for c in (1, 2, 4, 8) if c <= max(1, t)
    ]
    cands.append(("xla", {}))
    if n <= 512 or backend == "tpu":
        cands.append(("onehot", {"chunk": 1}))
    if backend == "tpu":
        for bn in (128, 256, 512):
            if bn <= max(128, n):
                cands.append(("pallas", {"block_n": bn}))
    return cands


def default_fill(backend: str) -> tuple[str, dict]:
    """Heuristic on a cache miss: the chunked XLA scan (chunk=1) on every
    backend. The Pallas fill is not the TPU default because Mosaic refuses
    its per-test-point gather (`_tile_sum`: a dynamic_slice of the rank
    tile, then a gather from the (n,) g row that is not 2-D); it stays a
    tuner candidate there, and a failing compile is recorded."""
    return "chunked", {"chunk": 1}


def autotune_fill(
    n: int,
    t: int,
    *,
    backend: Optional[str] = None,
    reps: int = 2,
    path: Optional[str] = None,
    verbose: bool = False,
) -> tuple[str, dict]:
    """Time every fill candidate at this (n, t, backend); persist the winner."""
    from repro.core.sti_knn import _FILL_FNS

    backend = backend or jax.default_backend()
    ts = int(min(max(1, t), _SAMPLE_T))
    g, ranks = _synthetic_fill_problem(n, ts)
    timings: dict[str, float] = {}
    failed: dict[str, str] = {}
    for name, params in fill_candidates(n, ts, backend):
        if name not in _FILL_FNS:
            continue
        label = f"{name} {json.dumps(params, sort_keys=True)}"
        fn = jax.jit(functools.partial(_FILL_FNS[name], **params))
        try:
            us = _time_call(fn, g, ranks, reps=reps)
        except Exception as e:  # noqa: BLE001 -- recorded, not dropped
            _candidate_failed(failed, label, e)
            continue
        timings[label] = us
        if verbose:
            print(f"autotune fill n={n} t={t} {name} {params}: {us:.0f}us")
    if not timings:
        return default_fill(backend)
    winner = min(timings, key=timings.get)
    name, params_json = winner.split(" ", 1)
    params = json.loads(params_json)
    entry = {
        "fill": name,
        "params": params,
        "us": timings[winner],
        "sample_t": ts,
        "candidates": timings,
        "failed": failed,
    }
    with _LOCK:
        # copy: never mutate the _MEMO-shared dict before _save succeeds.
        # Cross-process concurrent tunes of the SAME file are last-writer-
        # wins per entry set; acceptable for a self-healing cache (a dropped
        # entry just falls back to the heuristic until re-tuned).
        data = dict(_load(path))
        data[_key("fill", backend, n, t)] = entry
        _save(path, data)
    return name, params


def lookup_fill(
    n: int, t: int, *, backend: Optional[str] = None, path: Optional[str] = None
) -> Optional[tuple[str, dict]]:
    """Cached square-fill winner for this (n, t, backend), or None."""
    backend = backend or jax.default_backend()
    entry = _load(path).get(_key("fill", backend, n, t))
    if not isinstance(entry, dict) or "fill" not in entry:
        return None
    return str(entry["fill"]), dict(entry.get("params") or {})


def best_fill(
    n: int,
    t: int,
    *,
    backend: Optional[str] = None,
    allow_tune: bool = False,
    path: Optional[str] = None,
) -> tuple[str, dict]:
    """Cache hit > (optional) fresh tune > backend heuristic."""
    from repro.core.sti_knn import _FILL_FNS

    backend = backend or jax.default_backend()
    hit = lookup_fill(n, t, backend=backend, path=path)
    if hit is not None and hit[0] in _FILL_FNS:
        return hit
    if allow_tune:
        return autotune_fill(n, t, backend=backend, path=path)
    return default_fill(backend)


# -------------------------------------------------------------- rect fill --
def rect_fill_candidates(rows: int, n: int, t: int,
                         backend: str) -> list[tuple[str, dict]]:
    """Candidate (rect_registry_name, static_params) per backend for the
    sharded engine's (rows, n) row-block fill. Pallas block shapes are
    TPU-only (interpret mode would be timed as Python and always lose).
    A block candidate is only proposed when it preserves the aliased
    in-place path (`sti_fill_acc_rect_pallas` pads -- and therefore
    copies -- the accumulator unless block_rows | rows and
    block_cols | n): either the block divides the extent, or it exceeds
    it and clamps to the full extent (which divides trivially)."""
    cands: list[tuple[str, dict]] = [
        ("chunked", {"chunk": c}) for c in (1, 2, 4, 8) if c <= max(1, t)
    ]

    def aligned(block: int, extent: int) -> bool:
        return extent % block == 0 or block >= extent

    if backend == "tpu":
        for br in (128, 256):
            for bc in (256, 512):
                if aligned(br, rows) and aligned(bc, n):
                    cands.append(
                        ("pallas", {"block_rows": br, "block_cols": bc})
                    )
    return cands


def default_rect_fill(backend: str) -> tuple[str, dict]:
    """Heuristic on a cache miss: the XLA block scan on every backend (the
    Pallas rect kernel shares the square kernel's `_tile_sum`, which Mosaic
    refuses; see `default_fill`)."""
    return "chunked", {"chunk": 1}


def _synthetic_rect_fill_problem(rows: int, n: int, ts: int):
    g, ranks = _synthetic_fill_problem(n, ts)
    return g, ranks[:, : max(1, min(rows, n))], ranks


def autotune_rect_fill(
    rows: int,
    n: int,
    t: int,
    *,
    backend: Optional[str] = None,
    reps: int = 2,
    path: Optional[str] = None,
    verbose: bool = False,
) -> tuple[str, dict]:
    """Time every rect fill candidate at this (rows, n, t, backend) and
    persist the winner under the `rows{R}`-segmented key."""
    from repro.core.sti_knn import _RECT_FILL_FNS

    backend = backend or jax.default_backend()
    ts = int(min(max(1, t), _SAMPLE_T))
    g, r_rows, r_cols = _synthetic_rect_fill_problem(rows, n, ts)
    timings: dict[str, float] = {}
    failed: dict[str, str] = {}
    for name, params in rect_fill_candidates(rows, n, ts, backend):
        if name not in _RECT_FILL_FNS:
            continue
        label = f"{name} {json.dumps(params, sort_keys=True)}"
        fn = jax.jit(functools.partial(_RECT_FILL_FNS[name], **params))
        try:
            us = _time_call(fn, g, r_rows, r_cols, reps=reps)
        except Exception as e:  # noqa: BLE001 -- recorded, not dropped
            _candidate_failed(failed, label, e)
            continue
        timings[label] = us
        if verbose:
            print(f"autotune rect fill rows={rows} n={n} t={t} "
                  f"{name} {params}: {us:.0f}us")
    if not timings:
        return default_rect_fill(backend)
    winner = min(timings, key=timings.get)
    name, params_json = winner.split(" ", 1)
    params = json.loads(params_json)
    entry = {
        "fill": name,
        "params": params,
        "us": timings[winner],
        "sample_t": ts,
        "candidates": timings,
        "failed": failed,
    }
    with _LOCK:
        data = dict(_load(path))
        data[_key("rectfill", backend, n, t, rows=rows)] = entry
        _save(path, data)
    return name, params


def lookup_rect_fill(
    rows: int, n: int, t: int, *, backend: Optional[str] = None,
    path: Optional[str] = None,
) -> Optional[tuple[str, dict]]:
    """Cached rect-fill winner for this (rows, n, t, backend), or None."""
    backend = backend or jax.default_backend()
    entry = _load(path).get(_key("rectfill", backend, n, t, rows=rows))
    if not isinstance(entry, dict) or "fill" not in entry:
        return None
    return str(entry["fill"]), dict(entry.get("params") or {})


def best_rect_fill(
    rows: int,
    n: int,
    t: int,
    *,
    backend: Optional[str] = None,
    allow_tune: bool = False,
    path: Optional[str] = None,
) -> tuple[str, dict]:
    """Cache hit > (optional) fresh tune > backend heuristic, for the
    sharded engine's rectangular (rows, n) row-block fill."""
    from repro.core.sti_knn import _RECT_FILL_FNS

    backend = backend or jax.default_backend()
    hit = lookup_rect_fill(rows, n, t, backend=backend, path=path)
    if hit is not None and hit[0] in _RECT_FILL_FNS:
        return hit
    if allow_tune:
        return autotune_rect_fill(rows, n, t, backend=backend, path=path)
    return default_rect_fill(backend)


# ------------------------------------------------------------- distance ----
def distance_candidates(backend: str) -> list[tuple[str, dict]]:
    """Candidate (impl_name, static_params) for the distance stage; the
    Pallas block grid is TPU-only (the XLA expansion wins by construction
    elsewhere, so there is nothing to measure)."""
    if backend != "tpu":
        # interpret-mode Pallas is Python-speed; XLA's fused expansion wins
        # by construction off-TPU, so there is nothing to measure.
        return [("xla", {})]
    cands: list[tuple[str, dict]] = [("xla", {})]
    for bt in (128, 256):
        for bn in (128, 256, 512):
            cands.append(("pallas", {"block_t": bt, "block_n": bn}))
    return cands


def autotune_distance(
    t: int,
    n: int,
    d: int,
    *,
    backend: Optional[str] = None,
    reps: int = 2,
    path: Optional[str] = None,
) -> tuple[str, dict]:
    """Time distance candidates at (t, n, d); persist the winner per backend."""
    from repro.core.sti_knn import pairwise_sq_dists
    from repro.kernels.distance import distance_pallas

    backend = backend or jax.default_backend()
    cands = distance_candidates(backend)
    if len(cands) == 1:
        return cands[0]
    rng = np.random.default_rng(0)
    xt = jnp.asarray(rng.normal(size=(min(t, 256), d)).astype(np.float32))
    xn = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    timings: dict[str, float] = {}
    failed: dict[str, str] = {}
    for name, params in cands:
        label = f"{name} {json.dumps(params, sort_keys=True)}"
        if name == "xla":
            fn = jax.jit(pairwise_sq_dists)
        else:
            fn = functools.partial(distance_pallas, **params)
        try:
            us = _time_call(fn, xt, xn, reps=reps)
        except Exception as e:  # noqa: BLE001 -- recorded, not dropped
            _candidate_failed(failed, label, e)
            continue
        timings[label] = us
    if not timings:
        return "xla", {}
    winner = min(timings, key=timings.get)
    name, params_json = winner.split(" ", 1)
    params = json.loads(params_json)
    with _LOCK:
        data = dict(_load(path))
        data[_key(f"distance_d{d}", backend, n, t)] = {
            "distance": name, "params": params,
            "us": timings[winner], "candidates": timings, "failed": failed,
        }
        _save(path, data)
    return name, params


def best_distance(
    t: int,
    n: int,
    d: int,
    *,
    backend: Optional[str] = None,
    allow_tune: bool = False,
    path: Optional[str] = None,
) -> tuple[str, dict]:
    """Cache hit > (optional) fresh tune > backend heuristic, for the
    (t, n) x d distance stage."""
    backend = backend or jax.default_backend()
    entry = _load(path).get(_key(f"distance_d{d}", backend, n, t))
    if isinstance(entry, dict) and "distance" in entry:
        return str(entry["distance"]), dict(entry.get("params") or {})
    if allow_tune:
        return autotune_distance(t, n, d, backend=backend, path=path)
    return default_distance(backend)


def default_distance(backend: str) -> tuple[str, dict]:
    """Heuristic on a cache miss: the Pallas distance kernel on TPU (its
    norms are 2-D blocks, the layout Mosaic accepts), the XLA expansion
    elsewhere (interpret-mode Pallas is Python-speed)."""
    return ("pallas", {}) if backend == "tpu" else ("xla", {})


# ------------------------------------------------------------------ ann ----
# engine="approx" LSH index shapes: unlike the fill/distance triads, the
# ANN stage trades SPEED against RECALL, so the tuner picks the fastest
# (n_tables, window) whose measured candidate recall on synthetic data
# clears _ANN_RECALL_FLOOR -- falling back to the highest-recall config if
# none does. Keys bucket m alongside n/t ("ann_m{m}:...").

_ANN_RECALL_FLOOR = 0.95
_ANN_RECALL_K = 16


def default_ann(n: int, m: int) -> tuple[int, int]:
    """Heuristic (n_tables, window) for an untuned approx run: 4 tables
    with windows sized so the pooled candidates cover 2x top_m (clamped
    to n)."""
    n_tables = 4
    window = max(16, min(int(n), -(-2 * int(m) // n_tables)))
    return n_tables, window


def ann_candidates(n: int, m: int) -> list[tuple[int, int]]:
    """Candidate (n_tables, window) grid for the LSH candidate stage:
    table counts {4, 8} crossed with pool multipliers {2, 4} of top_m."""
    cands: list[tuple[int, int]] = []
    for n_tables in (4, 8):
        for mult in (2, 4):
            window = max(16, min(int(n), -(-mult * int(m) // n_tables)))
            if (n_tables, window) not in cands:
                cands.append((n_tables, window))
    return cands


def autotune_ann(
    n: int,
    t: int,
    d: int,
    m: int,
    *,
    backend: Optional[str] = None,
    reps: int = 2,
    path: Optional[str] = None,
) -> tuple[int, int]:
    """Time + recall-measure the ANN candidate grid on synthetic Gaussian
    data shaped (n, d) / (t-sample, d); persist the fastest config whose
    recall@16 clears the floor (else the highest-recall one)."""
    import jax.random as jrandom

    from repro.kernels.ann import (
        build_tables,
        matched_prefix_and_recall,
        topm_candidates,
    )

    backend = backend or jax.default_backend()
    rng = np.random.default_rng(0)
    ts = min(int(t), 64)
    xn = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    xt = jnp.asarray(rng.normal(size=(ts, d)).astype(np.float32))
    probe_k = min(_ANN_RECALL_K, int(m))
    results: dict[str, dict] = {}
    failed: dict[str, str] = {}
    for n_tables, window in ann_candidates(n, m):
        tables = build_tables(
            xn, key=jrandom.key(0), n_tables=n_tables, n_bits=16
        )
        fn = jax.jit(
            functools.partial(topm_candidates, m=int(m), window=window)
        )
        try:
            us = _time_call(fn, xt, xn, tables, reps=reps)
            cand, _, _ = fn(xt, xn, tables)
            _, recall = matched_prefix_and_recall(cand, xt, xn, probe_k)
            recall = float(jnp.mean(recall))
        except Exception as e:  # noqa: BLE001 -- recorded, not dropped
            _candidate_failed(failed, f"{n_tables}x{window}", e)
            continue
        results[f"{n_tables}x{window}"] = {
            "n_tables": n_tables, "window": window,
            "us": us, "recall": recall,
        }
    if not results:
        return default_ann(n, m)
    good = {k_: v for k_, v in results.items()
            if v["recall"] >= _ANN_RECALL_FLOOR}
    pool = good or results
    winner = min(pool, key=lambda k_: pool[k_]["us"]) if good else max(
        pool, key=lambda k_: pool[k_]["recall"]
    )
    entry = dict(results[winner])
    entry["candidates"] = results
    entry["failed"] = failed
    entry["sample_t"] = ts
    with _LOCK:
        data = dict(_load(path))
        data[_key(f"ann_m{_bucket(int(m))}_d{d}", backend, n, t)] = entry
        _save(path, data)
    return int(entry["n_tables"]), int(entry["window"])


def best_ann(
    n: int,
    t: int,
    d: int,
    m: int,
    *,
    backend: Optional[str] = None,
    allow_tune: bool = False,
    path: Optional[str] = None,
) -> tuple[int, int]:
    """Cache hit > (optional) fresh tune > heuristic, for the approx
    engine's (n_tables, window) LSH index shape at (n, t, d, top_m)."""
    backend = backend or jax.default_backend()
    entry = _load(path).get(
        _key(f"ann_m{_bucket(int(m))}_d{d}", backend, n, t)
    )
    if isinstance(entry, dict) and "n_tables" in entry and "window" in entry:
        return int(entry["n_tables"]), int(entry["window"])
    if allow_tune:
        return autotune_ann(n, t, d, m, backend=backend, path=path)
    return default_ann(n, m)


# ------------------------------------------------------------ megakernel ----
# The fused valuation megakernel (kernels/sti_megakernel.py) is an
# alternative to the whole three-stage step, not to one stage, so its tuner
# times COMPLETE steps: the best three-stage configuration (distance ->
# sort/rank -> fill, via best_fill) against megakernel tile-shape
# candidates, and records which STEP wins. `fill="auto"` in the fused
# pipeline consults `best_megastep`; the untuned default is "stages"
# everywhere (interpret-mode Pallas on CPU is Python-speed, and on TPU the
# winner should be measured, not assumed).


def megakernel_candidates(n: int, t: int, backend: str) -> list[dict]:
    """Candidate megakernel tile-shape dicts per backend. On TPU: lane-
    aligned test-row tiles crossed with train-tile widths and accumulator
    block shapes, in f32 and bf16 compute. Off-TPU (interpret mode) a
    single coarse full-extent candidate represents the kernel -- block
    shapes are irrelevant at Python speed, and the entry exists so a CPU
    tune records an honest "stages beats megakernel here" verdict."""
    if backend != "tpu":
        return [{"compute_dtype": "float32"}]
    cands: list[dict] = []
    for bt in (8, 16):
        for bn in (256, 512):
            if bn > max(256, n):
                continue
            for cdtype in ("float32", "bfloat16"):
                cands.append({
                    "block_t": bt,
                    "block_n": bn,
                    "block_rows": 256,
                    "block_cols": 256,
                    "compute_dtype": cdtype,
                })
    return cands


def _synthetic_step_problem(n: int, d: int, ts: int):
    rng = np.random.default_rng(0)
    xs = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    ys = jnp.asarray(rng.integers(0, 2, size=(n,)).astype(np.int32))
    xb = jnp.asarray(rng.normal(size=(ts, d)).astype(np.float32))
    yb = jnp.asarray(rng.integers(0, 2, size=(ts,)).astype(np.int32))
    mask = jnp.ones((ts,), jnp.float32)
    acc = jnp.zeros((n, n), jnp.float32)
    diag = jnp.zeros((n,), jnp.float32)
    return acc, diag, xb, yb, mask, xs, ys


def autotune_megastep(
    n: int,
    d: int,
    k: int,
    t: int,
    *,
    backend: Optional[str] = None,
    reps: int = 2,
    path: Optional[str] = None,
    verbose: bool = False,
) -> tuple[str, dict]:
    """Time the best three-stage step against every megakernel tile
    candidate on a synthetic (t-sample, n, d) problem; persist which step
    wins ("stages" or "megakernel") plus its params."""
    from repro.kernels.sti_pipeline import make_fused_step

    backend = backend or jax.default_backend()
    ts = int(min(max(1, t), _SAMPLE_T))
    acc, diag, xb, yb, mask, xs, ys = _synthetic_step_problem(n, d, ts)
    args = (acc, diag, xb, yb, mask, xs, ys)

    stages_name, stages_params = best_fill(n, t, backend=backend, path=path)
    timings: dict[str, float] = {}
    failed: dict[str, str] = {}
    # donate=False: the timing loop replays the same operands, so the step
    # must not consume its accumulator buffers.
    base = make_fused_step(
        int(k), "sti", stages_name, tuple(sorted(stages_params.items())),
        donate=False,
    )
    try:
        timings["stages {}"] = _time_call(base, *args, reps=reps)
    except Exception as e:  # noqa: BLE001 -- recorded, not dropped
        _candidate_failed(failed, "stages {}", e)
    for params in megakernel_candidates(n, ts, backend):
        step = make_fused_step(
            int(k), "sti", "megakernel", tuple(sorted(params.items())),
            donate=False,
        )
        label = f"megakernel {json.dumps(params, sort_keys=True)}"
        try:
            us = _time_call(step, *args, reps=reps)
        except Exception as e:  # noqa: BLE001 -- recorded, not dropped
            _candidate_failed(failed, label, e)
            continue
        timings[label] = us
        if verbose:
            print(f"autotune megastep n={n} t={t} {params}: {us:.0f}us")
    if not timings:
        return "stages", {}
    winner = min(timings, key=timings.get)
    name, params_json = winner.split(" ", 1)
    params = json.loads(params_json) if params_json.strip() != "{}" else {}
    entry = {
        "step": name,
        "params": params,
        "us": timings[winner],
        "sample_t": ts,
        "candidates": timings,
        "failed": failed,
    }
    with _LOCK:
        data = dict(_load(path))
        data[_key(f"megastep_d{d}", backend, n, t)] = entry
        _save(path, data)
    return name, params


def lookup_megastep(
    n: int, t: int, d: int, *, backend: Optional[str] = None,
    path: Optional[str] = None,
) -> Optional[tuple[str, dict]]:
    """Cached step winner ("stages"/"megakernel", params) for this
    (n, t, d, backend), or None."""
    backend = backend or jax.default_backend()
    entry = _load(path).get(_key(f"megastep_d{d}", backend, n, t))
    if not isinstance(entry, dict) or "step" not in entry:
        return None
    return str(entry["step"]), dict(entry.get("params") or {})


def best_megastep(
    n: int,
    t: int,
    d: int,
    k: int,
    *,
    backend: Optional[str] = None,
    allow_tune: bool = False,
    path: Optional[str] = None,
) -> tuple[str, dict]:
    """Cache hit > (optional) fresh tune > "stages". The untuned default
    keeps today's three-stage step on every backend: the megakernel only
    takes over a `fill="auto"` run after a measurement on this platform
    says it should."""
    backend = backend or jax.default_backend()
    hit = lookup_megastep(n, t, d, backend=backend, path=path)
    if hit is not None:
        return hit
    if allow_tune:
        return autotune_megastep(n, d, k, t, backend=backend, path=path)
    return "stages", {}
