#!/usr/bin/env python3
"""On-chip benchmark of the valuation sessions, one cell per run.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is a `workloads` entry of `BENCHMARK.json`; its configuration,
traffic mix, per-layer metrics and reference check are files found by name
(`catalog.py`). One run:

  1. set-up: makes the train set and the test pool on the device from the
     seed (`data.py`), builds the session as a user builds it (`fill`,
     `distance` "auto", no autotuning), and folds one warm batch, which
     compiles the cell's one step shape or loads it from the persistent
     cache;
  2. window: one caller feeds the session successive batches of the pool,
     keeping two steps and at least `QUEUE_S` of work queued on the
     device, as a caller that does not wait for each step does, until
     `--seconds` have passed; every step issued is waited for. The queue
     hides stalls of the shared host (PERF.md: with one step or none in
     flight, 4 of 48 runs lost 0.08 to 1.1 s of device time to them). A
     compilation inside the window fails the run (`WindowCompiled`): every
     shape is warmed in set-up. After each step waited for, the check
     moves what it keeps to the host (`poll`), so the peak read next is
     the program's;
  3. reads the peak device memory, takes what the check needs from the
     state, frees it, and compares with the plain reference.

With `--trace 0` the result carries the end-to-end metrics, with
`--trace 1` the per-layer ones, read from a profiler trace of the window
(`reduce_trace.py`). The last line of standard output is the JSON result; the
numbers compared and their limits end standard error. Without a TPU, or
with fewer chips than the cell asks for, the run prints no result and
exits with code 3.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from collections import deque  # noqa: E402
from contextlib import contextmanager  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

import catalog  # noqa: E402

NO_CHIP = 3
QUEUE_S = 1.0  # seconds of work kept queued on the device in the window


class NoChip(RuntimeError):
    """The machine lacks the accelerator or the chips the cell needs."""


class WindowCompiled(RuntimeError):
    """Something compiled inside the measured window."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _setup_jax(root: Path):
    """Persistent compile cache at a fixed path in the checkout (or where
    `JAX_COMPILATION_CACHE_DIR` says), every program cached; no autotune
    cache outside the checkout can choose what runs."""
    os.environ.setdefault(
        "REPRO_AUTOTUNE_CACHE", str(root / ".jax_cache" / "no-autotune.json"))
    src = str(root / "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(root / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return jax


def devices(jax, chips: int, require_tpu: bool):
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs[:chips]


def build_session(cfg: dict, traffic: dict, x, y):
    """The session as a user builds it, defaults resolved by the program."""
    from repro.core.session import ShardedValuationSession, ValuationSession

    kw = dict(k=int(cfg["k"]), mode=cfg["method"],
              test_batch=int(traffic["batch"]), fill="auto",
              distance="auto", autotune=False)
    shards = int(cfg["shards"])
    if shards == 1:
        return ValuationSession(x, y, **kw)
    sess = ShardedValuationSession(x, y, shards=shards, **kw)
    if sess.shards != shards:
        raise RuntimeError(f"session sharded over {sess.shards}, "
                           f"the cell states {shards}")
    return sess


class Spans:
    """The benchmark's own host spans: perf_counter intervals, and profiler
    annotations while a trace is on."""

    def __init__(self, jax, traced: bool):
        self.jax = jax
        self.traced = traced
        self.times: dict[str, list] = {}

    @contextmanager
    def __call__(self, name: str):
        t0 = time.perf_counter()
        if self.traced:
            with self.jax.profiler.TraceAnnotation(name):
                yield
        else:
            yield
        self.times.setdefault(name, []).append(time.perf_counter() - t0)


class CompileCounter:
    """Counts compilations reported through `jax.monitoring` while on; the
    window fails its run on any."""

    def __init__(self, jax):
        self.on = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, name, secs, **kw):
        if self.on and "backend_compile" in name:
            self.count += 1


def _peak_bytes(devs) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs)


def run_cell(root, workload: str, seed: int, seconds: float, trace: bool, *,
             require_tpu: bool = True, t_start: float = T_START,
             controls: tuple | None = None) -> dict:
    """One run of one cell (module docstring); returns the result object
    and prints the checks to standard error. With `controls` (for
    `control.py` only; names from `reference.CONTROLS`) it also reads each
    control's numbers into `result["control"]`, and the parts of every
    comparison into `result["parts"]`."""
    root = Path(root)
    cat = catalog.Catalog(root)
    cell = cat.cell(workload)
    cfg, traffic = cell.config, cell.traffic
    if traffic["loop"] != "closed" or int(traffic["clients"]) != 1:
        raise ValueError(f"traffic {traffic['name']!r}: only a closed loop "
                         f"of one client is generated")
    jax = _setup_jax(root)
    devs = devices(jax, cell.chips, require_tpu)
    import data

    tb = int(traffic["batch"])
    x, y, xb, yb = data.mixture(
        seed, n=cfg["n"], pool=cfg["test_pool"], d=cfg["d"],
        classes=cfg["classes"], sep=cfg["class_sep"], tb=tb)
    nb = len(xb)
    jax.block_until_ready((x, y, xb, yb))
    session = build_session(cfg, traffic, x, y)
    resolved = dict(session._resolved)
    print(json.dumps({"resolved": resolved}), flush=True)
    check = cat.method(cfg["method"]).Check(cfg, seed, int(cfg["shards"]))
    spans = Spans(jax, traced=False)
    compiles = CompileCounter(jax)
    fed: list[int] = []

    def feed(step: int):
        b = step % nb
        with spans("check"):
            check.before_step(step, session._state)
        with spans("update"):
            session.update(xb[b], yb[b])
        fed.append(b)

    t_warm = time.perf_counter()
    feed(0)
    jax.block_until_ready(session._state[-1][:1])
    step_s = time.perf_counter() - t_warm
    check.warm(session._state)
    setup_s = time.perf_counter() - t_start

    tdir = root / ".bench_trace" / workload
    if trace:
        shutil.rmtree(tdir, ignore_errors=True)
        jax.profiler.start_trace(str(tdir))
        spans.traced = True
    spans.times.clear()
    compiles.on = True
    # keep at least QUEUE_S of work, and two steps, queued on the device,
    # so that a stall of the (shared) host does not leave it idle
    step, queue, done = 1, deque(), 0
    t0 = time.perf_counter()
    with spans("window"):
        while True:
            open_ = time.perf_counter() - t0 < seconds
            while open_ and (len(queue) < 2
                             or len(queue) * step_s < QUEUE_S):
                feed(step)
                queue.append(session._state[-1][:1])
                step += 1
            if not queue:
                break
            with spans("block_until_ready"):
                queue.popleft().block_until_ready()
            with spans("check"):
                check.poll()
            done += 1
            step_s = (time.perf_counter() - t0) / done
    elapsed = time.perf_counter() - t0
    compiles.on = False
    if trace:
        jax.profiler.stop_trace()
    spans.traced = False
    steps = step - 1
    log(f"window: {steps} steps of {tb} points in {elapsed:.4f} s, "
        f"{compiles.count} compilations inside it")
    if compiles.count:
        raise WindowCompiled(f"{compiles.count} compilations inside the "
                             f"measured window; set-up warms every shape")

    peak = _peak_bytes(devs)
    check.after_window(session)
    del session
    t_ref = time.perf_counter()
    with spans("reference"):
        want = check.reference(fed, x, y, xb, yb)
        numbers, parts = check.numbers(check.got, want)
    log(f"reference: {time.perf_counter() - t_ref:.3f} s; parts "
        + json.dumps(parts))
    if controls is not None:
        # each control in the program's place: the reference at a lower
        # precision, against the reference
        result_control, all_parts = {}, {"program": parts}
        for prec in controls:
            lower = check.reference(fed, x, y, xb, yb, prec=prec)
            result_control[prec], all_parts[prec] = check.numbers(lower,
                                                                  want)
            log(f"control {prec}: parts {json.dumps(all_parts[prec])}")

    limits = cfg["check"]["limits"]
    checks = {name: {"value": v, "limit": float(limits[name])}
              for name, v in numbers.items()}
    failed = sum(not (c["value"] <= c["limit"]) for c in checks.values())
    correct = failed == 0

    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs), "memory_peak_bytes": peak}
    # an audit's steps fold into one state, so a wrong state fails them all
    result = {"correct": correct, "attempted": steps,
              "failed": 0 if correct else steps}
    if trace:
        import reduce_trace as tr

        red = tr.reduce_dir(tdir, devs, steps=steps, cfg=cfg, traffic=traffic,
                            spans=spans.times, bench=cat.bench)
        shutil.rmtree(tdir, ignore_errors=True)
        log("layers (ms per step per chip): " + json.dumps(
            {k: v / steps * 1e3 for k, v in red["layers"].items()}))
        metrics = {}
        for m in cell.per_layer:
            value = cat.metric(m["name"]).read(red)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        dev["busy_s"] = red["busy_s"]
        dev["window_s"] = red["window_s"]
        result["breakdown"] = red["breakdown"]
    else:
        values = {"points_per_s": steps * tb / elapsed,
                  "peak_hbm_gib": peak / 2**30, "setup_s": setup_s}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
    result["metrics"] = metrics
    result["device"] = dev
    if controls is not None:
        result["control"] = result_control
        result["parts"] = all_parts
    result["checks"] = checks
    for name, c in checks.items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run_cell(ROOT, args.workload, args.seed, args.seconds,
                          bool(args.trace))
    except NoChip as e:
        log(f"bench: {e}; this run needs the chip")
        return NO_CHIP
    except WindowCompiled as e:
        log(f"bench: {e}")
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
