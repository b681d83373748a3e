#!/usr/bin/env python3
"""Readings that set a cell's correctness limits, on the chip, in one process.

    python3 bench/control.py --workload <cell> --seeds 1,2,... \
        [--control-seeds 1,2,3] [--seconds 10]

For every seed the cell runs as `run.py` runs it and prints its compared
numbers, with the parts each is the worst of (the sampled steps, or the
rows and the diagonal): the largest over the seeds is the lower reading.
For the control seeds it also puts each control in the program's place
(`reference.CONTROLS`: the plain reference at a lower precision), prints
its numbers against the reference and passes them through the cell's
limits, as a run would: the smallest is the upper reading, and a control
that some seed reads as correct is printed as such. The limits lie
between the readings (PERF.md gives both and the limits). The benchmark's
own runs never run the controls.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys

import reference
import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    lower: dict = {}
    upper: dict = {}
    passed: dict = {}
    try:
        for seed in seeds:
            gc.collect()  # the last seed's session and state are gone
            res = run.run_cell(
                run.ROOT, args.workload, seed, args.seconds, False,
                controls=reference.CONTROLS if seed in controls else ())
            limits = {k: c["limit"] for k, c in res["checks"].items()}
            line = {"seed": seed, "correct": res["correct"],
                    "program": {k: c["value"]
                                for k, c in res["checks"].items()},
                    "points_per_s": res["metrics"]["points_per_s"]["value"],
                    "control": {}}
            for prec, nums in res["control"].items():
                ok = all(v <= limits[k] for k, v in nums.items())
                line["control"][prec] = {"numbers": nums, "correct": ok}
                passed[prec] = passed.get(prec, False) or ok
                for k, v in nums.items():
                    upper.setdefault(prec, {})
                    upper[prec][k] = min(upper[prec].get(k, float("inf")), v)
            line["parts"] = res["parts"]
            print(json.dumps(line), flush=True)
            for k, v in line["program"].items():
                lower[k] = max(lower.get(k, 0.0), v)
    except run.NoChip as e:
        run.log(f"control: {e}; this run needs the chip")
        return run.NO_CHIP
    print(json.dumps({"workload": args.workload, "lower": lower,
                      "upper": upper, "limits": limits,
                      "control_ever_correct": passed, "seeds": len(seeds),
                      "control_seeds": len(controls & set(seeds))}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
