"""Operations and bytes of the step's kernels, and the chip's peaks.

Counts are the least the algorithm needs for one step on one chip,
computed from shapes; a roofline share is the least time those counts
allow on the chip (`min_seconds`) over the measured device time, so it
cannot pass 100% unless a count is too high or a time leaves work out.
"""

from __future__ import annotations

import json
from pathlib import Path

F32 = 4


def peaks(bench_dir, device_kind: str) -> dict:
    """The peaks of `device_kind` from `peaks.json`; an unknown kind is an
    error, never a default."""
    table = json.loads((Path(bench_dir) / "peaks.json").read_text())
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json ({table['source']})")
    return table["devices"][device_kind]


def distance_work(tb: int, n: int, d: int) -> tuple[float, float]:
    """(FLOP, bytes) of the (tb, d) x (n, d) squared distances: the cross
    term's 2 tb n d, and reading both operands and writing the f32 result."""
    return 2.0 * tb * n * d, float(F32 * (n * d + tb * d + tb * n))


def fill_bytes(tb: int, rows: int, n: int) -> float:
    """HBM bytes of folding tb test points into a (rows, n) f32 block:
    per test point the block is read and written once (8 B a cell), and
    its g table and ranks are read (4 B each over n, and over the rows)."""
    return float(tb) * (2 * F32 * rows * n + F32 * (2 * n + 2 * rows))


def min_seconds(flops: float, nbytes: float, peak: dict) -> float:
    """The least time the chip can take: the larger of the compute and the
    memory bound (bf16 peak; f32 at HIGHEST runs several bf16 passes, so a
    share against it reads low by design)."""
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])
