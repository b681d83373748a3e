"""The distance kernel's share of its roofline: the least time its work
allows (2 tb n d FLOP against the bf16 peak, or 4 (n d + tb d + tb n)
bytes against HBM, whichever is longer; tb is the chip's own slice of the
batch) over its measured device time. At Precision.HIGHEST it runs several
bf16 passes, so the share reads low by design."""

LAYER = "kernels/distance distance_pallas"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "points_per_s"


def read(red):
    t = red["layers"].get("distance", 0.0)
    if t <= 0:
        return None
    w = red["work"]
    return 100.0 * red["steps"] * w["distance_min_s"] / t
