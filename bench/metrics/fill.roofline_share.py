"""The fill's share of its roofline: per test point the chip's (R, n) f32
block is read and written once and its g and ranks read,
8 R n + 4 (2 n + 2 R) bytes, against the HBM bandwidth, over the fill's
measured device time."""

LAYER = "core/sti_knn chunked select fill"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "points_per_s"


def read(red):
    t = red["layers"].get("fill", 0.0)
    if t <= 0:
        return None
    return 100.0 * red["steps"] * red["work"]["fill_min_s"] / t
