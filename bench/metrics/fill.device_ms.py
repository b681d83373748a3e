"""Device time of the chunked select fill per step and chip: the scan that
folds each test point's g[max(r_a, r_b)] block into the accumulator, as
`layers.json` classifies its ops."""

LAYER = "core/sti_knn chunked select fill"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "points_per_s"


def read(red):
    t = red["layers"].get("fill", 0.0)
    return 1e3 * t / red["steps"] if t > 0 else None
