"""Device time of the per-test sort and rank per step and chip: the stable
sort of the distances that carries the labels along, and the sorts keyed by
`order` back to train coordinates (sti's ranks and u, the point methods'
values), as `layers.json` classifies their ops."""

LAYER = "kernels/sti_pipeline sort and rank"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "points_per_s"


def read(red):
    t = red["layers"].get("sort_rank", 0.0)
    return 1e3 * t / red["steps"] if t > 0 else None
