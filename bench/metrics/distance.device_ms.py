"""Device time of the distance kernel (`distance_pallas`, found by its
kernel name) per step and chip."""

LAYER = "kernels/distance distance_pallas"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "points_per_s"


def read(red):
    t = red["layers"].get("distance", 0.0)
    return 1e3 * t / red["steps"] if t > 0 else None
