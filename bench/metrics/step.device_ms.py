"""Device time of the jitted step program per step and chip: the sum of
its module's executions in the trace over the window's steps."""

LAYER = "kernels/sti_pipeline jitted step"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "points_per_s"


def read(red):
    t = red["layers"].get("step", 0.0)
    return 1e3 * t / red["steps"] if t > 0 else None
