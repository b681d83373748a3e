"""Share of the traced window in which no operation ran on the device:
1 - (union of device op intervals / window), averaged over the chips."""

LAYER = "device"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
MOVES = "points_per_s"


def read(red):
    if red["window_s"] <= 0 or red["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])
