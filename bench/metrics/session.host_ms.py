"""Host time of one `session.update()` call in the window, mean per step.

The benchmark's own span around each call (padding, placement, dispatch);
with one step in flight it hides behind the device unless it grows."""

LAYER = "core/session host loop"
UNIT = "ms"
BETTER = "lower"
SOURCE = "host_clock"
MOVES = "points_per_s"


def read(red):
    times = red["host"].get("update")
    if not times:
        return None
    return 1e3 * sum(times) / len(times)
