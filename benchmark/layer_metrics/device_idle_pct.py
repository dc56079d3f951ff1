"""Share of the traced window in which no operation ran on the device:
1 - union of the device's operation intervals / window, first device."""

LAYER = "device"
UNIT = "%"
MOVES = "step_ms"


def read(summary, counters, context):
    if not summary.get("window_s"):
        return None
    return 100.0 * (1.0 - summary["busy_s"] / summary["window_s"])
