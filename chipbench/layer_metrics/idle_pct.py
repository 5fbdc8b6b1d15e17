"""1 - the union of the device-busy intervals over the traced window."""

NAME = "idle_pct"
UNIT = "%"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "device"
MOVES = "evals_per_s"


def read(view):
    if view.trace is None or not view.trace["window_s"]:
        return None
    return 100.0 * (1.0 - view.trace["busy_s"] / view.trace["window_s"])
