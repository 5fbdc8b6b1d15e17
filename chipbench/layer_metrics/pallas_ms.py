"""Device time per evaluation in Mosaic custom calls other than the dot, from the trace
(``trace_reduce.classify`` tells the groups apart by op name)."""

NAME = "pallas_ms"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "ring kernels"
MOVES = "evals_per_s"


def read(view):
    if view.trace is None:
        return None
    seconds = view.trace["group_s"]["pallas"]
    if not seconds:
        return None  # no such op ran: nothing to read
    return 1e3 * seconds / len(view.trace["evaluations"])
