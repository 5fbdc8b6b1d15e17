"""Device time per evaluation in convolutions, dots and the tiled dot kernel, from the trace
(``trace_reduce.classify`` tells the groups apart by op name)."""

NAME = "mxu_ms"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "ring matmul"
MOVES = "evals_per_s"


def read(view):
    if view.trace is None:
        return None
    seconds = view.trace["group_s"]["mxu"]
    if not seconds:
        return None  # no such op ran: nothing to read
    return 1e3 * seconds / len(view.trace["evaluations"])
