"""Device time per evaluation in every other device operation (limb split and recombine, PRF draws, shares, reveal), from the trace
(``trace_reduce.classify`` tells the groups apart by op name)."""

NAME = "xla_rest_ms"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "stacked protocol as XLA"
MOVES = "evals_per_s"


def read(view):
    if view.trace is None:
        return None
    seconds = view.trace["group_s"]["xla_rest"]
    if not seconds:
        return None  # no such op ran: nothing to read
    return 1e3 * seconds / len(view.trace["evaluations"])
