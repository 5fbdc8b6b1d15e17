"""Per evaluation: the benchmark's span around the call less the time
the device was busy inside it: what the host path (eDSL, runtime, plan
lookup, bind, transfers, decode) adds to each evaluation."""

NAME = "host_gap_ms"
UNIT = "ms"
BETTER = "lower"
SOURCE = "device_trace"
LAYER = "eDSL + runtime"
MOVES = "evals_per_s"


def read(view):
    if view.trace is None:
        return None
    gaps = [e["span_s"] - e["busy_s"] for e in view.trace["evaluations"]]
    return 1e3 * sum(gaps) / len(gaps)
