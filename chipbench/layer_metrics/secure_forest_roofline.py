"""The least time the chip could take for the protocol's ring work of
one evaluation (``chipbench/work.py``: the larger of operations over the
int8 peak and bytes over the memory peak; here the bytes bound, by the
comparisons' AND gates) over the device-busy time per evaluation.  For
the cell whose work is the secure forest."""

from chipbench import work

NAME = "secure_forest_roofline"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
LAYER = "secure forest"
MOVES = "evals_per_s"
WORKLOADS = ["gbt-score-batch"]


def read(view):
    if view.trace is None or not view.trace["busy_s"]:
        return None
    least, _bound = work.least_seconds(view.config, view.size, view.device_kind)
    busy = view.trace["busy_s"] / len(view.trace["evaluations"])
    return 100.0 * least / busy
