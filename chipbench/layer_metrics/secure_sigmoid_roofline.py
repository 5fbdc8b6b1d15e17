"""The least time the chip could take for the protocol's ring work of
one evaluation (``chipbench/work.py``: the larger of operations over the
int8 peak and bytes over the memory peak; here the bytes bound, by the
sigmoid's secure multiplications and AND gates) over the device-busy
time per evaluation.  For the cell whose work is the secure sigmoid."""

from chipbench import work

NAME = "secure_sigmoid_roofline"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
LAYER = "secure sigmoid"
MOVES = "evals_per_s"
WORKLOADS = ["logreg-score-64k"]


def read(view):
    if view.trace is None or not view.trace["busy_s"]:
        return None
    least, _bound = work.least_seconds(view.config, view.size, view.device_kind)
    busy = view.trace["busy_s"] / len(view.trace["evaluations"])
    return 100.0 * least / busy
