"""The least time the chip could take for the protocol's ring work of
one evaluation (``chipbench/work.py``: the larger of operations over the
int8 peak and bytes over the memory peak) over the device-busy time per
evaluation.  For the cell whose work is the ring matmul."""

from chipbench import work

NAME = "secure_dot_roofline"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
LAYER = "ring matmul"
MOVES = "evals_per_s"
WORKLOADS = ["dot-2048"]


def read(view):
    if view.trace is None or not view.trace["busy_s"]:
        return None
    least, _bound = work.least_seconds(view.config, view.size, view.device_kind)
    busy = view.trace["busy_s"] / len(view.trace["evaluations"])
    return 100.0 * least / busy
