"""The least time the chip could take for the protocol's ring work of
one evaluation (``chipbench/work.py``: the larger of operations over the
int8 peak and bytes over the memory peak; here the bytes bound, by the
relus' AND gates) over the device-busy time per evaluation.  For the
cell whose work is the secure dense stack: three dots with their
truncations, two relus, a softmax.  The same reading as
``secure_forest_roofline``, over this cell's ``work`` block."""

from chipbench.layer_metrics.secure_forest_roofline import read  # noqa: F401

NAME = "secure_mlp_roofline"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
LAYER = "secure dense stack"
MOVES = "evals_per_s"
WORKLOADS = ["mlp-score-batch"]
