"""What the kernels' first-use checks (``pallas_selfcheck`` spans) have
left after the trace, lower and compile seconds they carry (counted
in those rows): the kernels' and their twins' runs and the comparisons."""

from chipbench import setup_spans

NAME = "setup_kernel_checks_s"
UNIT = "s"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "ring kernels"
MOVES = "setup_s"


def read(view):
    return setup_spans.row_s(view, "kernel_checks")
