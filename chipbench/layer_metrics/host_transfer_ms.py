"""Per evaluation: results and saves from ready device arrays to NumPy
(``host_transfer``)."""

from chipbench import program_spans

NAME = "host_transfer_ms"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "eDSL + runtime"
MOVES = "evals_per_s"


def read(view):
    return program_spans.row_ms(view, "host_transfer")
