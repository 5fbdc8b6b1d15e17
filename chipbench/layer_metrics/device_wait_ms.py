"""Per evaluation: the host blocked on the results' device arrays
(``device_wait``), after every copy to the host was started and a fixed
output's decode dispatched."""

from chipbench import program_spans

NAME = "device_wait_ms"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "device"
MOVES = "evals_per_s"


def read(view):
    return program_spans.row_ms(view, "device_wait")
