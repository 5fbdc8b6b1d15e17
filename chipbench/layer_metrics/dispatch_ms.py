"""Per evaluation: the plan's call to its return (``dispatch``): the
jitted program handed to the device, or everything a validating or
eager plan does on the host."""

from chipbench import program_spans

NAME = "dispatch_ms"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "plan, ladder"
MOVES = "evals_per_s"


def read(view):
    return program_spans.row_ms(view, "dispatch")
