"""Per evaluation: the content hashes of the large arguments (one
``input_fingerprint`` span per array of 64 KiB or more), which the
device cache takes on every call to tell a mutated array from a
resident one."""

from chipbench import program_spans

NAME = "input_fingerprint_ms"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "eDSL + runtime"
MOVES = "evals_per_s"


def read(view):
    return program_spans.row_ms(view, "input_fingerprint")
