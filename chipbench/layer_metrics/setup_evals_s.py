"""The part of ``setup_s`` inside the program's entry point: the durations
of the ``evaluate_computation`` trees from before the window (the
driver's evaluations until the plan has settled, and one more).  The
rows beside it (``setup_spans.ROWS``) add up to it."""

from chipbench import setup_spans

NAME = "setup_evals_s"
UNIT = "s"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "eDSL + runtime"
MOVES = "setup_s"


def read(view):
    return setup_spans.row_s(view, "evals")
