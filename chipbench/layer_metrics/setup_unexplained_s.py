"""``setup_evals_s`` less every row that says what it is: JAX's seconds,
the checks, the validation, and the spans whose names say what they
are (``setup_spans.OWNERS``).  What is left is the self time of
``evaluate_computation``, ``execute``, ``dispatch`` and the verdict
lookup: near 0, or set-up does something no span names."""

from chipbench import setup_spans

NAME = "setup_unexplained_s"
UNIT = "s"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "eDSL + runtime"
MOVES = "setup_s"


def read(view):
    return setup_spans.row_s(view, setup_spans.UNEXPLAINED)
