"""What the ladder's validating evaluations (``ladder_validate`` spans:
``candidate_run``, ``twin_run``, ``compare``) have left after the
trace, lower and compile seconds they carry (counted in those rows):
the two runs and the comparison.  0 from the record."""

from chipbench import setup_spans

NAME = "setup_validate_s"
UNIT = "s"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "plan, ladder"
MOVES = "setup_s"
WORKLOADS = ["logreg-score-64k", "gbt-score-batch", "mlp-score-batch"]


def read(view):
    return setup_spans.row_s(view, "validate")
