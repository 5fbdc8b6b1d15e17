"""Per evaluation: what no leaf span covers: the self time of
``evaluate_computation``, ``bind_arguments`` and ``execute`` (and of
``trace`` and ``build_plan`` where they ran): plan lookup, cache keys,
plan facts."""

from chipbench import program_spans

NAME = "runtime_self_ms"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "eDSL + runtime"
MOVES = "evals_per_s"


def read(view):
    return program_spans.row_ms(view, program_spans.SELF)
