"""Set-up's seconds in the backend's compiler or the persistent cache's
load (``backend_compile_s``, whichever span carries it): the plan's
programs, the kernels' checks, the eager twin's, and every small
program an eager step compiles."""

from chipbench import setup_spans

NAME = "setup_compile_s"
UNIT = "s"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "plan + compile cache"
MOVES = "setup_s"


def read(view):
    return setup_spans.row_s(view, "compile")
