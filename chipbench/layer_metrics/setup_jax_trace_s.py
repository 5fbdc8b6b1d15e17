"""Set-up's seconds in JAX's tracer: the protocol's Python run to make a
jaxpr (``jax_trace_s``, a nested trace counted once), whichever span
carries it: ``record_key`` on a ladder plan, ``dispatch`` on a static
one, a ``ladder_validate`` or a ``pallas_selfcheck`` too."""

from chipbench import setup_spans

NAME = "setup_jax_trace_s"
UNIT = "s"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "plan, ladder"
MOVES = "setup_s"


def read(view):
    return setup_spans.row_s(view, "jax_trace")
