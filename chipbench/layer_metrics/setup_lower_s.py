"""Set-up's seconds from jaxpr to the text the verdict record is keyed by:
JAX's lowering to an MLIR module (``jax_lower_s``, whichever span
carries it) and what each ``record_key`` span has left after the
seconds it carries: ``as_text``, the computation's serialization, the
digests."""

from chipbench import setup_spans

NAME = "setup_lower_s"
UNIT = "s"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "plan, ladder"
MOVES = "setup_s"


def read(view):
    return setup_spans.row_s(view, "lower")
