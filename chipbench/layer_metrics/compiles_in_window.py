"""Compile requests JAX made inside the window, cache hits included
(``/jax/core/compile/backend_compile_duration``, by a listener the
benchmark registers).  Nothing should compile there."""

NAME = "compiles_in_window"
UNIT = "compiles"
BETTER = "lower"
SOURCE = "program_counter"
LAYER = "plan + compile cache"
MOVES = "eval_p90_ms"


def read(view):
    return view.compiles_in_window
