"""How many ops the validated-jit ladder pinned eager in the plan the
window ran (0: the whole graph is one jitted program)."""

NAME = "pinned_ops"
UNIT = "ops"
BETTER = "lower"
SOURCE = "program_counter"
LAYER = "plan, ladder"
MOVES = "evals_per_s"


def read(view):
    if "pinned_ops" not in view.plan:
        return None
    return len(view.plan["pinned_ops"])
