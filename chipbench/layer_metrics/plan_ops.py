"""How many logical operations the plan that ran the window holds
(``runtime.last_plan["ops"]``): some 50 for a forest folded by level,
25,000 for the same forest traced node by node.  Nothing where the
program does not publish the count."""

NAME = "plan_ops"
UNIT = "ops"
BETTER = "lower"
SOURCE = "program_counter"
LAYER = "plan, ladder"
MOVES = "setup_s"
WORKLOADS = ["gbt-score-batch"]


def read(view):
    return view.plan.get("ops")
