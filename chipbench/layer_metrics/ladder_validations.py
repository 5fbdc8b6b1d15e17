"""How many validating evaluations (the jitted plan against its eager
twin, at full size) this process ran for the plan the window ran: 0
where the plan started from a verdict kept beside the compile cache, K
on a checkout's first process.  Nothing where the program does not
publish the count."""

NAME = "ladder_validations"
UNIT = "evals"
BETTER = "lower"
SOURCE = "program_counter"
LAYER = "plan, ladder"
MOVES = "setup_s"
WORKLOADS = ["logreg-score-64k"]


def read(view):
    return view.plan.get("validations_run")
