"""How many times a Mosaic ring kernel declined or failed and its XLA
twin ran in its place (``moose_tpu_pallas_fallback_total``, every kernel
and reason together): 0 where ``msb`` took the forest's shape.  The twin
of ``msb`` is the program XLA:TPU miscompiles (PERF.md section 6), so a
fallback there is what the ladder would have to pin."""

NAME = "kernel_fallbacks"
UNIT = "count"
BETTER = "lower"
SOURCE = "program_counter"
LAYER = "ring kernels"
MOVES = "evals_per_s"
WORKLOADS = ["gbt-score-batch"]


def read(view):
    fallbacks = view.counters.get("pallas_fallback_total")
    if fallbacks is None:
        return None
    return sum(fallbacks.values())
