"""How many times a Mosaic ring kernel declined or failed and its XLA
twin ran in its place (``moose_tpu_pallas_fallback_total``, every kernel
and reason together) in the dense stack's cell: 0 where ``msb`` took
rows x 128 for the relus and rows x 5, 2, 1 and 10 in the softmax, and
``bit_decompose``, ``horner`` and ``trunc_combine`` the class axis of
10.  The twin of ``msb`` / ``bit_decompose`` is the program XLA:TPU
miscompiles (PERF.md section 6), so a fallback there is what the ladder
would have to pin.  The same reading as ``kernel_fallbacks``, whose file
names ``gbt-score-batch`` alone."""

from chipbench.layer_metrics.kernel_fallbacks import read  # noqa: F401

NAME = "mlp_kernel_fallbacks"
UNIT = "count"
BETTER = "lower"
SOURCE = "program_counter"
LAYER = "ring kernels"
MOVES = "evals_per_s"
WORKLOADS = ["mlp-score-batch"]
