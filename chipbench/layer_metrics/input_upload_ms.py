"""Per evaluation: the ``jax.device_put`` calls for arguments the device
cache did not hold, or held stale (``input_upload`` spans; 0 where every
argument was resident)."""

from chipbench import program_spans

NAME = "input_upload_ms"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "eDSL + runtime"
MOVES = "evals_per_s"


def read(view):
    return program_spans.row_ms(view, "input_upload")
