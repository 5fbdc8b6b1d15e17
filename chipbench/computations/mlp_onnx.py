"""Multilayer-perceptron scoring as a user writes it: the fitted sklearn
``MLPClassifier`` written in skl2onnx's layout, imported with
``predictors.from_onnx`` and run as ``predictor_factory()`` (the
reference's ``MLPClassifier.from_onnx``): the rows from alice, the class
probabilities to bob, the weights and biases constants of the
computation."""

import types


def _mul_kernel_broadcasts() -> bool:
    """Whether the program's Mosaic multiply kernel pairs the lanes of
    operands that broadcast as ``ring.mul`` does: shapes only, nothing
    compiled or run."""
    import jax
    import numpy as np

    from moose_tpu.native import ring128_kernels as rk

    def product(x, y):
        return rk.cross_terms_mul((x, x), (x, x), (y, y), (y, y), 128)[0]

    sums = jax.ShapeDtypeStruct((3, 4, 1), np.uint64)
    lanes = jax.ShapeDtypeStruct((3, 4, 10), np.uint64)
    try:
        return jax.eval_shape(product, sums, lanes).shape == lanes.shape
    except Exception:  # noqa: BLE001 — a kernel that refuses the shapes
        return False


def build(pm, config: dict, case: dict, fixed_dtype):
    if not _mul_kernel_broadcasts():
        # the softmax divides rows x 10 by a rows x 1 sum; a multiply
        # kernel that walks flat lanes pairs lane i of one with lane i
        # of the other and the answer is off by 2^47 (PERF.md, PR 35):
        # fail by name, and soon
        raise SystemExit(
            "chipbench: this cell needs a program whose cross_terms_mul "
            "kernel takes operands that broadcast (rows x classes by "
            "rows x 1, the softmax's division)"
        )
    from moose_tpu import predictors
    from moose_tpu.predictors.multilayer_perceptron_predictor import (
        MLPClassifier,
    )
    from moose_tpu.predictors.sklearn_export import mlp_onnx

    # the arrays as sklearn holds them; the ONNX initializers are float32
    fitted = types.SimpleNamespace(
        coefs_=case["model"]["weights"],
        intercepts_=case["model"]["biases"],
        activation="relu",
    )
    onnx = mlp_onnx(fitted, config["shapes"]["features"], classifier=True)
    model = predictors.from_onnx(onnx.encode())
    if not isinstance(model, MLPClassifier):
        raise SystemExit(
            "chipbench: the ONNX bytes of this cell import as "
            f"{type(model).__name__}, not MLPClassifier"
        )
    return model.predictor_factory(fixed_dtype)
