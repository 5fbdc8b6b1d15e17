"""Logistic-regression scoring as a user writes it: the fitted sklearn
model exported to ONNX, imported with ``predictors.from_onnx`` and run as
``predictor_factory()`` (the reference's ``ml-inference-with-onnx``
tutorial): the rows from alice, the scores to bob, the model's
coefficients constants of the computation."""

import types


def build(pm, config: dict, case: dict, fixed_dtype):
    from moose_tpu import compile_cache, predictors

    if not hasattr(compile_cache, "plan_verdict_dir"):
        # a plan of this size is validated against its eager twin at
        # full size on a TPU; a program that keeps no verdict does that
        # in every process, 318-331 s of set-up (PERF.md, PR 25), and a
        # run has no room for it: fail by name, and soon
        raise SystemExit(
            "chipbench: this cell needs a program that keeps a validated "
            "plan's verdict beside the compile cache "
            "(moose_tpu.compile_cache.plan_verdict_dir)"
        )
    from moose_tpu.predictors.sklearn_export import logistic_regression_onnx

    fitted = types.SimpleNamespace(
        coef_=case["model"]["coef"],
        intercept_=case["model"]["intercept"],
        classes_=case["model"]["classes"],
    )
    onnx = logistic_regression_onnx(fitted, config["shapes"]["features"])
    return predictors.from_onnx(onnx.encode()).predictor_factory(fixed_dtype)
