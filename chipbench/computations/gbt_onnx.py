"""Boosted-forest scoring as a user writes it: the fitted forest written
in the ONNX layout, imported with ``predictors.from_onnx`` and run as
``predictor_factory()`` (the reference's
``TreeEnsembleRegressor.from_onnx``): the rows from alice, the prediction
to bob, the thresholds, tree shape and leaf weights constants of the
computation."""

import types


def build(pm, config: dict, case: dict, fixed_dtype):
    if not hasattr(pm, "gather"):
        # a program without the static gather traces a forest node by
        # node: ~25,000 logical ops for this one, an hour of tracing and
        # a segmented plan that keeps no verdict (PERF.md, PR 32): fail
        # by name, and soon
        raise SystemExit(
            "chipbench: this cell needs a program whose eDSL has the "
            "static gather (moose_tpu.gather) and a tree-ensemble "
            "predictor that folds a forest by level"
        )
    from moose_tpu import predictors
    from moose_tpu.predictors.sklearn_export import (
        gradient_boosting_regressor_onnx,
    )

    model = case["model"]
    # the arrays as sklearn holds them; the weights already carry the
    # learning rate, as the ONNX file does
    fitted = types.SimpleNamespace(
        learning_rate=1.0,
        init_=types.SimpleNamespace(constant_=[[model["base"]]]),
        estimators_=[
            (types.SimpleNamespace(tree_=types.SimpleNamespace(
                children_left=t["left"], children_right=t["right"],
                feature=t["feature"], threshold=t["threshold"],
                value=t["weight"].reshape(-1, 1, 1),
                node_count=len(t["left"]),
            )),)
            for t in model["trees"]
        ],
    )
    onnx = gradient_boosting_regressor_onnx(
        fitted, config["shapes"]["features"]
    )
    return predictors.from_onnx(onnx.encode()).predictor_factory(fixed_dtype)
