"""Plain reference of ONNX logistic-regression scoring, and the data of
its cells.

Float64 NumPy (and the sklearn fit that makes the model) and nothing
else: no import from ``moose_tpu``, no value the program has made.  The
model is part of the configuration, not of ``--seed``: it is fitted once
from the configuration's own seed on synthetic data, and its
coefficients are what an ONNX file carries, float32.  The rows come from
``--seed`` and are scaled so that the largest |logit| of a block is the
configuration's ``inputs.max_abs_logit``: the program's ``fixed(24, 40)``
sigmoid overflows by design past |logit| 16.6 (PERF.md, PR 22).

The answer is the two-column layout of the ONNX ``LinearClassifier``
with the ``LOGISTIC`` post-transform: ``[sigmoid(-z), sigmoid(z)]``.
"""

import numpy as np


def _model(config: dict) -> dict:
    from sklearn.linear_model import LogisticRegression

    spec, features = config["model"], config["shapes"]["features"]
    rng = np.random.default_rng(spec["seed"])
    x = rng.normal(size=(spec["train_rows"], features))
    w = rng.normal(size=features) / np.sqrt(features)
    # noisy labels: a separable fit grows its weights without bound
    y = (x @ w + rng.normal(size=spec["train_rows"]) > 0).astype(int)
    fitted = LogisticRegression().fit(x, y)

    def as_onnx_carries(a):
        return np.asarray(a, dtype=np.float32).astype(np.float64)

    return {
        "coef": as_onnx_carries(fitted.coef_),
        "intercept": as_onnx_carries(fitted.intercept_),
        "classes": [int(c) for c in fitted.classes_],
    }


def make_case(config: dict, size: dict, distinct: int, seed: int) -> dict:
    """The model from the configuration's seed; ``distinct`` blocks of
    rows, N(0, 1) from ``seed``, each scaled to the logit bound."""
    model = _model(config)
    w, b = model["coef"][0], float(model["intercept"][0])
    rows, features = size["rows"], config["shapes"]["features"]
    room = config["inputs"]["max_abs_logit"] - abs(b)
    rng = np.random.default_rng(seed)
    inputs = []
    for _ in range(distinct):
        x = rng.normal(size=(rows, features))
        x *= room / np.abs(x @ w).max()
        inputs.append({"x": x})
    return {"inputs": inputs, "model": model}


def _scores(z: np.ndarray) -> np.ndarray:
    return np.stack(
        [1.0 / (1.0 + np.exp(z)), 1.0 / (1.0 + np.exp(-z))], axis=1
    )


def expected(config: dict, case: dict, i: int) -> np.ndarray:
    model = case["model"]
    z = case["inputs"][i]["x"] @ model["coef"][0] + model["intercept"][0]
    return _scores(z)


def degraded(config: dict, case: dict, i: int) -> np.ndarray:
    """The control: this reference at the next precision below the
    configuration's, in the program's place: operands, logit and scores
    rounded to 2^-frac of the control."""
    frac = config["control"]["fixed"][1]
    model = case["model"]

    def q(a):
        return np.round(a * 2.0 ** frac) / 2.0 ** frac

    z = q(q(case["inputs"][i]["x"]) @ q(model["coef"][0])
          + q(model["intercept"][0]))
    return q(_scores(z))


def numbers(config: dict, case: dict, i: int, got, want) -> dict:
    err = np.asarray(got, dtype=np.float64) - want
    return {
        "max_abs_err": float(np.abs(err).max()),
        "rms_err": float(np.sqrt(np.mean(err * err))),
    }
