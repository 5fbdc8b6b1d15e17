"""Plain reference of ONNX multilayer-perceptron scoring, and the data
of its cells.

Float64 NumPy (and the sklearn fit that makes the model) and nothing
else: no import from ``moose_tpu``, no value the program has made.  The
model is part of the configuration, not of ``--seed``: sklearn's
``MLPClassifier(hidden_layer_sizes=(128, 128), activation="relu")``
fitted once from the configuration's own seed on synthetic 10-class rows
of 784 features in [0, 1] (a sparse prototype a class, Gaussian noise,
clipped), ``max_iter`` few enough that the fit takes 2-3 s (sklearn warns
that it has not converged, which is the point: the weights are a
network's, not a good one's).  What an ONNX file carries of it is
float32: ``_model`` rounds weights and biases so and everything below
computes in float64 over those numbers.

The equations, as the upstream predictor evaluates them:
h1 = relu(x W1 + b1), h2 = relu(h1 W2 + b2), z = h2 W3 + b3; then the
softmax of ``softmax.rs``: m = max_j z_j, d = z - m, a lane with
d < -ln 2 * min(i - 1, f - 1) (15.94 at fixed(24, 40)) gives exactly 0
and the sum leaves it out, the others e = 2^(d log2 e), p = e / sum e.

The rows come from ``--seed``, uniform in [0, 1); a row whose logits
spread (largest less smallest) by more than the configuration's
``inputs.logit_spread_max`` is drawn again, so that no lane comes near
the clamp's edge, where the program's 2^-40 rounding of d would decide
between 0 and e^-15.94 (``expected`` asserts the spread).
"""

import functools
import json
import math
import warnings

import numpy as np


def _as_onnx_carries(a) -> np.ndarray:
    return np.asarray(a, dtype=np.float32).astype(np.float64)


@functools.lru_cache(maxsize=2)
def _fit(spec_json: str, features: int, hidden: tuple, classes: int) -> dict:
    from sklearn.neural_network import MLPClassifier

    spec = json.loads(spec_json)
    rng = np.random.default_rng(spec["seed"])
    rows = spec["train_rows"]
    # a sparse prototype a class (a quarter of the features lit), noise
    # on every feature, clipped to a pixel's range
    prototypes = rng.uniform(size=(classes, features)) * (
        rng.uniform(size=(classes, features)) < spec["lit_share"]
    )
    y = rng.integers(0, classes, size=rows)
    x = np.clip(
        prototypes[y] + spec["noise"] * rng.normal(size=(rows, features)),
        0.0, 1.0,
    )
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")  # max_iter reached: as set
        fitted = MLPClassifier(
            hidden_layer_sizes=hidden, activation="relu",
            max_iter=spec["max_iter"], random_state=spec["seed"],
        ).fit(x, y)
    assert list(fitted.classes_) == list(range(classes))
    return {
        "weights": [_as_onnx_carries(w) for w in fitted.coefs_],
        "biases": [_as_onnx_carries(b) for b in fitted.intercepts_],
    }


def _model(config: dict) -> dict:
    shapes = config["shapes"]
    return _fit(
        json.dumps(config["model"]["fit"], sort_keys=True),
        shapes["features"], tuple(shapes["hidden"]), shapes["classes"],
    )


def logits(model: dict, x: np.ndarray, q=lambda a: a) -> np.ndarray:
    """The dense stack; ``q`` rounds weights, biases and every layer's
    output (the control), and is the identity for the reference."""
    last = len(model["weights"]) - 1
    h = x
    for i, (w, b) in enumerate(zip(model["weights"], model["biases"])):
        h = q(h @ q(w) + q(b))
        if i < last:
            h = np.maximum(h, 0.0)
    return h


def spread(z: np.ndarray) -> np.ndarray:
    """Per row, the largest logit less the smallest."""
    return z.max(axis=1) - z.min(axis=1)


def clamp_edge(fixed) -> float:
    """How far under its row's largest a lane may lie before the
    upstream's softmax gives exactly 0 for it (softmax.rs)."""
    integral, fractional = fixed
    return math.log(2.0) * min(integral - 1, fractional - 1)


def softmax(z: np.ndarray, edge: float) -> np.ndarray:
    d = z - z.max(axis=1, keepdims=True)
    e = np.where(d < -edge, 0.0, np.exp2(d * math.log2(math.e)))
    return e / e.sum(axis=1, keepdims=True)


def make_case(config: dict, size: dict, distinct: int, seed: int) -> dict:
    """The model from the configuration's seed; ``distinct`` blocks of
    rows, uniform in [0, 1) from ``seed``, every row's logits within
    the configuration's spread."""
    model = _model(config)
    rows, features = size["rows"], config["shapes"]["features"]
    most = config["inputs"]["logit_spread_max"]
    rng = np.random.default_rng(seed)
    inputs = []
    for _ in range(distinct):
        x = rng.random(size=(rows, features))
        wide = spread(logits(model, x)) > most
        while wide.any():
            x[wide] = rng.random(size=(int(wide.sum()), features))
            wide = spread(logits(model, x)) > most
        inputs.append({"x": x})
    return {"inputs": inputs, "model": model}


def expected(config: dict, case: dict, i: int) -> np.ndarray:
    z = logits(case["model"], case["inputs"][i]["x"])
    widest = spread(z).max()
    assert widest <= config["inputs"]["logit_spread_max"], (
        f"a row's logits spread {widest}: too near the softmax's clamp "
        f"at {clamp_edge(config['fixed'])}"
    )
    return softmax(z, clamp_edge(config["fixed"]))


def degraded(config: dict, case: dict, i: int) -> np.ndarray:
    """The control: this reference at the next precision below the
    configuration's, in the program's place: rows, weights, biases,
    every layer's output and the probabilities rounded to 2^-frac of
    the control (the clamp stays the configuration's)."""
    frac = config["control"]["fixed"][1]

    def q(a):
        return np.round(a * 2.0 ** frac) / 2.0 ** frac

    z = logits(case["model"], q(case["inputs"][i]["x"]), q)
    return q(softmax(z, clamp_edge(config["fixed"])))


def numbers(config: dict, case: dict, i: int, got, want) -> dict:
    err = np.asarray(got, dtype=np.float64) - want
    return {
        "max_abs_err": float(np.abs(err).max()),
        "rms_err": float(np.sqrt(np.mean(err * err))),
    }


def counts(config: dict) -> dict:
    """The network counted by hand from the configuration's shapes, for
    its ``work`` block and its test."""
    shapes = config["shapes"]
    widths = [shapes["features"], *shapes["hidden"], shapes["classes"]]
    pairs = list(zip(widths[:-1], widths[1:]))
    return {
        "parameters": sum(a * b + b for a, b in pairs),
        "macs_per_row": [a * b for a, b in pairs],
        "relu_lanes_per_row": sum(shapes["hidden"]),
    }
