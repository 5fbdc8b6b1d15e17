"""Plain reference of ONNX boosted-forest scoring, and the data of its
cells.

Float64 NumPy (and the sklearn fit that makes the model) and nothing
else: no import from ``moose_tpu``, no value the program has made.  The
model is part of the configuration, not of ``--seed``: a
``GradientBoostingRegressor`` at XGBoost's documented defaults, fitted
once from the configuration's own seed on synthetic rows with a
non-linear target.  What an ONNX file carries of it is float32: the
thresholds, the leaf weights (leaf value times learning rate) and the
base value; ``_model`` rounds them so and everything below computes in
float64 over those numbers.

A row goes down a tree as sklearn and the ONNX ``BRANCH_LEQ`` mode say:
to the true (left) child where ``x[feature] <= threshold``.  The rows
come from ``--seed``, N(0, 1); a row with a feature within the
configuration's ``inputs.threshold_margin`` of a threshold on that
feature is drawn again, so that no comparison turns on the rounding of
an operand to the program's 2^-40 (``expected`` asserts the margin).
"""

import functools
import json

import numpy as np

LEAF = -1  # sklearn's child id of a leaf


def _as_onnx_carries(a) -> np.ndarray:
    return np.asarray(a, dtype=np.float32).astype(np.float64)


@functools.lru_cache(maxsize=2)
def _fit(spec_json: str, features: int) -> dict:
    from sklearn.ensemble import GradientBoostingRegressor

    spec = json.loads(spec_json)
    rng = np.random.default_rng(spec["seed"])
    x = rng.normal(size=(spec["train_rows"], features))
    # a target no linear model fits: products, a kink, a step and noise
    y = (
        np.sin(2.0 * x[:, 0]) * x[:, 1]
        + np.abs(x[:, 2]) * x[:, 3]
        + (x[:, 4] > 0.5) * x[:, 5]
        + 0.5 * x[:, 6:10].sum(axis=1)
        + 0.3 * rng.normal(size=spec["train_rows"])
    )
    fitted = GradientBoostingRegressor(
        n_estimators=spec["n_estimators"], max_depth=spec["max_depth"],
        learning_rate=spec["learning_rate"], random_state=spec["seed"],
    ).fit(x, y)
    trees = []
    for (est,) in fitted.estimators_:
        t = est.tree_
        trees.append({
            "left": t.children_left.astype(np.int64),
            "right": t.children_right.astype(np.int64),
            "feature": t.feature.astype(np.int64),
            "threshold": _as_onnx_carries(t.threshold),
            # the ONNX target_weights: leaf value times learning rate
            "weight": _as_onnx_carries(
                t.value[:, 0, 0] * fitted.learning_rate
            ),
        })
    return {
        "trees": trees,
        "base": float(_as_onnx_carries(np.ravel(fitted.init_.constant_)[0])),
    }


def _model(config: dict) -> dict:
    return _fit(
        json.dumps(config["model"]["fit"], sort_keys=True),
        config["shapes"]["features"],
    )


def splits(model: dict) -> tuple:
    """(feature, threshold) of every inner node of the forest."""
    inner = [t["left"] != LEAF for t in model["trees"]]
    return tuple(
        np.concatenate([t[key][m] for t, m in zip(model["trees"], inner)])
        for key in ("feature", "threshold")
    )


def margin(model: dict, x: np.ndarray) -> np.ndarray:
    """Per row, the least distance of a feature from a threshold on
    that feature."""
    feature, threshold = splits(model)
    return np.abs(x[:, feature] - threshold).min(axis=1)


def make_case(config: dict, size: dict, distinct: int, seed: int) -> dict:
    """The model from the configuration's seed; ``distinct`` blocks of
    rows, N(0, 1) from ``seed``, every row clear of every threshold."""
    model = _model(config)
    rows, features = size["rows"], config["shapes"]["features"]
    least = config["inputs"]["threshold_margin"]
    rng = np.random.default_rng(seed)
    inputs = []
    for _ in range(distinct):
        x = rng.normal(size=(rows, features))
        close = margin(model, x) < least
        while close.any():
            x[close] = rng.normal(size=(int(close.sum()), features))
            close = margin(model, x) < least
        inputs.append({"x": x})
    return {"inputs": inputs, "model": model}


def _score(model: dict, x: np.ndarray, threshold_of, weight_of) -> np.ndarray:
    """Every row down every tree, a plain loop: at an inner node left
    where ``x[feature] <= threshold``, at a leaf its weight."""
    rows = np.arange(len(x))
    total = np.zeros(len(x))
    for tree in model["trees"]:
        threshold, weight = threshold_of(tree), weight_of(tree)
        node = np.zeros(len(x), dtype=np.int64)
        while True:
            inner = tree["left"][node] != LEAF
            if not inner.any():
                break
            go_left = x[rows, tree["feature"][node]] <= threshold[node]
            below = np.where(go_left, tree["left"][node], tree["right"][node])
            node = np.where(inner, below, node)
        total += weight[node]
    return total


def expected(config: dict, case: dict, i: int) -> np.ndarray:
    model, x = case["model"], case["inputs"][i]["x"]
    least = margin(model, x).min()
    assert least >= config["inputs"]["threshold_margin"], (
        f"a row lies {least} from a threshold: x < t and x <= t differ"
    )
    return model["base"] + _score(
        model, x, lambda t: t["threshold"], lambda t: t["weight"]
    )


def degraded(config: dict, case: dict, i: int) -> np.ndarray:
    """The control: this reference at the next precision below the
    configuration's, in the program's place: rows, thresholds, leaf
    weights, base value and score rounded to 2^-frac of the control."""
    frac = config["control"]["fixed"][1]
    model = case["model"]

    def q(a):
        return np.round(a * 2.0 ** frac) / 2.0 ** frac

    score = _score(
        model, q(case["inputs"][i]["x"]),
        lambda t: q(t["threshold"]), lambda t: q(t["weight"]),
    )
    return q(q(model["base"]) + score)


def numbers(config: dict, case: dict, i: int, got, want) -> dict:
    err = np.asarray(got, dtype=np.float64) - want
    return {
        "max_abs_err": float(np.abs(err).max()),
        "rms_err": float(np.sqrt(np.mean(err * err))),
    }


def counts(model: dict) -> dict:
    """The forest counted by hand, for the configuration's ``work``
    block and its test: inner nodes, those with two leaf children, and
    the trees' sizes."""
    nodes = two_leaf = 0
    sizes = []
    for t in model["trees"]:
        inner = np.flatnonzero(t["left"] != LEAF)
        nodes += len(inner)
        two_leaf += int(np.sum(
            (t["left"][t["left"][inner]] == LEAF)
            & (t["left"][t["right"][inner]] == LEAF)
        ))
        sizes.append(len(inner))
    return {
        "nodes": nodes, "two_leaf_nodes": two_leaf,
        "smallest_tree": min(sizes), "largest_tree": max(sizes),
    }
