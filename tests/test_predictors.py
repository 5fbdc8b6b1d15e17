"""Predictor zoo acceptance tests (modeled on the reference's
``pymoose/pymoose/predictors/*_test.py``): train sklearn models, export to
ONNX via the in-repo encoder, import with ``from_onnx``, run encrypted
inference under LocalMooseRuntime, and compare against sklearn outputs
within fixed-point tolerance."""

import numpy as np
import pytest

import moose_tpu as pm
from moose_tpu import predictors
from moose_tpu.predictors import predictor_utils
from moose_tpu.runtime import LocalMooseRuntime

import onnx_fixtures as fx

sklearn = pytest.importorskip("sklearn")
from sklearn import ensemble, linear_model, neural_network  # noqa: E402

RNG = np.random.default_rng(1234)


def _run_predictor(model, x, serialize_roundtrip=False):
    if serialize_roundtrip:
        model = predictors.from_onnx(model.encode())
    else:
        model = predictors.from_onnx(model)
    comp = model.predictor_factory()
    runtime = LocalMooseRuntime(["alice", "bob", "carole"])
    outs = runtime.evaluate_computation(
        comp, arguments={"x": np.asarray(x, dtype=np.float64)}
    )
    (res,) = outs.values()
    return model, np.asarray(res)


def _regression_data(n=40, d=5, targets=1):
    x = RNG.normal(size=(n, d))
    w = RNG.normal(size=(d, targets))
    y = x @ w + 0.1 * RNG.normal(size=(n, targets))
    return x, y if targets > 1 else y.ravel()


def _classification_data(n=60, d=4, classes=2):
    x = RNG.normal(size=(n, d))
    y = RNG.integers(0, classes, size=n)
    # make classes linearly separable-ish so probabilities aren't degenerate
    x += 0.8 * np.eye(d)[y % d]
    return x, y


def test_linear_regressor_matches_sklearn():
    x, y = _regression_data()
    sk = linear_model.LinearRegression().fit(x, y)
    onnx_model = fx.linear_regressor_onnx(sk, x.shape[1])
    model, got = _run_predictor(onnx_model, x[:8], serialize_roundtrip=True)
    assert isinstance(model, predictors.LinearRegressor)
    np.testing.assert_allclose(
        got.ravel(), sk.predict(x[:8]).ravel(), atol=1e-4
    )


def test_linear_regressor_two_targets():
    x, y = _regression_data(targets=2)
    sk = linear_model.LinearRegression().fit(x, y)
    onnx_model = fx.linear_regressor_onnx(sk, x.shape[1])
    _, got = _run_predictor(onnx_model, x[:8])
    np.testing.assert_allclose(got, sk.predict(x[:8]), atol=1e-4)


def test_logistic_regression_binary_matches_sklearn():
    x, y = _classification_data(classes=2)
    sk = linear_model.LogisticRegression().fit(x, y)
    onnx_model = fx.logistic_regression_onnx(sk, x.shape[1])
    model, got = _run_predictor(onnx_model, x[:8], serialize_roundtrip=True)
    assert isinstance(model, predictors.LinearClassifier)
    np.testing.assert_allclose(got, sk.predict_proba(x[:8]), atol=5e-3)


def test_logistic_regression_multiclass_softmax():
    x, y = _classification_data(classes=3)
    sk = linear_model.LogisticRegression().fit(x, y)
    onnx_model = fx.logistic_regression_onnx(sk, x.shape[1])
    _, got = _run_predictor(onnx_model, x[:8])
    np.testing.assert_allclose(got, sk.predict_proba(x[:8]), atol=5e-3)


def test_random_forest_regressor():
    x, y = _regression_data(n=80)
    sk = ensemble.RandomForestRegressor(
        n_estimators=4, max_depth=3, random_state=0
    ).fit(x, y)
    onnx_model = fx.random_forest_regressor_onnx(sk, x.shape[1])
    model, got = _run_predictor(onnx_model, x[:6], serialize_roundtrip=True)
    assert isinstance(model, predictors.TreeEnsembleRegressor)
    np.testing.assert_allclose(got.ravel(), sk.predict(x[:6]), atol=1e-3)


def test_random_forest_classifier_binary():
    x, y = _classification_data(n=80, classes=2)
    sk = ensemble.RandomForestClassifier(
        n_estimators=4, max_depth=3, random_state=0
    ).fit(x, y)
    onnx_model = fx.random_forest_classifier_onnx(sk, x.shape[1])
    model, got = _run_predictor(onnx_model, x[:6])
    assert isinstance(model, predictors.TreeEnsembleClassifier)
    np.testing.assert_allclose(got, sk.predict_proba(x[:6]), atol=1e-3)


def test_random_forest_classifier_multiclass():
    x, y = _classification_data(n=90, classes=3)
    sk = ensemble.RandomForestClassifier(
        n_estimators=3, max_depth=2, random_state=0
    ).fit(x, y)
    onnx_model = fx.random_forest_classifier_onnx(sk, x.shape[1])
    _, got = _run_predictor(onnx_model, x[:6])
    np.testing.assert_allclose(got, sk.predict_proba(x[:6]), atol=1e-3)


def _seeded(make, seed, **kw):
    """``_regression_data`` / ``_classification_data`` from a generator
    of the test's own, so the forest does not depend on which tests ran
    before."""
    global RNG
    kept, RNG = RNG, np.random.default_rng(seed)
    try:
        return make(**kw)
    finally:
        RNG = kept


def _forest_of(shape, kind, x, y):
    """A small sklearn forest of one of four shapes: ``complete`` (every
    leaf at depth 2), ``ragged`` (best-first growth: leaves at several
    depths), ``single_leaf`` (two grown trees and two that are one leaf
    each) and ``depth1`` (stumps: every root has two leaf children)."""
    cls = (
        ensemble.RandomForestRegressor if kind == "regressor"
        else ensemble.RandomForestClassifier
    )
    grow = {
        "complete": dict(max_depth=2),
        "ragged": dict(max_depth=4, max_leaf_nodes=6),
        "single_leaf": dict(max_depth=3),
        "depth1": dict(max_depth=1),
    }[shape]
    sk = cls(n_estimators=2, random_state=0, **grow).fit(x, y)
    if shape == "single_leaf":
        sk.set_params(
            n_estimators=4, warm_start=True, min_samples_split=10 ** 6
        )
        sk.fit(x, y)
        assert sorted(e.tree_.node_count for e in sk.estimators_)[:2] == [1, 1]
    return sk


@pytest.mark.parametrize("kind", ["regressor", "binary", "three_class"])
@pytest.mark.parametrize(
    "shape", ["complete", "ragged", "single_leaf", "depth1"]
)
def test_level_fold_matches_sklearn(shape, kind):
    if kind == "regressor":
        x, y = _seeded(_regression_data, 32, n=120)
        sk = _forest_of(shape, kind, x, y)
        onnx_model = fx.random_forest_regressor_onnx(sk, x.shape[1])
        want = sk.predict(x[:5])
    else:
        x, y = _seeded(
            _classification_data, 32, n=120,
            classes=2 if kind == "binary" else 3,
        )
        sk = _forest_of(shape, kind, x, y)
        onnx_model = fx.random_forest_classifier_onnx(sk, x.shape[1])
        want = sk.predict_proba(x[:5])
    sizes = {e.tree_.max_depth for e in sk.estimators_}
    assert sizes == {
        "complete": {2}, "depth1": {1}, "single_leaf": {0, 3},
    }.get(shape, sizes)
    model, got = _run_predictor(onnx_model, x[:5])
    np.testing.assert_allclose(got.reshape(want.shape), want, atol=1e-3)
    # the graph's size follows the forest's depth, not its nodes
    assert len(model.traced_predictor().operations) < 60


def test_gradient_boosting_regressor_branches_as_sklearn():
    x, y = _seeded(_regression_data, 32, n=120)
    sk = ensemble.GradientBoostingRegressor(
        n_estimators=4, max_depth=3, learning_rate=0.3, random_state=0
    ).fit(x, np.sin(2 * y))
    onnx_model = fx.gradient_boosting_regressor_onnx(sk, x.shape[1])
    model, got = _run_predictor(onnx_model, x[:4], serialize_roundtrip=True)
    assert isinstance(model, predictors.TreeEnsembleRegressor)
    assert {m for t in model.trees for m in t.modes} == {"BRANCH_LEQ", "LEAF"}
    assert model.base_score == pytest.approx(float(np.sin(2 * y).mean()))
    np.testing.assert_allclose(got.ravel(), sk.predict(x[:4]), atol=1e-5)


def _stump_onnx(mode):
    """One split, x0 against 0.5: 1.0 on the true branch, 2.0 on the
    false one."""
    node = fx.op.make_node(
        "TreeEnsembleRegressor", ["float_input"], ["variable"],
        name="TreeEnsembleRegressor", post_transform="NONE",
        nodes_treeids=[0, 0, 0], nodes_nodeids=[0, 1, 2],
        nodes_truenodeids=[1, 0, 0], nodes_falsenodeids=[2, 0, 0],
        nodes_featureids=[0, 0, 0], nodes_values=[0.5, 0.0, 0.0],
        nodes_modes=[mode, "LEAF", "LEAF"],
        target_treeids=[0, 0], target_nodeids=[1, 2], target_ids=[0, 0],
        target_weights=[1.0, 2.0],
    )
    graph = fx.op.GraphProto(
        name="stump", node=[node], initializer=[],
        input=[fx.op.make_tensor_value_info("float_input", fx.FLOAT, [None, 2])],
        output=[fx.op.make_tensor_value_info("variable", fx.FLOAT, [None, 1])],
    )
    return fx.op.make_model(graph, producer_name="skl2onnx")


@pytest.mark.parametrize(
    "mode, on_the_threshold", [("BRANCH_LT", 2.0), ("BRANCH_LEQ", 1.0)]
)
def test_branch_modes_are_evaluated_as_written(mode, on_the_threshold):
    x = np.array([[0.25, 0.0], [0.5, 0.0], [0.75, 0.0]])
    _, got = _run_predictor(_stump_onnx(mode), x)
    np.testing.assert_allclose(
        got.ravel(), [1.0, on_the_threshold, 2.0], atol=1e-9
    )


def test_an_unsupported_branch_mode_is_refused_by_name():
    with pytest.raises(ValueError, match="BRANCH_GT"):
        predictors.from_onnx(_stump_onnx("BRANCH_GT"))


def test_a_forest_traces_by_depth_not_by_nodes():
    """20 trees of depth 5, some 330 inner nodes: traced node by node a forest of 620 nodes
    was still compiling after 7.5 minutes and one of 150 took 84 s (PERF.md, PR 32)."""
    import time

    x, y = _seeded(_regression_data, 32, n=400, d=8)
    sk = ensemble.RandomForestRegressor(
        n_estimators=20, max_depth=5, random_state=0
    ).fit(x, np.sin(2 * y) + x[:, 0] * x[:, 1])
    inner = sum(
        int((e.tree_.children_left != -1).sum()) for e in sk.estimators_
    )
    assert inner > 300
    model = predictors.from_onnx(
        fx.random_forest_regressor_onnx(sk, x.shape[1])
    )
    t0 = time.perf_counter()
    traced = model.traced_predictor()
    assert time.perf_counter() - t0 < 20.0
    runtime = LocalMooseRuntime(["alice", "bob", "carole"])
    (got,) = runtime.evaluate_computation(
        traced, arguments={"x": x[:8]}
    ).values()
    assert len(traced.operations) < 60
    assert runtime.last_plan["ops"] == len(traced.operations)
    kinds = [op.kind for op in traced.operations.values()]
    assert kinds.count("Less") == 1 and kinds.count("Mux") <= 5
    np.testing.assert_allclose(
        np.asarray(got).ravel(), sk.predict(x[:8]), atol=1e-5
    )


@pytest.mark.parametrize("activation", ["relu", "logistic"])
def test_mlp_regressor(activation):
    x, y = _regression_data(n=60)
    sk = neural_network.MLPRegressor(
        hidden_layer_sizes=(8,),
        activation=activation,
        max_iter=200,
        random_state=0,
    ).fit(x, y)
    onnx_model = fx.mlp_onnx(sk, x.shape[1])
    model, got = _run_predictor(onnx_model, x[:6], serialize_roundtrip=True)
    assert isinstance(model, predictors.MLPRegressor)
    np.testing.assert_allclose(got.ravel(), sk.predict(x[:6]), atol=5e-3)


def test_mlp_classifier_binary():
    x, y = _classification_data(n=70, classes=2)
    sk = neural_network.MLPClassifier(
        hidden_layer_sizes=(6,),
        activation="relu",
        max_iter=200,
        random_state=0,
    ).fit(x, y)
    onnx_model = fx.mlp_onnx(sk, x.shape[1], classifier=True)
    model, got = _run_predictor(onnx_model, x[:6])
    assert isinstance(model, predictors.MLPClassifier)
    np.testing.assert_allclose(got, sk.predict_proba(x[:6]), atol=1e-2)


def test_mlp_classifier_multiclass():
    x, y = _classification_data(n=90, classes=3)
    sk = neural_network.MLPClassifier(
        hidden_layer_sizes=(6,),
        activation="logistic",
        max_iter=200,
        random_state=0,
    ).fit(x, y)
    onnx_model = fx.mlp_onnx(sk, x.shape[1], classifier=True)
    _, got = _run_predictor(onnx_model, x[:6])
    np.testing.assert_allclose(got, sk.predict_proba(x[:6]), atol=1e-2)


def test_pytorch_neural_network():
    d = 4
    w0 = RNG.normal(size=(6, d)) * 0.5  # pytorch (out, in) layout
    b0 = RNG.normal(size=(6,)) * 0.1
    w1 = RNG.normal(size=(1, 6)) * 0.5
    b1 = RNG.normal(size=(1,)) * 0.1
    onnx_model = fx.pytorch_nn_onnx(
        [w0, w1], [b0, b1], ["Relu", "Sigmoid"], d
    )
    x = RNG.normal(size=(5, d))
    model, got = _run_predictor(onnx_model, x, serialize_roundtrip=True)
    assert isinstance(model, predictors.NeuralNetwork)

    h = np.maximum(x.astype(np.float32) @ w0.T.astype(np.float32) + b0, 0)
    want = 1 / (1 + np.exp(-(h @ w1.T + b1)))
    np.testing.assert_allclose(got, want, atol=1e-2)


def test_onnx_roundtrip_preserves_structure():
    x, y = _regression_data()
    sk = linear_model.LinearRegression().fit(x, y)
    model = fx.linear_regressor_onnx(sk, x.shape[1])
    decoded = predictors.onnx_proto.ModelProto.decode(model.encode())
    assert decoded.producer_name == "skl2onnx"
    node = decoded.graph.node[0]
    assert node.op_type == "LinearRegressor"
    coeffs = predictor_utils.find_attribute_in_node(node, "coefficients")
    np.testing.assert_allclose(
        np.asarray(coeffs.floats, dtype=np.float64),
        np.asarray(sk.coef_, dtype=np.float32).ravel(),
        rtol=1e-6,
    )


def test_from_onnx_rejects_unknown_graph():
    graph = fx.op.GraphProto(
        name="g",
        node=[fx.op.make_node("Unknown", ["x"], ["y"])],
        input=[fx.op.make_tensor_value_info("x", fx.FLOAT, [None, 2])],
        output=[fx.op.make_tensor_value_info("y", fx.FLOAT, [None, 1])],
    )
    with pytest.raises(ValueError, match="Incompatible ONNX graph"):
        predictors.from_onnx(fx.op.make_model(graph))
