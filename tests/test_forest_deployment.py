"""The deployment the benchmark's ``gbt-score-batch`` cell runs, tied to
its plain reference at a small size on the CPU: the computation as
``chipbench/computations/gbt_onnx.py`` builds it (the seeded boosted
forest -> ONNX -> ``from_onnx`` -> ``predictor_factory()``) through
``LocalMooseRuntime``, against ``chipbench/reference/gbt_onnx.py``, by
the configuration's own limits; and the control, the same reference at
``fixed(14, 23)``, outside them.
"""

import json
import os
import types

import numpy as np
import pytest

import moose_tpu as pm
from chipbench.computations import gbt_onnx as computation
from chipbench.drivers import eval_loop
from chipbench.reference import gbt_onnx as reference
from moose_tpu.edsl import tracer
from moose_tpu.runtime import LocalMooseRuntime

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROWS = 8


@pytest.fixture(scope="module")
def config():
    path = os.path.join(ROOT, "chipbench", "configs", "gbt-onnx-r128.json")
    with open(path) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def case(config):
    return reference.make_case(config, {"rows": ROWS}, 2, 2147483659)


def _check(config, case, answers) -> dict:
    ctx = types.SimpleNamespace(config=config)
    state = eval_loop.State(ctx, case, None, None)
    rec = eval_loop.Window()
    for n, got in enumerate(answers):
        rec.starts.append(0.0)
        rec.ends.append(1.0)
        rec.kept.append((n, got))
    return eval_loop.check(state, rec)


def test_the_case_is_the_configurations(config, case):
    model, shapes = case["model"], config["shapes"]
    assert len(model["trees"]) == shapes["trees"]
    counted = reference.counts(model)
    assert counted["nodes"] == shapes["nodes"]
    assert counted["two_leaf_nodes"] == shapes["two_leaf_nodes"]
    # the forest is ragged: the level fold has to take that
    assert counted["smallest_tree"] < counted["largest_tree"] <= 63
    # what an ONNX file carries: float32 thresholds and weights
    for tree in model["trees"]:
        for key in ("threshold", "weight"):
            assert np.array_equal(tree[key], tree[key].astype(np.float32))
    assert model["base"] == np.float32(model["base"])
    least = config["inputs"]["threshold_margin"]
    assert least == 2.0 ** -30
    for arguments in case["inputs"]:
        x = arguments["x"]
        assert x.shape == (ROWS, shapes["features"]) and x.dtype == np.float64
        assert reference.margin(model, x).min() >= least
    # the model is the configuration's, whatever --seed is
    other = reference.make_case(config, {"rows": 4}, 1, 7)
    assert other["model"] is model
    assert not np.array_equal(other["inputs"][0]["x"], case["inputs"][0]["x"][:4])


def test_a_row_on_a_threshold_is_drawn_again(config, case, monkeypatch):
    model = case["model"]
    feature, threshold = reference.splits(model)
    drawn = []
    real_rng = np.random.default_rng

    class Rng:
        def __init__(self, seed):
            self._rng = real_rng(seed)

        def normal(self, size):
            x = self._rng.normal(size=size)
            if not drawn:  # the first block: one row sits on a threshold
                x[1, feature[0]] = threshold[0] + 2.0 ** -32
            drawn.append(size)
            return x

    monkeypatch.setattr(reference.np.random, "default_rng", Rng)
    made = reference.make_case(config, {"rows": 4}, 1, 11)
    assert drawn == [(4, 100), (1, 100)]
    assert reference.margin(model, made["inputs"][0]["x"]).min() >= 2.0 ** -30
    on_it = {"inputs": [{"x": made["inputs"][0]["x"].copy()}], "model": model}
    on_it["inputs"][0]["x"][0, feature[0]] = threshold[0]
    with pytest.raises(AssertionError, match="from a threshold"):
        reference.expected(config, on_it, 0)


def test_the_reference_is_sklearns_forest_in_float32_carriage(config, case):
    """The plain loop against sklearn's own predict on a forest fitted
    the same way: they differ by the float32 rounding of 100 weights."""
    from sklearn.ensemble import GradientBoostingRegressor

    spec = config["model"]["fit"]
    rng = np.random.default_rng(spec["seed"])
    x = rng.normal(size=(spec["train_rows"], config["shapes"]["features"]))
    want = reference.expected(config, case, 0)
    assert want.shape == (ROWS,)
    numbers = reference.numbers(config, case, 0, want + 1e-9, want)
    assert numbers["max_abs_err"] == pytest.approx(1e-9, rel=1e-3)
    assert numbers["rms_err"] == pytest.approx(1e-9, rel=1e-3)
    # a small forest of the same kind, scored both ways
    y = np.sin(2.0 * x[:, 0]) * x[:, 1] + np.abs(x[:, 2]) * x[:, 3]
    sk = GradientBoostingRegressor(
        n_estimators=5, max_depth=3, learning_rate=0.3, random_state=0
    ).fit(x[:256], y[:256])
    model = {
        "trees": [{
            "left": e.tree_.children_left, "right": e.tree_.children_right,
            "feature": e.tree_.feature,
            "threshold": reference._as_onnx_carries(e.tree_.threshold),
            "weight": reference._as_onnx_carries(e.tree_.value[:, 0, 0] * 0.3),
        } for (e,) in sk.estimators_],
        "base": float(np.ravel(sk.init_.constant_)[0]),
    }
    small = {"inputs": [{"x": x[300:332]}], "model": model}
    np.testing.assert_allclose(
        reference.expected(config, small, 0), sk.predict(x[300:332]), atol=1e-6
    )


def test_the_program_is_inside_the_limits_and_the_control_outside(config, case):
    comp = computation.build(
        pm, config, case, eval_loop.fixed_dtype(pm, config)
    )
    traced = tracer.trace(comp)
    assert len(traced.operations) < 60
    # one jitted program, as the cell runs it (the suite's default is eager)
    runtime = LocalMooseRuntime(list(config["parties"]), use_jit=True)
    answers = []
    for arguments in case["inputs"]:
        (out,) = runtime.evaluate_computation(comp, arguments=arguments).values()
        answers.append(np.asarray(out))
    assert answers[0].shape == (ROWS,)
    plan = runtime.last_plan
    assert plan["ops"] == len(traced.operations)
    assert plan["layout"] == "stacked" and plan["plan_mode"] == "whole-graph"
    assert plan["pinned_ops"] == []
    good = _check(config, case, answers)
    assert good["correct"] and good["failed"] == 0, good["numbers"]
    assert set(good["numbers"]) == set(config["limits"])

    control = _check(config, case, [
        reference.degraded(config, case, i) for i in range(len(case["inputs"]))
    ])
    assert not control["correct"]
    assert control["failed"] == len(case["inputs"])
    for name, number in control["numbers"].items():
        assert number["value"] > number["limit"], name


def test_a_program_without_the_gather_fails_the_cell_by_name(config, case):
    parent = types.SimpleNamespace(fixed128=pm.fixed128)  # no `gather`
    with pytest.raises(SystemExit, match="moose_tpu.gather"):
        computation.build(parent, config, case, None)
