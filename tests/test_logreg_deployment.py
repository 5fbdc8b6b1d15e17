"""The deployment the benchmark's ``logreg-score-64k`` cell runs, tied to
its plain reference at a small size on the CPU: the computation as
``chipbench/computations/logreg_onnx.py`` builds it (sklearn fit -> ONNX
-> ``from_onnx`` -> ``predictor_factory()``) through
``LocalMooseRuntime``, against ``chipbench/reference/logreg_onnx.py``,
by the configuration's own limits; and the control, the same reference
at ``fixed(14, 23)``, outside them.
"""

import json
import os
import types

import numpy as np
import pytest

import moose_tpu as pm
from chipbench.computations import logreg_onnx as computation
from chipbench.drivers import eval_loop
from chipbench.reference import logreg_onnx as reference
from moose_tpu import metrics, telemetry
from moose_tpu.runtime import LocalMooseRuntime

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROWS = 64


@pytest.fixture(scope="module")
def config():
    path = os.path.join(ROOT, "chipbench", "configs", "logreg-onnx-r128.json")
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
    model = case["model"]
    assert model["coef"].shape == (1, config["shapes"]["features"])
    assert model["classes"] == [0, 1]
    # what an ONNX file carries: float32 coefficients
    assert np.array_equal(model["coef"], model["coef"].astype(np.float32))
    bound = config["inputs"]["max_abs_logit"]
    for arguments in case["inputs"]:
        x = arguments["x"]
        assert x.shape == (ROWS, 100) and x.dtype == np.float64
        logit = x @ model["coef"][0] + model["intercept"][0]
        assert np.abs(logit).max() <= bound + 1e-9  # to rounding
        assert np.abs(logit).max() > bound - 2 * abs(model["intercept"][0]) - 1e-9
    # the model is the configuration's, whatever --seed is
    other = reference.make_case(config, {"rows": 4}, 1, 7)
    assert np.array_equal(other["model"]["coef"], model["coef"])
    assert not np.array_equal(other["inputs"][0]["x"][:4], case["inputs"][0]["x"][:4])


def test_the_program_is_inside_the_limits_and_the_control_outside(config, case):
    comp = computation.build(
        pm, config, case, eval_loop.fixed_dtype(pm, config)
    )
    runtime = LocalMooseRuntime(list(config["parties"]))

    def sigmoids(form):
        return metrics.REGISTRY.value(
            "moose_tpu_elementwise_flat_total", fn="sigmoid", form=form
        )

    before = sigmoids("flat"), sigmoids("as_is")
    answers = []
    for arguments in case["inputs"]:
        (out,) = runtime.evaluate_computation(comp, arguments=arguments).values()
        answers.append(np.asarray(out))
    # the head's sigmoid ran on one column, rank 1: taken as it came, so
    # the helper emitted nothing (``spmd_math._flat_view``)
    assert sigmoids("flat") == before[0] and sigmoids("as_is") > before[1]
    root = telemetry.recent_roots("evaluate_computation")[-1]
    assert telemetry.find_attr(root, "flat_lanes") == 0
    assert answers[0].shape == (ROWS, config["shapes"]["classes"])
    np.testing.assert_allclose(answers[0].sum(axis=1), 1.0, atol=1e-6)
    good = _check(config, case, answers)
    assert good["correct"] and good["failed"] == 0, good["numbers"]
    assert set(good["numbers"]) == set(config["limits"])

    control = _check(config, case, [
        reference.degraded(config, case, i) for i in range(len(case["inputs"]))
    ])
    assert not control["correct"]
    assert control["failed"] == len(case["inputs"])


def test_the_layout_is_the_onnx_classifiers(config, case):
    want = reference.expected(config, case, 0)
    logit = (
        case["inputs"][0]["x"] @ case["model"]["coef"][0]
        + case["model"]["intercept"][0]
    )
    np.testing.assert_allclose(want[:, 1], 1 / (1 + np.exp(-logit)), rtol=1e-15)
    np.testing.assert_allclose(want.sum(axis=1), 1.0, rtol=1e-12)
    numbers = reference.numbers(config, case, 0, want + 1e-9, want)
    assert numbers["max_abs_err"] == pytest.approx(1e-9, rel=1e-3)
    assert numbers["rms_err"] == pytest.approx(1e-9, rel=1e-3)
