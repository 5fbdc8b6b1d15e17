"""The deployment the benchmark's ``mlp-score-batch`` cell runs, tied to
its plain reference at a small size (64 rows) on the CPU: the computation as
``chipbench/computations/mlp_onnx.py`` builds it (the seeded 784-128-128-10
ReLU ``MLPClassifier`` -> ONNX -> ``from_onnx`` -> ``predictor_factory()``)
through ``LocalMooseRuntime``, against ``chipbench/reference/mlp_onnx.py``,
by the configuration's own limits; and the control, the same reference at
``fixed(14, 23)``, outside them.  One computation, one runtime and one
compile for the module (about 90 s the first time, the rest 0.1 s each).
"""

import json
import os
import types

import numpy as np
import pytest

import moose_tpu as pm
from chipbench.computations import mlp_onnx as computation
from chipbench.drivers import eval_loop
from chipbench.reference import mlp_onnx as reference
from moose_tpu import metrics, telemetry
from moose_tpu.edsl import tracer
from moose_tpu.runtime import LocalMooseRuntime

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROWS = 64  # 640 lanes under the softmax: 128 divides them, as the cell's 61,440


@pytest.fixture(scope="module")
def config():
    path = os.path.join(ROOT, "chipbench", "configs", "mlp-onnx-r128.json")
    with open(path) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def case(config):
    return reference.make_case(config, {"rows": ROWS}, 2, 2147483659)


@pytest.fixture(scope="module")
def scored(config, case):
    """The cell's computation evaluated once per block of the case: the
    answers, the runtime's plan and the first call's ``trace`` span."""
    comp = computation.build(
        pm, config, case, eval_loop.fixed_dtype(pm, config)
    )
    # one jitted program, as the cell runs it (the suite's default is eager)
    runtime = LocalMooseRuntime(list(config["parties"]), use_jit=True)
    before = _flat_view_counts()
    answers = []
    for arguments in case["inputs"]:
        (out,) = runtime.evaluate_computation(comp, arguments=arguments).values()
        answers.append(np.asarray(out))
        if len(answers) == 1:
            root = telemetry.recent_roots("evaluate_computation")[-1]
            (first,) = [s for s in root.children if s.name == "trace"]
    return types.SimpleNamespace(
        comp=comp, answers=answers, plan=dict(runtime.last_plan), trace=first,
        first_call=root,
        flat_views={
            k: v - before[k] for k, v in _flat_view_counts().items()
        },
    )


def _flat_view_counts() -> dict:
    return {
        (fn, form): metrics.REGISTRY.value(
            "moose_tpu_elementwise_flat_total", fn=fn, form=form
        )
        for fn in ("softmax", "exp", "pow2") for form in ("flat", "as_is")
    }


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
    widths = [shapes["features"], *shapes["hidden"], shapes["classes"]]
    assert widths == [784, 128, 128, 10] and config["reduced"] == []
    assert [w.shape for w in model["weights"]] == list(zip(widths, widths[1:]))
    assert [b.shape for b in model["biases"]] == [(n,) for n in widths[1:]]
    counted = reference.counts(config)
    assert counted["parameters"] == shapes["parameters"] == 118282
    # what an ONNX file carries: float32 weights and biases
    for a in model["weights"] + model["biases"]:
        assert a.dtype == np.float64
        assert np.array_equal(a, a.astype(np.float32))
    most = config["inputs"]["logit_spread_max"]
    assert most == 12.0 < reference.clamp_edge(config["fixed"])
    assert reference.clamp_edge(config["fixed"]) == pytest.approx(15.94, abs=5e-3)
    for arguments in case["inputs"]:
        x = arguments["x"]
        assert x.shape == (ROWS, 784) and x.dtype == np.float64
        assert 0.0 <= x.min() and x.max() < 1.0
        z = reference.logits(model, x)
        assert reference.spread(z).max() <= most
        # both signs reach the relus
        assert (z < 0).any() and (z > 0).any()
    # the model is the configuration's, whatever --seed is
    other = reference.make_case(config, {"rows": 4}, 1, 7)
    assert other["model"] is model
    assert not np.array_equal(other["inputs"][0]["x"], case["inputs"][0]["x"][:4])


def test_a_row_past_the_clamp_is_caught_by_the_reference(config, case):
    model = case["model"]
    wide = {"inputs": [{"x": case["inputs"][0]["x"].copy()}], "model": dict(model)}
    # the same rows through a head whose first class is pushed far down
    wide["model"]["biases"] = model["biases"][:2] + [
        model["biases"][2] - 20.0 * np.eye(10)[0]
    ]
    with pytest.raises(AssertionError, match="too near the softmax's clamp"):
        reference.expected(config, wide, 0)
    # and what the clamp does there: exactly 0, left out of the sum
    z = reference.logits(wide["model"], wide["inputs"][0]["x"])
    p = reference.softmax(z, reference.clamp_edge(config["fixed"]))
    under = z.max(axis=1) - z[:, 0] > reference.clamp_edge(config["fixed"])
    assert under.any() and (p[under, 0] == 0.0).all()
    np.testing.assert_allclose(p.sum(axis=1), 1.0, atol=1e-12)


def test_the_reference_is_sklearns_network_in_float32_carriage(config, case):
    """The plain equations against sklearn's own ``predict_proba`` on a
    network fitted the same way: they differ by the float32 rounding of
    the weights."""
    from sklearn.neural_network import MLPClassifier

    rng = np.random.default_rng(3)
    x = rng.random(size=(96, 12))
    y = rng.integers(0, 3, size=96)
    sk = MLPClassifier(
        hidden_layer_sizes=(8, 8), activation="relu", max_iter=30,
        random_state=0,
    ).fit(x, y)
    model = {
        "weights": [reference._as_onnx_carries(w) for w in sk.coefs_],
        "biases": [reference._as_onnx_carries(b) for b in sk.intercepts_],
    }
    z = reference.logits(model, x[:16])
    np.testing.assert_allclose(
        reference.softmax(z, reference.clamp_edge(config["fixed"])),
        sk.predict_proba(x[:16]), atol=1e-6,
    )
    want = reference.expected(config, case, 0)
    assert want.shape == (ROWS, 10)
    numbers = reference.numbers(config, case, 0, want + 1e-9, want)
    assert numbers["max_abs_err"] == pytest.approx(1e-9, rel=1e-3)
    assert numbers["rms_err"] == pytest.approx(1e-9, rel=1e-3)


def test_the_onnx_bytes_import_as_the_classifier(
    config, case, scored, monkeypatch
):
    traced = tracer.trace(scored.comp)
    kinds = [op.kind for op in traced.operations.values()]
    assert kinds.count("Dot") == 3 and kinds.count("Relu") == 2
    assert kinds.count("Softmax") == 1
    dense = [
        op.kind for op in traced.operations.values()
        if op.attributes.get("scope") == "dense"
    ]
    assert dense == ["Dot", "Add"] * 3
    assert scored.plan["ops"] == len(traced.operations)
    # a file without the classifier's ZipMap imports as the regressor,
    # which has no softmax head: the cell fails by name
    from moose_tpu.predictors import sklearn_export

    as_written = sklearn_export.mlp_onnx
    monkeypatch.setattr(
        sklearn_export, "mlp_onnx",
        lambda fitted, n, classifier: as_written(fitted, n, classifier=False),
    )
    with pytest.raises(SystemExit, match="MLPRegressor, not MLPClassifier"):
        computation.build(pm, config, case, eval_loop.fixed_dtype(pm, config))


def test_the_trace_span_names_the_network(scored):
    assert scored.trace.attrs["dense_layers"] == 3
    assert scored.trace.attrs["dense_widths"] == [784, 128, 128, 10]
    assert scored.trace.attrs["classes"] == 10


def test_the_softmaxs_middle_ran_on_the_flat_view(scored):
    """The elementwise middle of the softmax was traced at rank 1, rows x
    classes lanes long, and the exponential inside it took its operand as
    it came (``spmd_math._flat_view``); the attribute is on the span open
    while the plan is traced, as ``bank_draw_mb`` is."""
    assert telemetry.find_attr(scored.first_call, "flat_lanes") == 10 * ROWS
    assert scored.flat_views == {
        ("softmax", "flat"): 1, ("softmax", "as_is"): 0,
        ("exp", "flat"): 0, ("exp", "as_is"): 1,
        ("pow2", "flat"): 0, ("pow2", "as_is"): 1,
    }


def test_the_program_is_inside_the_limits_and_the_control_outside(
    config, case, scored
):
    assert scored.answers[0].shape == (ROWS, 10)
    plan = scored.plan
    assert plan["layout"] == "stacked" and plan["plan_mode"] == "whole-graph"
    assert plan["pinned_ops"] == [] and not plan.get("run_errors")
    good = _check(config, case, scored.answers)
    assert good["correct"] and good["failed"] == 0, good["numbers"]
    assert set(good["numbers"]) == set(config["limits"])
    np.testing.assert_allclose(scored.answers[0].sum(axis=1), 1.0, atol=1e-9)

    control = _check(config, case, [
        reference.degraded(config, case, i) for i in range(len(case["inputs"]))
    ])
    assert not control["correct"]
    assert control["failed"] == len(case["inputs"])
    for name, number in control["numbers"].items():
        assert number["value"] > number["limit"], name


def test_a_program_whose_multiply_kernel_mispairs_lanes_fails_by_name(
    config, case, monkeypatch
):
    """The parent's ``cross_terms_mul`` took its shape from x and walked
    flat lanes: rows x 1 times rows x 10 came back rows x 1."""
    from moose_tpu.native import ring128_kernels as rk

    assert computation._mul_kernel_broadcasts()
    as_fixed = rk.cross_terms_mul

    def parents(x0, x1, y0, y1, width):
        lo, hi = as_fixed(x0, x1, y0, y1, width)
        return lo[..., :1], hi[..., :1]

    monkeypatch.setattr(rk, "cross_terms_mul", parents)
    assert not computation._mul_kernel_broadcasts()
    with pytest.raises(SystemExit, match="operands that broadcast"):
        computation.build(pm, config, case, None)

