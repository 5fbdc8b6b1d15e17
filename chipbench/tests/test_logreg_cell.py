"""The ``logreg-score-64k`` cell: its last line under ``--rehearse`` with
and without a trace, its two readers, and what ``correct`` rests on at
the cell's own size (NumPy only).  By hand, as the other files here:
``JAX_PLATFORMS=cpu python -m pytest chipbench/tests/test_logreg_cell.py``
(the first run compiles the ``fixed(24, 40)`` sigmoid with its kernels in
interpret mode for the CPU: about five minutes; then about one)."""

import json
import os
import types

import pytest

from chipbench import files, run, work
from chipbench.drivers import eval_loop
from chipbench.layer_metrics import ladder_validations, secure_sigmoid_roofline
from chipbench.tests.test_correct import _window_of

CELL = "logreg-score-64k"
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


@pytest.mark.parametrize("trace", [0, 1])
def test_last_line_under_rehearse(trace, capsys):
    code = run.main([
        "--workload", CELL, "--seed", "2147483659", "--seconds", "2",
        "--trace", str(trace), "--rehearse",
    ])
    assert code == 0
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert RESULT_KEYS <= set(last)
    assert last["rehearsal"] is True  # never a device number
    assert last["correct"] is True and last["failed"] == 0
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    if trace == 0:
        assert set(last["metrics"]) == {m["name"] for m in bench["end_to_end"]}
    else:
        # the CPU's trace has no device plane: the readers that count
        # from the program report; on the CPU the plan is not gated, so
        # no validating evaluation ran
        assert set(last["metrics"]) == {
            "pinned_ops", "compiles_in_window", "ladder_validations",
        }
        assert last["metrics"]["ladder_validations"] == {
            "value": 0, "unit": "evals",
        }
    assert set(last["compared"]) == {"max_abs_err", "rms_err"}
    for number in last["compared"].values():
        assert number["value"] <= number["limit"]


def test_the_cell_is_one_chip_and_lists_its_readers():
    ns = run.read_cell(CELL)
    assert ns.cell["chips"] == 1 and ns.cell["config"] == "logreg-onnx-r128"
    assert ns.traffic["size"] == {"rows": 65536}
    mine = {
        m["name"] for m in ns.bench["per_layer"] if CELL in m["workloads"]
    }
    assert {"secure_sigmoid_roofline", "ladder_validations"} <= mine
    assert "secure_dot_roofline" not in mine and len(mine) == 17


def test_ladder_validations_reads_the_plan_and_is_silent_on_a_parent():
    view = types.SimpleNamespace(plan={"validations_run": 2})
    assert ladder_validations.read(view) == 2
    view = types.SimpleNamespace(plan={"plan_state": "jit", "pinned_ops": []})
    assert ladder_validations.read(view) is None  # a program without it


def test_secure_sigmoid_roofline_is_bytes_over_busy_time():
    ns = run.read_cell(CELL)
    size = ns.traffic["size"]
    assert work.ring_ops(ns.config, size) == pytest.approx(4.34e10, rel=0.01)
    least, bound = work.least_seconds(ns.config, size, "TPU v5 lite")
    assert bound == "hbm" and least == pytest.approx(6.12e-3, rel=0.01)
    view = types.SimpleNamespace(
        config=ns.config, size=size, device_kind="TPU v5 lite",
        trace={"busy_s": 8 * 0.205, "evaluations": [None] * 8},
    )
    assert secure_sigmoid_roofline.read(view) == pytest.approx(2.98, rel=0.01)
    view.trace = None
    assert secure_sigmoid_roofline.read(view) is None


@pytest.mark.parametrize("seed", [7, 2147483659, 3000000019])
def test_reference_passes_and_control_fails_each_limit_at_the_cells_size(seed):
    ns = run.read_cell(CELL)
    ctx = types.SimpleNamespace(
        config=ns.config, traffic=ns.traffic, seed=seed, size=ns.traffic["size"],
    )
    state = eval_loop.State(ctx, eval_loop.make_case(ctx), None, None)
    reference = files.load_module("reference", ns.config["reference"])

    good = eval_loop.check(state, _window_of(state, reference.expected))
    assert good["correct"] and good["failed"] == 0
    control = eval_loop.check(state, _window_of(state, reference.degraded))
    assert not control["correct"]
    assert control["failed"] == len(state.case["inputs"])
    for name, number in control["numbers"].items():
        assert number["value"] > number["limit"], name
