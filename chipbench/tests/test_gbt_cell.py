"""The ``gbt-score-batch`` cell: its files found by name, its last line
under ``--rehearse`` with and without a trace, its three readers, and the
``work`` block against the fitted forest counted by hand.  By hand, as
the other files here: ``JAX_PLATFORMS=cpu python -m pytest
chipbench/tests/test_gbt_cell.py`` (each rehearsal fits the forest, 11 s,
and compiles the ``msb`` kernel in interpret mode for the CPU: about 80 s
the first time)."""

import json
import os
import types

import pytest

from chipbench import files, run, work
from chipbench.layer_metrics import (
    kernel_fallbacks,
    plan_ops,
    secure_forest_roofline,
)

CELL = "gbt-score-batch"
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
        # from the program report
        assert set(last["metrics"]) == {
            "pinned_ops", "compiles_in_window", "plan_ops", "kernel_fallbacks",
        }
        assert last["metrics"]["kernel_fallbacks"] == {"value": 0, "unit": "count"}
        assert last["metrics"]["plan_ops"]["value"] < 60
    assert set(last["compared"]) == {"max_abs_err", "rms_err"}
    for number in last["compared"].values():
        assert number["value"] <= number["limit"]


def test_the_cell_is_one_chip_and_its_files_are_found():
    ns = run.read_cell(CELL)
    assert ns.cell["chips"] == 1 and ns.cell["config"] == "gbt-onnx-r128"
    assert ns.cell["traffic"] == "closed1-rows-forest"
    assert ns.traffic["size"]["rows"] % 64 == 0
    assert ns.traffic["rehearse_size"] == {"rows": 8}
    assert ns.config["reduced"] == [] and ns.config["fixed"] == [24, 40]
    for kind in ("driver", "computation", "reference"):
        plural = kind + "s" if kind != "reference" else kind
        files.load_module(plural, ns.config[kind])
    mine = {
        m["name"] for m in ns.bench["per_layer"] if CELL in m["workloads"]
    }
    assert {"secure_forest_roofline", "plan_ops", "kernel_fallbacks"} <= mine
    assert not {"secure_dot_roofline", "secure_sigmoid_roofline"} & mine


def test_work_counts_match_the_forest_counted_by_hand():
    ns = run.read_cell(CELL)
    reference = files.load_module("reference", ns.config["reference"])
    counted = reference.counts(reference._model(ns.config))
    block, shapes = ns.config["work"], ns.config["shapes"]
    nodes, two_leaf = counted["nodes"], counted["two_leaf_nodes"]
    assert (shapes["nodes"], shapes["two_leaf_nodes"]) == (nodes, two_leaf)
    # a comparison: 16 AND banks of 128 planes; b2a: 2 multiplications
    # a node; the mux: one more where a child is an inner node
    assert block["and_gates_per_row"] == nodes * 16 * 128
    assert block["secure_mul_per_row"] == 2 * nodes + (nodes - two_leaf)
    words = block["secure_mul_per_row"] * 18 + block["and_gates_per_row"] * 18 // 128
    assert block["elementwise_ring_passes"] == words
    size = ns.traffic["size"]
    rows = size["rows"]
    assert work.dot_shape(ns.config, size) == (rows, 0, 1)  # no matmul
    assert work.ring_ops(ns.config, size) == rows * 6 * (
        block["secure_mul_per_row"] * 272 + block["and_gates_per_row"]
    )
    least, bound = work.least_seconds(ns.config, size, "TPU v5 lite")
    assert bound == "hbm"
    assert least == pytest.approx(rows * (words + 6) * 16 / 819e9, rel=1e-3)


def test_the_readers_read_the_plan_the_counters_and_the_trace():
    ns = run.read_cell(CELL)
    view = types.SimpleNamespace(
        plan={"ops": 50}, counters={"pallas_fallback_total": {}},
        config=ns.config, size=ns.traffic["size"], device_kind="TPU v5 lite",
        trace=None,
    )
    assert plan_ops.read(view) == 50
    assert kernel_fallbacks.read(view) == 0
    assert secure_forest_roofline.read(view) is None
    view.counters = {"pallas_fallback_total": {
        "kernel=msb,reason=shape": 3, "kernel=horner,reason=error": 1,
    }}
    assert kernel_fallbacks.read(view) == 4
    least, _ = work.least_seconds(ns.config, view.size, "TPU v5 lite")
    view.trace = {"busy_s": 8 * 10 * least, "evaluations": [None] * 8}
    assert secure_forest_roofline.read(view) == pytest.approx(10.0)
    # a program that publishes neither (the parent's): silent, no error
    parent = types.SimpleNamespace(plan={"plan_state": "jit"}, counters={})
    assert plan_ops.read(parent) is None
    assert kernel_fallbacks.read(parent) is None
