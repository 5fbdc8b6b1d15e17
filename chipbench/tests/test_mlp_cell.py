"""The ``mlp-score-batch`` cell: its files found by name, its last line
under ``--rehearse`` with and without a trace, its two readers, and the
``work`` block against the network counted by hand.  By hand, as the
other files here: ``JAX_PLATFORMS=cpu python -m pytest
chipbench/tests/test_mlp_cell.py`` (each rehearsal fits the network, 3 s,
and compiles the fixed(24, 40) network with interpret-mode kernels for
the CPU at 8 rows: 13 minutes the first time here, 4 the second, which
finds that program in the compile cache)."""

import json
import os
import types

import pytest

from chipbench import files, run, work
from chipbench.layer_metrics import mlp_kernel_fallbacks, secure_mlp_roofline

CELL = "mlp-score-batch"
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
            "pinned_ops", "compiles_in_window", "mlp_kernel_fallbacks",
        }
        assert last["metrics"]["mlp_kernel_fallbacks"] == {
            "value": 0, "unit": "count",
        }
    assert set(last["compared"]) == {"max_abs_err", "rms_err"}
    for number in last["compared"].values():
        assert number["value"] <= number["limit"]


def test_the_cell_is_one_chip_and_its_files_are_found():
    ns = run.read_cell(CELL)
    assert ns.cell["chips"] == 1 and ns.cell["config"] == "mlp-onnx-r128"
    assert ns.cell["traffic"] == "closed1-rows-mlp"
    assert ns.traffic["size"]["rows"] % 2048 == 0
    assert ns.traffic["rehearse_size"] == {"rows": 8}
    assert ns.traffic["control_test_size"] == {"rows": 8}
    assert ns.traffic["distinct_inputs"] == 4 and ns.traffic["callers"] == 1
    config = ns.config
    assert config["reduced"] == [] and config["fixed"] == [24, 40]
    assert config["ring"] == 128 and config["prf"] == "threefry"
    assert len(config["source"]) <= 200
    shapes = config["shapes"]
    assert (shapes["features"], shapes["hidden"], shapes["classes"]) == (
        784, [128, 128], 10,
    )
    for kind in ("driver", "computation", "reference"):
        plural = kind + "s" if kind != "reference" else kind
        files.load_module(plural, config[kind])
    listed = {c["name"]: c for c in ns.bench["configs"]}["mlp-onnx-r128"]
    assert listed["source"] == config["source"] and listed["reduced"] == []
    mine = {
        m["name"] for m in ns.bench["per_layer"] if CELL in m["workloads"]
    }
    assert {"secure_mlp_roofline", "mlp_kernel_fallbacks", "eval_mfu"} <= mine
    assert not {
        "secure_dot_roofline", "secure_sigmoid_roofline",
        "secure_forest_roofline", "kernel_fallbacks", "plan_ops",
    } & mine
    # every key chipbench/README.md lists for a configuration
    assert {
        "source", "shapes", "fixed", "ring", "prf", "parties", "driver",
        "computation", "reference", "guarantees", "reduced", "assumed",
        "autotune_measurements", "control", "limits", "work",
    } <= set(config)
    # the program's own A/B row for each class its three dots fall in
    from moose_tpu.compilation import autotune

    rows = ns.traffic["size"]["rows"]
    classes = {
        autotune.dot_shape_class(rows, k, n)
        for k, n in ((784, 128), (128, 128), (128, 10))
    }
    assert {
        key.split("/")[2] for key in config["autotune_measurements"]
    } == classes == {"mxu", "tall"}


def test_work_counts_match_the_network_counted_by_hand():
    ns = run.read_cell(CELL)
    reference = files.load_module("reference", ns.config["reference"])
    counted = reference.counts(ns.config)
    block = ns.config["work"]
    assert counted["parameters"] == 118282
    assert counted["macs_per_row"] == [784 * 128, 128 * 128, 128 * 10]
    comparison = 16 * 128  # AND banks x bit planes of a ring128 adder
    lanes, classes = counted["relu_lanes_per_row"], 10
    assert lanes == 256
    tournament = 5 + 2 + 1 + 1  # 10 -> 5 -> 3 -> 2 -> 1
    exponential = 2 * 7 + 6 + 14 + 1  # b2a of 7 bits, their tree, Horner, product
    division = (2 * 64 + 2) + 1 + classes + 4 * (classes + 1) + classes
    softmax = 3 * tournament + (2 + 1 + 1) * classes + exponential * classes + division
    assert (exponential, division, softmax) == (35, 195, 612)
    assert block["secure_mul_per_row"] == sum(counted["macs_per_row"][1:]) + 3 * lanes + softmax
    adders = lanes + tournament + classes + classes + 1
    assert block["and_gates_per_row"] == adders * comparison + 6 * 64
    dots = 6 * (128 + 128) + 6 * (128 + 10)
    words = (3 * lanes + softmax) * 18 + block["and_gates_per_row"] * 18 // 128 + dots
    assert words == 109626
    assert block["elementwise_ring_passes"] * 128 == words
    size = ns.traffic["size"]
    rows = size["rows"]
    assert work.dot_shape(ns.config, size) == (rows, 784, 128)
    assert work.ring_ops(ns.config, size) == rows * 6 * (
        (784 * 128 + block["secure_mul_per_row"]) * 272
        + block["and_gates_per_row"]
    )
    least, bound = work.least_seconds(ns.config, size, "TPU v5 lite")
    assert bound == "hbm"
    matmul = 6 * (rows * 784 + 784 * 128 + rows * 128)
    assert least == pytest.approx((matmul + rows * words) * 16 / 819e9, rel=1e-9)


def test_the_readers_read_the_counters_and_the_trace():
    ns = run.read_cell(CELL)
    view = types.SimpleNamespace(
        plan={"ops": 25}, counters={"pallas_fallback_total": {}},
        config=ns.config, size=ns.traffic["size"], device_kind="TPU v5 lite",
        trace=None,
    )
    assert mlp_kernel_fallbacks.read(view) == 0
    assert secure_mlp_roofline.read(view) is None
    view.counters = {"pallas_fallback_total": {
        "kernel=msb,reason=shape": 3, "kernel=horner,reason=error": 1,
    }}
    assert mlp_kernel_fallbacks.read(view) == 4
    least, _ = work.least_seconds(ns.config, view.size, "TPU v5 lite")
    view.trace = {"busy_s": 8 * 10 * least, "evaluations": [None] * 8}
    assert secure_mlp_roofline.read(view) == pytest.approx(10.0)
    # a program that publishes no counter (a parent's): silent, no error
    parent = types.SimpleNamespace(plan={"plan_state": "jit"}, counters={})
    assert mlp_kernel_fallbacks.read(parent) is None
    for module in (mlp_kernel_fallbacks, secure_mlp_roofline):
        assert module.WORKLOADS == [CELL] and module.MOVES == "evals_per_s"
