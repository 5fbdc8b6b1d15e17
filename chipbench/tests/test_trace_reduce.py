"""The trace reduction on one small recorded trace and on made-up ones.

``recorded_trace.json.gz`` is two evaluations of ``dot-2048`` on one TPU
v5e (my chip run, PR 25, call 1), written by ``run.py --keep-plain`` and
cut down: evaluations 2 and 3 of the traced window with the ``keep`` span
between them, times moved to start near 0, and each op's operand list
replaced by ``...`` (its name, result shape, opcode, fusion kind and
custom-call target are as recorded).  The device's own ``XLA Modules``
line, which the reduction does not read, is the independent witness: one
event per evaluation, 144.35 and 144.34 ms.
"""

import gzip
import json
import os

import pytest

from chipbench import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))

# whole event names as the v5e's op line gives them
INSTRUCTIONS = [
    "%fusion.3660 = (u32[4]{0:T(128)}, u32[4]{0:T(128)}, u32[4]{0:T(128)}, u32[4]{0:T(128)}, u32[4]{0:T(128)}, /*index=5*/u32[4]{0:T(128)S(1)}, u32[4]{0:T(128)S(1)}, u32[4]{0:T(128)S(1)}) fusion(u32[4]{0:T(128)} %master_key.1, u32[4]{0:T(128)} %constant.5913, u32[4]{0:T(128)} %constant.5795, u32[4]{0:T(128)} %constant.5677, u32[4]{0:T(128)} %constant.5559, u32[4]{0:T(128)} %constant.5435, u32[4]{0:T(128)} %constant.1726, u32[4]{0:T(128)} %constant.224, u32[4]{0:T(128)} %constant.185), kind=kLoop, calls=%fused_computation.5575",
    "%copy-start.348 = (u32[2048]{0:T(1024)}, u32[2048]{0:T(1024)S(1)}, u32[]{:S(2)}) copy-start(u32[2048]{0:T(1024)S(1)} %fusion.3554)",
    "%custom-call.2 = f32[2048,2048]{1,0:T(8,128)S(1)} custom-call(f64[2048,2048]{1,0:T(8,128)} %dyn__y__.1), custom_call_target=\"X64SplitHigh\"",
    "%core.1 = u32[3,4,32768,128]{3,2,1,0:T(8,128)} custom-call(u32[4,32768,128]{2,1,0:T(8,128)} %pad_add_fusion, u32[4,32768,128]{2,1,0:T(8,128)} %pad_add_fusion.1, u32[4,32768,128]{2,1,0:T(8,128)} %pad_add_fusion.2, u32[4,32768,128]{2,1,0:T(8,128)} %pad_add_fusion.3, u32[4,32768,128]{2,1,0:T(8,128)} %pad_add_fusion.4, u32[4,32768,128]{2,1,0:T(8,128)} %pad_add_fusion.5, u32[4,32768,128]{2,1,0:T(8,128)} %pad_add_fusion.6), custom_call_target=\"tpu_custom_call\", operand_layout_constraints={u32[4,32768,128]{2,1,0}, u32[4,32768,128]{2,1,0}, u32[4,32768,128]{2,1,0}, u32[4,32768,128]{2,1,0}, u32[4,32768,128]{2,1,0}, u32[4,32768,128]{2,1,0}, u32[4,32768,128]{2,1,0}}, frontend_attributes={kernel_metadata={}}",
    "%fusion.3354 = u32[3,2048,2048]{2,1,0:T(8,128)} fusion(s8[3,2048,2048]{2,1,0:T(8,128)(4,1)S(1)} %get-tuple-element.124, s8[3,2048,2048]{2,1,0:T(8,128)(4,1)} %get-tuple-element.75, s32[3,2048]{1,0:T(4,128)S(1)} %copy-done.364, s32[3,2048]{1,0:T(4,128)S(1)} %get-tuple-element.123), kind=kOutput, calls=%fused_computation.4733",
]


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(os.path.join(HERE, "recorded_trace.json.gz")) as f:
        return json.load(f)


def test_parse_op_on_whole_instructions():
    parsed = {op["name"]: op for op in map(tr.parse_op, INSTRUCTIONS)}
    assert parsed["%fusion.3660"]["opcode"] == "fusion"  # a tuple shape
    assert parsed["%fusion.3660"]["kind"] == "kLoop"
    assert parsed["%fusion.3354"]["kind"] == "kOutput"
    assert parsed["%copy-start.348"]["opcode"] == "copy-start"
    assert parsed["%custom-call.2"]["target"] == "X64SplitHigh"
    assert parsed["%core.1"]["target"] == "tpu_custom_call"
    groups = {name: tr.classify(op) for name, op in parsed.items()}
    assert groups == {
        "%fusion.3660": "xla_rest", "%fusion.3354": "mxu",
        "%copy-start.348": "xla_rest", "%custom-call.2": "xla_rest",
        "%core.1": "pallas",
    }
    assert tr.label(parsed["%fusion.3354"]) == "%fusion* fusion kOutput"
    assert tr.parse_op("not an instruction")["opcode"] == "not an instruction"


def test_union_and_self_times():
    assert tr._union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [[0, 3], [5, 8]]
    assert tr._covered([[0, 3], [5, 8]], 2, 6) == 2
    # a loop of 10 that encloses two ops of 3 counts 4 of its own
    events = [["%while", 0.0, 10.0], ["%a", 1.0, 3.0], ["%b", 5.0, 3.0]]
    assert tr._self_times(events) == {"%while": 4.0, "%a": 3.0, "%b": 3.0}


def _plain(ops, spans):
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": ops}]},
        {"name": "/host:CPU", "lines": [{"name": "python3", "events": spans}]},
    ]}


def test_reduce_on_a_made_up_trace():
    conv = "%fusion.1 = s32[8]{0} fusion(s32[8]{0} %p), kind=kOutput, calls=%c"
    loop = "%fusion.2 = s32[8]{0} fusion(s32[8]{0} %p), kind=kLoop, calls=%d"
    core = ('%core = u32[8]{0} custom-call(u32[8]{0} %p), '
            'custom_call_target="tpu_custom_call"')
    reduced = tr.reduce(_plain(
        # busy 100..400 and 500..600 in the first evaluation, 1100..1300
        # in the second; an op before the window is clipped away
        [[loop, 0.0, 50.0], [conv, 100.0, 300.0], [loop, 500.0, 100.0],
         [core, 1100.0, 200.0]],
        [["chipbench.evaluate", 60.0, 740.0], ["chipbench.keep", 800.0, 100.0],
         ["chipbench.evaluate", 1000.0, 1000.0]],
    ))
    ns = 1e-9
    assert reduced["window_s"] == pytest.approx(1940 * ns)
    assert reduced["busy_s"] == pytest.approx(600 * ns)
    assert reduced["group_s"] == pytest.approx(
        {"mxu": 300 * ns, "pallas": 200 * ns, "xla_rest": 100 * ns}
    )
    first, second = reduced["evaluations"]
    assert first["busy_s"] == pytest.approx(400 * ns)
    assert first["first_op_after_s"] == pytest.approx(40 * ns)
    assert first["last_op_before_end_s"] == pytest.approx(200 * ns)
    assert second["busy_s"] == pytest.approx(200 * ns)
    gaps = dict(reduced["idle_gaps"])
    assert gaps["evaluate:before_first_op"] == pytest.approx(140 * ns)
    assert gaps["evaluate:between_ops"] == pytest.approx(100 * ns)
    assert gaps["evaluate:after_last_op"] == pytest.approx(900 * ns)
    assert gaps["keep"] == pytest.approx(100 * ns)
    assert gaps["between_spans"] == pytest.approx(100 * ns)
    assert sum(gaps.values()) == pytest.approx(
        reduced["window_s"] - reduced["busy_s"]
    )


def test_a_trace_without_spans_or_device_is_an_error():
    with pytest.raises(ValueError, match="chipbench.evaluate"):
        tr.reduce(_plain([["%a", 0.0, 1.0]], []))
    with pytest.raises(ValueError, match="no device plane"):
        tr.reduce({"planes": [{"name": "/host:CPU", "lines": [
            {"name": "python3", "events": [["chipbench.evaluate", 0.0, 9.0]]}
        ]}]})


def test_recorded_trace_busy_union_matches_the_device_module_line(recorded):
    reduced = tr.reduce(recorded)
    device = [p for p in recorded["planes"] if p["name"] == "/device:TPU:0"][0]
    modules = [l for l in device["lines"] if l["name"] == "XLA Modules"][0]
    assert len(modules["events"]) == 2 == len(reduced["evaluations"])
    module_s = sum(d for _, _, d in modules["events"]) / 1e9
    assert reduced["busy_s"] == pytest.approx(module_s, rel=2e-3)
    for evaluation, (_, _, d) in zip(reduced["evaluations"], modules["events"]):
        assert evaluation["busy_s"] == pytest.approx(d / 1e9, rel=2e-3)
        assert evaluation["span_s"] > evaluation["busy_s"]


def test_recorded_trace_idle_share_and_groups(recorded):
    reduced = tr.reduce(recorded)
    assert reduced["chips"] == 1
    # the groups are self times, so they add up to the busy time
    assert sum(reduced["group_s"].values()) == pytest.approx(
        reduced["busy_s"], rel=1e-6
    )
    idle = 1 - reduced["busy_s"] / reduced["window_s"]
    assert idle == pytest.approx(0.498, abs=0.002)  # these two evaluations
    assert sum(s for _, s in reduced["idle_gaps"]) == pytest.approx(
        reduced["window_s"] - reduced["busy_s"], rel=1e-6
    )
    # dot-2048: 272 limb convolutions an evaluation, 40% of the device's
    # time; one Mosaic kernel (trunc_combine), 1.5%
    ops = [l for p in recorded["planes"] if p["name"] == "/device:TPU:0"
           for l in p["lines"] if l["name"] == "XLA Ops"][0]["events"]
    groups = [tr.classify(tr.parse_op(name)) for name, _, _ in ops]
    assert groups.count("mxu") == 2 * 272
    assert groups.count("pallas") == 2 * 1
    assert reduced["group_s"]["mxu"] / reduced["busy_s"] == pytest.approx(0.40, abs=0.01)
    assert reduced["device_ops"][0][0] == "%fusion* fusion kOutput"
