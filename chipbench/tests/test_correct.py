"""What ``correct`` rests on, at a size a test run can hold.

1. The plain reference in the program's place passes; the control (the
   same reference at the next precision below the configuration's) comes
   out as not correct.  NumPy only, at the mix's ``control_test_size``
   (the dot's numbers are in units that hold at every size).
2. The rest of a run with the timed path broken underneath: the
   harness's look for a chip skipped (``--rehearse``), one answer altered
   where it is produced, and ``correct`` comes out false.  The one fault
   of the builder's list that a one-chip evaluation loop can have: it has
   no state to leave unchanged, no batch mean and no exchange between
   chips.
"""

import json
import types

import numpy as np
import pytest

from chipbench import files, run
from chipbench.drivers import eval_loop

CELLS = ["dot-2048"]


def _state(cell, seed=7):
    ns = run.read_cell(cell)
    ctx = types.SimpleNamespace(
        config=ns.config, traffic=ns.traffic, seed=seed,
        size=ns.traffic["control_test_size"],
    )
    state = eval_loop.State(ctx, eval_loop.make_case(ctx), None, None)
    reference = files.load_module("reference", ns.config["reference"])
    return state, reference


def _window_of(state, produce):
    rec = eval_loop.Window()
    for n in range(len(state.case["inputs"])):
        rec.starts.append(0.0)
        rec.ends.append(1.0)
        rec.kept.append((n, produce(state.ctx.config, state.case, n)))
    return rec


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("seed", [7, 2147483659, 3000000019])
def test_reference_passes_and_control_fails(cell, seed):
    state, reference = _state(cell, seed)
    good = eval_loop.check(state, _window_of(state, reference.expected))
    assert good["correct"] and good["failed"] == 0
    control = eval_loop.check(state, _window_of(state, reference.degraded))
    assert not control["correct"]
    assert control["failed"] == len(state.case["inputs"])
    for name, number in control["numbers"].items():
        # every number of these cells separates its two readings
        assert number["value"] > number["limit"], name


@pytest.mark.parametrize("cell", CELLS)
def test_an_altered_answer_makes_the_run_incorrect(cell, monkeypatch, capsys):
    from moose_tpu.runtime import LocalMooseRuntime

    real = LocalMooseRuntime.evaluate_computation
    calls = {"n": 0}

    def altered(self, computation, arguments=None, compiler_passes=None):
        out = real(self, computation, arguments, compiler_passes)
        calls["n"] += 1
        if calls["n"] == 5:  # one evaluation inside the window
            (name, value), = out.items()
            value = np.array(value, copy=True)
            value.flat[0] += 1e-3
            out = {name: value}
        return out

    monkeypatch.setattr(LocalMooseRuntime, "evaluate_computation", altered)
    code = run.main([
        "--workload", cell, "--seed", "11", "--seconds", "600",
        "--trace", "1", "--rehearse",  # a traced window is 8 evaluations
    ])
    assert code == 0
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert last["correct"] is False
    assert last["failed"] == 1
    assert last["attempted"] == 8
