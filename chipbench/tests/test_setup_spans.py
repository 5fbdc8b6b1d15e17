"""The rows that split ``setup_s`` (ISSUE 37): under ``--rehearse --trace
1`` every new reader reports, the rows add up to the set-up trees'
durations, each second is in one row, and a program whose spans carry
none of JAX's seconds (a parent commit) leaves every one of them out.
By hand, as the other files here (``logreg-score-64k``'s rehearsal
compiles the ``fixed(24, 40)`` sigmoid for the CPU: minutes the first
time)."""

import json
import types

import pytest

from chipbench import run, setup_spans

SECONDS = (
    "setup_jax_trace_s", "setup_lower_s", "setup_compile_s",
    "setup_kernel_checks_s", "setup_validate_s", "setup_unexplained_s",
)
NEW = SECONDS + ("setup_evals_s", "setup_cache_misses")


def _modules():
    return {m.NAME: m for m in run.layer_metric_modules()}


def _rehearse(cell, capsys, monkeypatch):
    """One traced rehearsal of ``cell``: its last line, its ``plan``
    line, and a view of the trees it left (what ``run.main`` gave its
    readers: nothing evaluates after the window).  The trees are the
    process's, and a test before this one may have left some: only
    those begun since count, as in a run of the benchmark all do."""
    import time

    from moose_tpu import telemetry

    t0, kept = time.perf_counter(), telemetry.recent_roots
    monkeypatch.setattr(
        telemetry, "recent_roots",
        lambda name=None: [r for r in kept(name) if r.start_s >= t0],
    )
    code = run.main([
        "--workload", cell, "--seed", "2147483659", "--seconds", "2",
        "--trace", "1", "--rehearse",
    ])
    assert code == 0
    lines = [json.loads(l) for l in capsys.readouterr().out.strip().splitlines()]
    by_phase = {l["phase"]: l for l in lines if "phase" in l}
    view = types.SimpleNamespace(
        cell={"name": cell}, evals=by_phase["window"]["evaluations"],
    )
    return lines[-1], by_phase["plan"], view


@pytest.mark.parametrize("cell", ["dot-2048", "logreg-score-64k"])
def test_every_reader_reports_and_the_rows_add_up(cell, capsys, monkeypatch):
    last, plan, view = _rehearse(cell, capsys, monkeypatch)
    modules = _modules()
    mine = {
        name for name in NEW
        if cell in (getattr(modules[name], "WORKLOADS", None) or [cell])
    }
    assert mine <= set(last["metrics"])
    assert ("setup_validate_s" in mine) == (cell != "dot-2048")
    got = {name: last["metrics"][name] for name in mine}
    for name, metric in got.items():
        assert metric["unit"] == ("count" if name == "setup_cache_misses" else "s")
        assert metric["value"] == modules[name].read(view), name
    # set-up's trees are the driver's evaluations, and nothing else
    roots = setup_spans.setup_roots(view)
    assert len(roots) == plan["setup_evals"]
    evals_s = got["setup_evals_s"]["value"]
    assert evals_s == pytest.approx(sum(r.duration_s for r in roots))
    assert evals_s <= sum(plan["setup_phases"]["evals_s"])
    assert evals_s > 0.9 * sum(plan["setup_phases"]["evals_s"])
    # every second in one row: the reported rows and the named spans
    rows = setup_spans.rows_s(view)
    assert sum(rows[r] for r in setup_spans.ROWS) == pytest.approx(evals_s)
    reported = sum(got[n]["value"] for n in SECONDS if n in got)
    assert reported + rows[setup_spans.NAMED] == pytest.approx(evals_s)
    # a first evaluation traces, lowers and compiles (or loads)
    for name in ("setup_jax_trace_s", "setup_lower_s", "setup_compile_s"):
        assert got[name]["value"] > 0, name
    assert got["setup_kernel_checks_s"]["value"] >= 0
    # and the spans account for it: what no row names is small
    assert abs(got["setup_unexplained_s"]["value"]) < max(1.0, 0.05 * evals_s)
    if "setup_validate_s" in got:  # on the CPU the plan is not gated
        assert got["setup_validate_s"]["value"] == 0


def _span(name, duration, children=(), **attrs):
    from moose_tpu import telemetry

    return telemetry.Span(
        name=name, start_s=0.0, end_s=duration, attrs=attrs,
        children=list(children),
    )


def test_each_second_goes_to_one_row(monkeypatch):
    """A constructed first tree of a cold ladder plan: every attribute
    and every remainder lands where ``setup_spans`` says."""
    from moose_tpu import telemetry

    check = _span(
        "pallas_selfcheck", 5.0, jax_trace_s=1.0, jax_lower_s=1.0,
        backend_compile_s=2.0, cache_misses=2,
    )
    key = _span("record_key", 50.0, [check], jax_trace_s=30.0, jax_lower_s=10.0)
    lookup = _span("plan_verdict", 51.0, [
        _span("candidate_build", 0.25), key, _span("verdict_read", 0.25),
    ], op="lookup")
    validate = _span("ladder_validate", 100.0, [
        _span("candidate_run", 40.0, backend_compile_s=38.0, cache_misses=1),
        _span("twin_run", 55.0, jax_trace_s=5.0, jax_lower_s=10.0,
              backend_compile_s=20.0),
        _span("compare", 1.0),
    ])
    store = _span("plan_verdict", 0.5, op="store")
    dispatch = _span("dispatch", 152.0, [lookup, validate, store])
    first = _span(setup_spans.ROOT, 160.0, [
        _span("trace", 1.0), _span("autotune", 1.0),
        _span("bind_arguments", 2.0, [_span("input_upload", 1.5)]),
        _span("execute", 155.0, [
            dispatch, _span("device_wait", 1.0), _span("host_transfer", 1.0),
        ], backend_compile_s=0.5),
    ])
    steady = _span(setup_spans.ROOT, 0.5, [_span("device_wait", 0.25)])
    window = _span(setup_spans.ROOT, 0.25)
    monkeypatch.setattr(
        telemetry, "recent_roots", lambda name=None: [first, steady, window]
    )
    view = types.SimpleNamespace(cell={"name": "mlp-score-batch"}, evals=1)
    rows = setup_spans.rows_s(view)
    assert rows == {
        "evals": 160.5,
        "jax_trace": 36.0, "lower": 21.0 + (50.0 - 5.0 - 40.0),
        "compile": 60.5, "cache_misses": 3,
        "kernel_checks": 1.0,
        "validate": (100.0 - 96.0) + 2.0 + 20.0 + 1.0,
        # trace, autotune, bind, wait, transfer, build, read, store,
        # and the steady evaluation's wait
        "named": 1 + 1 + 2 + 1 + 1 + 0.25 + 0.25 + 0.5 + 0.25,
        # the root 1, execute 0.5, dispatch 0.5, the lookup 0.5, and
        # the steady root's own 0.25
        "unexplained": 1 + 0.5 + 0.5 + 0.5 + 0.25,
    }
    assert sum(rows[r] for r in setup_spans.ROWS) == rows["evals"]
    modules = _modules()
    assert modules["setup_validate_s"].read(view) == rows["validate"]
    assert modules["setup_cache_misses"].read(view) == 3


def test_a_program_without_the_attributes_gives_nothing(monkeypatch):
    """A parent commit's trees: spans, no seconds of JAX's on any."""
    from moose_tpu import telemetry

    tree = _span(setup_spans.ROOT, 80.0, [
        _span("execute", 79.0, [_span("dispatch", 78.0, plan_state="jit")]),
    ])
    monkeypatch.setattr(telemetry, "recent_roots", lambda name=None: [tree, tree])
    view = types.SimpleNamespace(cell={"name": "dot-2048"}, evals=1)
    modules = _modules()
    for name in NEW:
        assert modules[name].read(view) is None, name
    # nor where the program keeps no trees at all (older still)
    monkeypatch.delattr(telemetry, "recent_roots")
    for name in NEW:
        assert modules[name].read(view) is None, name


def test_benchmark_json_lists_the_new_readers_as_they_describe_themselves():
    """``test_run``'s comparison, for the readers of this file: its own
    case fails on ``mxu_ms`` alone, before and after (PERF.md 7, 8)."""
    import os

    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = [w["name"] for w in bench["workloads"]]
    listed = [m for m in bench["per_layer"] if m["name"] in NEW]
    assert [m["name"] for m in bench["per_layer"]][-len(NEW):] == [
        m["name"] for m in listed
    ]  # appended, in the order PERF.md lists them
    modules = _modules()
    assert {m["name"] for m in listed} == set(NEW)
    for entry in listed:
        module = modules[entry["name"]]
        assert entry == {
            "name": module.NAME, "unit": module.UNIT, "better": module.BETTER,
            "source": module.SOURCE, "layer": module.LAYER,
            "moves": module.MOVES,
            "workloads": getattr(module, "WORKLOADS", None) or cells,
        }
        assert (module.SOURCE, module.MOVES) == ("program_span", "setup_s")
