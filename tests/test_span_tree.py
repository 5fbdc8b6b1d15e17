"""One span tree per evaluation (ISSUE 26): the names and shape of the
tree ``LocalMooseRuntime.evaluate_computation`` records, the last 64
trees kept process-wide, the same spans in a ``jax.profiler`` trace on
the profiler's clock, the two counters at the host/device boundary, and
the device half: ``moose/`` scopes in the lowered program."""

import contextlib
import glob
import os
import re
import time

import jax
import numpy as np
import pytest

import moose_tpu as pm
from moose_tpu import metrics, profiling, telemetry
from moose_tpu.runtime import LocalMooseRuntime

N = 96  # 96 x 96 float64 = 72 KiB: over the device cache's 64 KiB floor
PARTIES = ["alice", "bob", "carole"]


def _secure_dot():
    alice, bob, carole = (pm.host_placement(p) for p in PARTIES)
    rep = pm.replicated_placement("rep", players=[alice, bob, carole])
    fixed = pm.fixed128(14, 23)

    @pm.computation
    def secure_dot(
        x: pm.Argument(placement=alice, dtype=pm.float64),
        y: pm.Argument(placement=bob, dtype=pm.float64),
    ):
        with alice:
            xf = pm.cast(x, dtype=fixed)
        with bob:
            yf = pm.cast(y, dtype=fixed)
        with rep:
            z = pm.dot(xf, yf)
        with carole:
            return pm.cast(z, dtype=pm.float64)

    return secure_dot


def _inputs(seed=0):
    rng = np.random.default_rng(seed)
    return {"x": rng.normal(size=(N, N)), "y": rng.normal(size=(N, N))}


def _names(span):
    """The tree as nested (name, [children]) pairs."""
    return (span.name, [_names(c) for c in span.children])


def _count(span):
    return 1 + sum(_count(c) for c in span.children)


def _evaluate(runtime, comp, arguments):
    (out,) = runtime.evaluate_computation(comp, arguments=arguments).values()
    assert runtime.last_plan["layout"] == "stacked"
    return out, telemetry.last_trace()


@pytest.fixture(scope="module")
def trees():
    """First call, steady call, and a call after an in-place mutation
    of one argument; the plan jitted, as a deployment's is (the suite's
    default is eager, whose every evaluation traces its small programs
    again)."""
    runtime = LocalMooseRuntime(PARTIES, use_jit=True)
    comp, arguments = _secure_dot(), _inputs()
    _, first = _evaluate(runtime, comp, arguments)
    _, steady = _evaluate(runtime, comp, arguments)
    arguments["x"][0, 0] += 1.0
    out, mutated = _evaluate(runtime, comp, arguments)
    np.testing.assert_allclose(out, arguments["x"] @ arguments["y"], atol=1e-3)
    return {"first": first, "steady": steady, "mutated": mutated}


HASH, UPLOAD = ("input_fingerprint", []), ("input_upload", [])
DISPATCH, WAIT = ("dispatch", []), ("device_wait", [])
TRANSFER = ("host_transfer", [])
# a jitted plan's arguments are checked while the device runs it
EXECUTE = ("execute", [DISPATCH, HASH, HASH, WAIT, TRANSFER])


@pytest.mark.parametrize("which,want", [
    ("first", ("evaluate_computation", [
        ("trace", []), ("autotune", []), ("build_plan", []),
        ("bind_arguments", [UPLOAD, UPLOAD]), EXECUTE,
    ])),
    ("steady", ("evaluate_computation", [("bind_arguments", []), EXECUTE])),
    # the stale argument's run is taken back and dispatched again
    ("mutated", ("evaluate_computation", [("bind_arguments", []), (
        "execute", [DISPATCH, HASH, HASH, UPLOAD, DISPATCH, WAIT, TRANSFER],
    )])),
])
def test_tree_has_exactly_these_names(trees, which, want):
    assert _names(trees[which]) == want


def test_steady_evaluation_is_at_most_twelve_spans(trees):
    assert _count(trees["steady"]) == 8 <= 12


JAX_ATTRS = {
    "jax_trace_s", "jax_traces", "jax_lower_s", "backend_compile_s",
    "compiles", "cache_retrieval_s", "cache_hits", "cache_misses",
    "compile_saved_s",
}


def _attrs(span):
    """Every attribute name in the tree."""
    return set(span.attrs).union(*(_attrs(c) for c in span.children))


def test_jaxs_own_seconds_land_on_the_first_calls_dispatch(trees):
    """ISSUE 37: the static road's first ``dispatch`` is JAX's trace of
    the plan, its lowering and the compile (or the cache's load), and
    says so; together they are what the span took, and a steady call
    fires no such event."""
    dispatch = trees["first"].find("dispatch")
    attrs = dispatch.attrs
    assert attrs["jax_trace_s"] > 0 and attrs["jax_traces"] >= 1
    assert attrs["jax_lower_s"] > 0
    assert attrs["backend_compile_s"] > 0 and attrs["compiles"] >= 1
    carried = (
        attrs["jax_trace_s"] + attrs["jax_lower_s"] + attrs["backend_compile_s"]
    )
    # a nested trace is counted once, so the three never pass the span
    assert 0.5 * dispatch.duration_s < carried <= dispatch.duration_s
    for which in ("steady", "mutated"):
        assert not _attrs(trees[which]) & JAX_ATTRS, which


def test_report_prints_the_tree_it_is_given(trees):
    """An operator's first question is the first call, long after the
    thread's last tree has moved on."""
    import io

    out = io.StringIO()
    telemetry.report(file=out, root=trees["first"])
    lines = out.getvalue().splitlines()
    assert lines[0].startswith("evaluate_computation: ")
    assert any(l.startswith("    dispatch: ") and "compiles=" in l for l in lines)


TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


@contextlib.contextmanager
def _region(event, took: list):
    """One of JAX's timed regions, as ``log_elapsed_time`` reports it:
    at its close, with everything nested in it inside its seconds (put
    in ``took`` as well); some milliseconds long, so that on a loaded
    machine too what follows it begins after it ended."""
    t0 = time.perf_counter()
    yield
    time.sleep(0.01)
    took.append(time.perf_counter() - t0)
    jax.monitoring.record_event_duration_secs(event, took[-1])


def test_a_jax_event_lands_on_the_innermost_open_span_and_nowhere_else():
    roots = len(telemetry.recent_roots())
    # no span open: nothing changes, nothing is kept
    jax.monitoring.record_event_duration_secs(COMPILE_EVENT, 9.0)
    jax.monitoring.record_event("/jax/compilation_cache/cache_misses")
    assert len(telemetry.recent_roots()) == roots
    took = []
    with telemetry.span("outer") as outer:
        with telemetry.span("inner") as inner:
            with _region(COMPILE_EVENT, took):
                pass
            with _region(COMPILE_EVENT, took):  # after it, not inside it
                jax.monitoring.record_event("/jax/compilation_cache/cache_hits")
                jax.monitoring.record_event_duration_secs(
                    "/jax/compilation_cache/cache_retrieval_time_sec", 0.25
                )
            jax.monitoring.record_event_duration_secs("/jax/other", 7.0)
        jax.monitoring.record_event("/jax/compilation_cache/cache_misses")
    assert inner.attrs == {
        "backend_compile_s": pytest.approx(sum(took)), "compiles": 2,
        "cache_hits": 1, "cache_retrieval_s": 0.25,
    }
    assert outer.attrs == {"cache_misses": 1}


def test_nested_regions_are_counted_once_where_they_are_innermost():
    """A ``jit`` traced while a ``jit`` is traced, an eager op compiled
    while a plan is traced, a span that closes inside a region: each
    second goes to the innermost interval round it, so a tree's
    attributes add up to the time JAX spent and never pass the span."""
    plan, nested, eager, checks = [], [], [], []
    with telemetry.span("root") as root:
        with _region(TRACE_EVENT, plan):  # the plan's trace
            with _region(TRACE_EVENT, nested):  # a jit inside it
                pass
            with _region(COMPILE_EVENT, eager):  # an eager op meanwhile
                pass
            with telemetry.span("check") as check:  # a first-use check
                with _region(COMPILE_EVENT, checks):
                    pass
                time.sleep(0.01)  # and what the check does besides
    assert check.attrs == {
        "backend_compile_s": pytest.approx(checks[0]), "compiles": 1,
    }
    assert root.attrs["jax_traces"] == 2 and root.attrs["compiles"] == 1
    assert root.attrs["backend_compile_s"] == pytest.approx(eager[0])
    # the nested trace's seconds, and the plan's less everything that
    # closed inside it: not what the outer region reported, nor the sum
    own = plan[0] - nested[0] - eager[0] - check.duration_s
    assert own > 0.005
    assert root.attrs["jax_trace_s"] == pytest.approx(nested[0] + own)
    carried = root.attrs["jax_trace_s"] + root.attrs["backend_compile_s"]
    assert carried + check.duration_s <= root.duration_s
    # a root takes the thread's books with it: nothing is left to grow
    assert telemetry._state.closed == []


def test_a_worker_thread_attached_to_a_span_adds_to_its_tree():
    """A kernel's first-use check and the autotuner's micro leave the
    tracing thread; ``attach`` keeps their spans and JAX's seconds in
    the tree of the evaluation that waits for them."""
    import threading

    def work(parent):
        with telemetry.attach(parent):
            jax.monitoring.record_event_duration_secs(COMPILE_EVENT, 1.0)
            with telemetry.span("child"):
                pass
        with telemetry.span("own root"):
            pass

    with telemetry.span("caller") as caller:
        with telemetry.span("waits") as waits:
            t = threading.Thread(target=work, args=(waits,))
            t.start()
            t.join(timeout=30)
            assert not t.is_alive()
    assert waits.attrs == {"backend_compile_s": 1.0, "compiles": 1}
    assert _names(caller) == ("caller", [("waits", [("child", [])])])
    assert waits.children[0].trace_id == caller.trace_id
    assert telemetry.recent_roots()[-2].name == "own root"


def test_span_attributes(trees):
    first = trees["first"]
    bind = first.find("bind_arguments")
    assert bind.attrs == {"inputs": 2, "bytes": 2 * N * N * 8}
    # ``form``: the contiguous array hashed where it lies (ISSUE 29)
    assert first.find("input_fingerprint").attrs == {
        "bytes": N * N * 8, "form": "pieces",
    }
    assert first.find("input_upload").attrs == {"bytes": N * N * 8, "why": "miss"}
    assert set(first.find("dispatch").attrs) - JAX_ATTRS == {"plan_state"}
    assert trees["steady"].find("dispatch").attrs == {"plan_state": "static"}
    assert first.find("autotune").attrs == {"source": "default,predicted"}
    # ``halves``: results joined from float32 halves (a float64 on a
    # TPU, ISSUE 27); none on the CPU backend
    assert first.find("host_transfer").attrs == {
        "outputs": 1, "saves": 0, "bytes": N * N * 8, "halves": 0,
    }
    assert {"jit", "plan_mode", "pinned_ops"} <= set(first.find("execute").attrs)


def test_in_place_mutation_uploads_the_stale_argument_again(trees):
    uploads = [
        s for s in trees["mutated"].find("execute").children
        if s.name == "input_upload"
    ]
    assert [u.attrs["why"] for u in uploads] == ["stale"]
    assert trees["steady"].find("input_upload") is None
    assert trees["mutated"].find("execute").attrs["taken_back"] == 1
    assert trees["steady"].find("execute").attrs["taken_back"] == 0


def test_a_resident_argument_is_hashed_after_its_dispatch(trees):
    """The provisional hit: the program is dispatched with the resident
    copies, and only then are the arguments hashed, while it runs."""
    execute = trees["steady"].find("execute")
    dispatch = execute.find("dispatch")
    hashes = [c for c in execute.children if c.name == "input_fingerprint"]
    assert len(hashes) == 2
    for span in hashes:
        assert dispatch.end_s <= span.start_s
        assert span.end_s <= execute.find("device_wait").start_s


PROVISIONAL = "moose_tpu_provisional_dispatch_total"


def _moved(before, after):
    return {k: after[k] - before.get(k, 0) for k in after
            if after[k] != before.get(k, 0)}


def test_a_taken_back_run_is_never_fetched_and_its_rerun_has_a_fresh_key(
    monkeypatch,
):
    """``x[0, 0] += 1.0`` between two evaluations: the run dispatched
    with the stale resident copy is dropped unread; the run again on the
    new content draws a new master key, and its answer is, bit for bit,
    the new content's under that key."""
    from moose_tpu.execution import interpreter

    drawn, stagings, fetched = [], [], []
    real_stage, real_fetch = interpreter.stage_results, interpreter._fetch_user_value

    def draw(domain=""):
        drawn.append(np.array([len(drawn) + 1, 7, 11, 13], dtype=np.uint32))
        return drawn[-1]

    def stage(outputs, saves):
        stagings.append(real_stage(outputs, saves))
        return stagings[-1]

    def fetch(staged):
        fetched.append(staged)
        return real_fetch(staged)

    monkeypatch.setattr(interpreter, "master_key_words", draw)
    monkeypatch.setattr(interpreter, "stage_results", stage)
    monkeypatch.setattr(interpreter, "_fetch_user_value", fetch)
    runtime = LocalMooseRuntime(PARTIES, use_jit=True)
    comp, arguments = _secure_dot(), _inputs(4)
    _evaluate(runtime, comp, arguments)  # the arrays become resident
    arguments["x"][0, 0] += 1.0
    lookups = _counter_values("moose_tpu_device_cache_lookups_total")
    provisional = _counter_values(PROVISIONAL)
    del drawn[:], stagings[:], fetched[:]
    out, tree = _evaluate(runtime, comp, arguments)

    assert _moved(provisional, _counter_values(PROVISIONAL)) == {
        "outcome=taken_back": 1,
    }
    assert _moved(
        lookups, _counter_values("moose_tpu_device_cache_lookups_total")
    ) == {"result=stale": 1, "result=hit": 1}
    assert tree.find("execute").attrs["taken_back"] == 1
    taken_back, rerun = stagings
    assert len(fetched) == 1
    assert fetched[0] is rerun[1][0]
    assert not any(f is v for f in fetched for v in taken_back[1])
    first_key, rerun_key = drawn
    assert not np.array_equal(first_key, rerun_key)
    # the new content, as a new array, under the rerun's key
    monkeypatch.setattr(interpreter, "master_key_words", lambda d="": rerun_key)
    again, _ = _evaluate(
        runtime, comp, {"x": arguments["x"].copy(), "y": arguments["y"]}
    )
    np.testing.assert_array_equal(out, again)
    np.testing.assert_allclose(out, arguments["x"] @ arguments["y"], atol=1e-3)


def test_a_new_array_is_uploaded_then_hashed_then_resident():
    """The miss road: the upload before the dispatch, the hash after it,
    and on the next call a provisional hit, kept."""
    runtime = LocalMooseRuntime(PARTIES, use_jit=True)
    comp, arguments = _secure_dot(), _inputs(5)
    _evaluate(runtime, comp, arguments)  # the plan, out of the way
    arguments = {"x": arguments["x"].copy(), "y": arguments["y"]}
    provisional = _counter_values(PROVISIONAL)
    _, tree = _evaluate(runtime, comp, arguments)
    assert _names(tree.find("bind_arguments")) == ("bind_arguments", [UPLOAD])
    assert _names(tree.find("execute"))[1][:3] == [DISPATCH, HASH, HASH]
    middle = _counter_values(PROVISIONAL)
    _, tree = _evaluate(runtime, comp, arguments)
    assert _names(tree) == ("evaluate_computation", [
        ("bind_arguments", []), EXECUTE,
    ])
    assert _moved(provisional, middle) == {"outcome=kept": 1}
    assert _moved(middle, _counter_values(PROVISIONAL)) == {"outcome=kept": 1}


def test_an_eager_or_validating_plan_binds_checked_arguments(monkeypatch):
    """Where the plan is eager, or its ladder still validates, the
    arguments are hashed before the dispatch, as before, and nothing is
    counted provisional; a ladder promoted to ``jit`` takes the
    provisional road from its next evaluation."""
    comp, arguments = _secure_dot(), _inputs(6)
    provisional = _counter_values(PROVISIONAL)
    eager = LocalMooseRuntime(PARTIES)  # the suite's default: eager
    for _ in range(2):
        _, tree = _evaluate(eager, comp, arguments)
    assert eager.last_plan["plan_mode"] == "eager"
    assert _names(tree) == ("evaluate_computation", [
        ("bind_arguments", [HASH, HASH]),
        ("execute", [DISPATCH, WAIT, TRANSFER]),
    ])
    assert "taken_back" not in tree.find("execute").attrs
    assert _counter_values(PROVISIONAL) == provisional

    monkeypatch.setenv("MOOSE_TPU_SELFCHECK_FORCE", "1")
    monkeypatch.setenv("MOOSE_TPU_JIT_SELFCHECK", "1")
    gated = LocalMooseRuntime(PARTIES, use_jit=True)
    _, tree = _evaluate(gated, comp, arguments)
    assert tree.find("dispatch").attrs["plan_state"] == "jit"  # promoted
    assert tree.find("ladder_validate") is not None
    assert _names(tree.find("bind_arguments"))[1] == [HASH, HASH]
    assert _counter_values(PROVISIONAL) == provisional
    _, tree = _evaluate(gated, comp, arguments)
    assert _names(tree)[1][-2:] == [("bind_arguments", []), EXECUTE]
    assert _moved(provisional, _counter_values(PROVISIONAL)) == {
        "outcome=kept": 1,
    }


def test_leaves_cover_the_evaluation(trees):
    """What no leaf covers is self time of the three spans above them,
    and none of it is negative: children lie inside their parents."""
    def check(span):
        inside = sum(c.duration_s for c in span.children)
        assert inside <= span.duration_s + 1e-9, span.name
        for child in span.children:
            assert span.start_s <= child.start_s <= child.end_s <= span.end_s
            check(child)

    for tree in trees.values():
        check(tree)


def test_recent_roots_is_bounded_at_64_and_ordered():
    for i in range(70):
        with telemetry.span("numbered", i=i):
            pass
    with telemetry.span("other"):
        pass
    roots = telemetry.recent_roots()
    assert len(roots) == 64
    assert roots[-1].name == "other"
    numbered = telemetry.recent_roots("numbered")
    assert [r.attrs["i"] for r in numbered] == list(range(7, 70))
    assert telemetry.last_trace() is roots[-1]  # the thread's own, as before


def _counter_values(name):
    return dict(metrics.REGISTRY.snapshot().get(name, {}).get("values", {}))


def test_counters_move_by_the_bytes_of_inputs_and_result():
    runtime, comp, arguments = LocalMooseRuntime(PARTIES), _secure_dot(), _inputs(1)
    one = N * N * 8

    def delta(before, after):
        return {k: after[k] - before.get(k, 0) for k in after
                if after[k] != before.get(k, 0)}

    names = ("moose_tpu_device_cache_lookups_total",
             "moose_tpu_host_device_bytes_total",
             "moose_tpu_input_fingerprint_total")
    before = [_counter_values(n) for n in names]
    _evaluate(runtime, comp, arguments)
    middle = [_counter_values(n) for n in names]
    _evaluate(runtime, comp, arguments)
    after = [_counter_values(n) for n in names]
    assert delta(before[0], middle[0]) == {"result=miss": 2}
    assert delta(before[1], middle[1]) == {
        "direction=hashed": 2 * one, "direction=h2d": 2 * one,
        "direction=d2h": one,
    }
    assert delta(before[2], middle[2]) == {"form=pieces": 2}
    assert delta(middle[0], after[0]) == {"result=hit": 2}
    assert delta(middle[1], after[1]) == {
        "direction=hashed": 2 * one, "direction=d2h": one,
    }
    assert delta(middle[2], after[2]) == {"form=pieces": 2}


def test_small_arguments_bypass_the_device_cache():
    from moose_tpu.execution.interpreter import _device_cache

    name = "moose_tpu_device_cache_lookups_total"
    before = _counter_values(name).get("result=bypass", 0)
    small = np.zeros((4, 4))
    with telemetry.span("root") as root:
        assert _device_cache.put(small) is small
    assert root.children == []
    assert _counter_values(name)["result=bypass"] == before + 1


def test_spans_reach_a_profiler_trace_on_the_profilers_clock(tmp_path):
    """With a profiler session attached, each span is an event of the
    host plane, nested by time inside the caller's own annotation on the
    same thread's line: the device's clock, as ``chipbench --trace 1``
    reads it."""
    from jax.profiler import ProfileData

    runtime, comp, arguments = LocalMooseRuntime(PARTIES), _secure_dot(), _inputs(2)
    _evaluate(runtime, comp, arguments)  # trace and plan out of the way
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("outer"):
            _, root = _evaluate(runtime, comp, arguments)
    (path,) = glob.glob(
        os.path.join(tmp_path, "plugins", "profile", "*", "*.xplane.pb")
    )
    found = None
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            events = {}
            for ev in line.events:
                if ev.name == "outer" or ev.name.startswith("moose_tpu."):
                    events.setdefault(ev.name, []).append(
                        (ev.start_ns, ev.start_ns + ev.duration_ns)
                    )
            if "outer" in events:
                found = events
    assert found is not None, "no line carries the caller's annotation"
    (outer,) = found["outer"]
    (whole,) = found["moose_tpu.evaluate_computation"]
    (transfer,) = found["moose_tpu.host_transfer"]
    assert outer[0] <= whole[0] <= transfer[0] <= transfer[1] <= whole[1] <= outer[1]
    assert len(found["moose_tpu.input_fingerprint"]) == 2
    for name in ("bind_arguments", "execute", "dispatch", "device_wait"):
        ((lo, hi),) = found["moose_tpu." + name]
        assert whole[0] <= lo <= hi <= whole[1], name
    # and the two clocks agree on how long the evaluation took
    assert (whole[1] - whole[0]) / 1e9 == pytest.approx(root.duration_s, rel=0.2)


def test_profiling_timeline_has_host_transfer_once_per_evaluation():
    """``host_transfer`` was a ``profiling.phase``; as a telemetry span
    it reaches the Perfetto timeline through the span hook, once."""
    runtime, comp, arguments = LocalMooseRuntime(PARTIES), _secure_dot(), _inputs(3)
    _evaluate(runtime, comp, arguments)
    profiling.start()
    try:
        for _ in range(2):
            _evaluate(runtime, comp, arguments)
    finally:
        doc = profiling.stop()
    names = [e["name"] for e in doc["traceEvents"] if e.get("ph") == "X"]
    for name in ("host_transfer", "device_wait", "dispatch", "execute"):
        assert names.count(name) == 2, name
    assert names.count("input_fingerprint") == 4


SCOPES = (
    "share", "reveal", "zero_share", "cross_terms", "reshare", "trunc_pr",
    "prf_draw", "encode", "decode", "limb_split", "limb_matmul",
    "limb_recombine",
)


@pytest.fixture(scope="module")
def lowered_text():
    """The tiny dot's whole plan, lowered (nothing compiled) with the
    matmul strategy a TPU picks."""
    from moose_tpu.dialects import ring, stacked
    from moose_tpu.edsl import tracer
    from moose_tpu.execution import interpreter

    arguments = {"x": np.ones((8, 8)), "y": np.ones((8, 8))}
    was = ring._MATMUL_STRATEGY
    ring._MATMUL_STRATEGY = "limb_int8"
    try:
        traced = tracer.trace(_secure_dot())  # the plan holds it weakly
        plan = interpreter.build_plan(
            traced, arguments, True, dialect=stacked.StackedDialect(),
        )
        key = interpreter.master_key_words("logical")
        return jax.jit(plan.core).lower(key, arguments).as_text(debug_info=True)
    finally:
        ring._MATMUL_STRATEGY = was


@pytest.mark.parametrize("scope", SCOPES)
def test_lowered_program_names_each_protocol_phase(lowered_text, scope):
    assert re.search(rf"moose/{scope}\b", lowered_text)
