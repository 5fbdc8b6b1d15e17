"""The validated-jit ladder's verdict, kept beside the compile cache.

On the CPU under ``MOOSE_TPU_SELFCHECK_FORCE=1`` (every jitted plan is
gated, as a heavy plan is on a TPU), with the store in a ``tmp_path``
directory.  "A later process" is a computation built afresh (another
object, so the in-process plan registry does not know it) on a fresh
runtime: what it shares with the first is the directory.
"""

import json
import shutil

import numpy as np
import pytest

import moose_tpu as pm
from moose_tpu import compile_cache, metrics
from moose_tpu.dialects import ring
from moose_tpu.execution import interpreter as interp
from moose_tpu.runtime import LocalMooseRuntime

PARTIES = ["alice", "bob", "carole"]


def make_comp(constant: float = 1.5):
    """z = x * x * constant under replicated sharing; a new computation
    object at every call, equal for equal ``constant``."""
    alice, bob, carole = (pm.host_placement(p) for p in PARTIES)
    rep = pm.replicated_placement("rep", players=[alice, bob, carole])

    @pm.computation
    def squared(x: pm.Argument(placement=alice, dtype=pm.float64)):
        with alice:
            xf = pm.cast(x, dtype=pm.fixed(8, 17))
            k = pm.cast(
                pm.constant(np.array([constant]), dtype=pm.float64),
                dtype=pm.fixed(8, 17),
            )
        with rep:
            z = pm.mul(pm.mul(xf, xf), k)
        with carole:
            return pm.cast(z, dtype=pm.float64)

    return squared


X = np.arange(8.0).reshape(4, 2) / 4.0


def evaluate(runtime, comp, x=X, constant=1.5):
    (got,) = runtime.evaluate_computation(comp, arguments={"x": x}).values()
    np.testing.assert_allclose(got, x * x * constant, atol=1e-3)
    return dict(runtime.last_plan)


def process(comp=None, evaluations=2, x=X, constant=1.5):
    """What one process does: a fresh runtime over a computation built
    afresh; the plan after each evaluation."""
    comp = comp if comp is not None else make_comp(constant)
    runtime = LocalMooseRuntime(PARTIES, layout="stacked", use_jit=True)
    return [evaluate(runtime, comp, x, constant) for _ in range(evaluations)]


def verdict_counts() -> dict:
    values = metrics.REGISTRY.snapshot().get(
        "moose_tpu_plan_verdict_total", {}
    ).get("values", {})
    return {k.split("=", 1)[1]: v for k, v in values.items()}


def counted(before: dict) -> dict:
    after = verdict_counts()
    return {
        k: after[k] - before.get(k, 0)
        for k in after if after[k] != before.get(k, 0)
    }


@pytest.fixture
def store(tmp_path, monkeypatch):
    """A verdict store of the test's own, and every plan gated."""
    directory = tmp_path / "plan_verdicts"
    monkeypatch.setattr(compile_cache, "plan_verdict_dir", lambda: directory)
    monkeypatch.setenv("MOOSE_TPU_SELFCHECK_FORCE", "1")
    return directory


def the_record(directory) -> tuple:
    (path,) = directory.glob("*.json")
    return path, json.loads(path.read_text())


@pytest.fixture(scope="module")
def promoted_store(tmp_path_factory):
    """A store holding the record of one plan validated to promotion
    (K = 2), made once; tests copy it."""
    directory = tmp_path_factory.mktemp("promoted") / "plan_verdicts"
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(compile_cache, "plan_verdict_dir", lambda: directory)
        mp.setenv("MOOSE_TPU_SELFCHECK_FORCE", "1")
        plans = process()
    assert [p["plan_state"] for p in plans] == ["validating", "jit"]
    assert [p["validations_run"] for p in plans] == [1, 2]
    assert {p["verdict"] for p in plans} == {"validated"}
    return directory


@pytest.fixture
def promoted(store, promoted_store):
    shutil.copytree(promoted_store, store)
    return store


def test_the_record_holds_the_verdict_and_its_key_in_clear(promoted):
    path, record = the_record(promoted)
    assert record["mode"] == "jit" and record["level"] == 0
    assert record["clean_runs"] == 2 and record["checks"] == 2
    assert record["pinned"] == [] and record["time"] > 0
    key = record["key"]
    assert key["plan_key"] == "StackedDialect"
    assert key["avals"] == [["x", [4, 2], "float64"]]
    assert key["prf"] == ring.get_prf_impl()
    import jax

    assert key["jax"] == jax.__version__
    assert key["platform"] == "cpu" and key["device_kind"] == "cpu"
    assert len(key["module"]) == 64 and len(key["computation"]) == 64
    assert not list(promoted.glob(".*"))  # nothing left beside


def test_a_later_process_restores_and_never_builds_the_twin(
    promoted, monkeypatch
):
    twins = []
    real = interp._logical_plan_builder

    def spying(dialect):
        build = real(dialect)

        def spy(comp, arguments, use_jit, segment_limit, jit_segments, **kw):
            if use_jit and not jit_segments:
                twins.append(segment_limit)
            return build(
                comp, arguments, use_jit, segment_limit, jit_segments, **kw
            )

        return spy

    monkeypatch.setattr(interp, "_logical_plan_builder", spying)
    before = verdict_counts()
    _, written = the_record(promoted)
    plans = process()
    assert [p["plan_state"] for p in plans] == ["jit", "jit"]
    assert [p["validations_run"] for p in plans] == [0, 0]
    assert {p["verdict"] for p in plans} == {"restored"}
    assert twins == []
    assert counted(before) == {"hit": 1}
    assert the_record(promoted)[1] == written  # read, not rewritten


def test_restore_opens_one_plan_verdict_span_and_no_validate_span(promoted):
    from moose_tpu import telemetry

    process(evaluations=2)
    first, second = telemetry.recent_roots("evaluate_computation")[-2:]
    span = first.find("plan_verdict")
    assert span is not None
    assert span.attrs["op"] == "lookup" and span.attrs["result"] == "hit"
    assert span.attrs["mode"] == "jit"
    assert first.find("ladder_validate") is None
    assert second.find("plan_verdict") is None
    # ISSUE 37: the lookup from inside.  The candidate is traced and
    # lowered for the record's key, and the span that does it says so
    build, key, read = span.children
    assert [s.name for s in span.children] == [
        "candidate_build", "record_key", "verdict_read",
    ]
    assert key.attrs["module_bytes"] > 0
    assert key.attrs["jax_trace_s"] > 0 and key.attrs["jax_lower_s"] > 0
    assert read.attrs == {"result": "hit"}
    # and the jitted call that follows pays the compile or the load
    assert first.find("dispatch").attrs["compiles"] >= 1


def test_a_validating_evaluation_times_the_candidate_and_the_twin_apart(store):
    from moose_tpu import telemetry

    # a constant no other test uses: a candidate this process has not
    # compiled (the twin's small programs it may well have, so nothing
    # is asked of its attributes)
    (plan,) = process(evaluations=1, constant=3.25)
    assert plan["plan_state"] == "validating"
    validate = telemetry.recent_roots("evaluate_computation")[-1].find(
        "ladder_validate"
    )
    candidate, twin, compare = validate.children
    assert [s.name for s in validate.children] == [
        "candidate_run", "twin_run", "compare",
    ]
    # the candidate's one compile (the lookup traced and lowered it)
    assert candidate.attrs["compiles"] == 1
    assert "jax_lower_s" not in candidate.attrs
    inside = sum(s.duration_s for s in validate.children)
    assert inside == pytest.approx(validate.duration_s, rel=0.05)


LIVE_CHANGES = {
    "avals": dict(x=np.ones((5, 2))),
    "constant": dict(constant=2.5),
}


@pytest.mark.parametrize("what", sorted(LIVE_CHANGES))
def test_another_binding_or_constant_has_no_record(promoted, what):
    before = verdict_counts()
    (plan,) = process(evaluations=1, **LIVE_CHANGES[what])
    assert plan["plan_state"] == "validating"
    assert plan["validations_run"] == 1 and plan["verdict"] == "validated"
    counts = counted(before)
    assert counts == {"miss": 1, "stored": 1}
    assert len(list(promoted.glob("*.json"))) == 2  # a slot of its own


def test_another_prf_is_another_program(promoted):
    before = verdict_counts()
    was = ring.get_prf_impl()
    ring.set_prf_impl("threefry" if was != "threefry" else "rbg")
    try:
        (plan,) = process(evaluations=1)
    finally:
        ring.set_prf_impl(was)
    assert plan["plan_state"] == "validating" and plan["validations_run"] == 1
    assert counted(before) == {"stale": 1, "stored": 1}
    _, record = the_record(promoted)  # same slot, overwritten
    assert record["key"]["prf"] != was and record["mode"] == "validating"


def test_another_k_is_not_served_by_this_record(promoted, monkeypatch):
    monkeypatch.setenv("MOOSE_TPU_JIT_SELFCHECK", "3")
    before = verdict_counts()
    (plan,) = process(evaluations=1)
    assert plan["plan_state"] == "validating" and plan["validations_run"] == 1
    assert counted(before) == {"stale": 1, "stored": 1}


KEY_PARTS = [
    "computation", "plan_key", "avals", "module", "ladder",
    "first_rung_limit", "jax", "jaxlib", "platform", "platform_version",
    "device_kind", "prf",
]


def test_key_parts_are_the_ones_the_test_below_changes(promoted):
    _, record = the_record(promoted)
    assert sorted(record["key"]) == sorted(KEY_PARTS)


@pytest.mark.parametrize("part", KEY_PARTS + ["checks", "format"])
def test_a_record_that_differs_in_one_key_part_is_stale(promoted, part):
    """Each part alone: a record earned under another value of it (a
    libtpu build, a JAX version, a lowered module, ...) is ignored and
    overwritten, never trusted."""
    path, record = the_record(promoted)
    holder = record if part in ("checks", "format") else record["key"]
    was = holder[part]
    holder[part] = (
        was + 1 if isinstance(was, int)
        else was + ["other"] if isinstance(was, list)
        else was + "-other"
    )
    path.write_text(json.dumps(record))
    before = verdict_counts()
    plans = process()
    assert [p["plan_state"] for p in plans] == ["validating", "jit"]
    assert [p["validations_run"] for p in plans] == [1, 2]
    assert counted(before) == {"stale": 1, "stored": 2}
    _, rewritten = the_record(promoted)
    assert rewritten["mode"] == "jit"
    holder = rewritten if part in ("checks", "format") else rewritten["key"]
    assert holder[part] == was


@pytest.mark.parametrize(
    "content", [b"", b'{"format": 1, "key": {"compu', b"[1, 2]", b'{"a": 1}'],
    ids=["empty", "truncated", "not-an-object", "foreign"],
)
def test_a_file_that_is_no_record_is_ignored_and_replaced(promoted, content):
    path, _ = the_record(promoted)
    path.write_bytes(content)
    before = verdict_counts()
    plans = process()
    assert [p["plan_state"] for p in plans] == ["validating", "jit"]
    assert counted(before) == {"miss": 1, "stored": 2}
    assert the_record(promoted)[1]["mode"] == "jit"


def test_a_process_cut_after_one_clean_run_leaves_one_run_to_do(store):
    before = verdict_counts()
    (plan,) = process(evaluations=1)  # K = 2: cut before promotion
    assert plan["plan_state"] == "validating"
    _, record = the_record(store)
    assert record["mode"] == "validating" and record["clean_runs"] == 1
    assert counted(before) == {"miss": 1, "stored": 1}

    before = verdict_counts()
    plans = process()
    assert [p["plan_state"] for p in plans] == ["jit", "jit"]
    assert [p["validations_run"] for p in plans] == [1, 1]
    assert {p["verdict"] for p in plans} == {"validated"}
    assert counted(before) == {"resumed": 1, "stored": 1}
    assert the_record(store)[1]["clean_runs"] == 2

    (plan,) = process(evaluations=1)  # and the third validates nothing
    assert plan["plan_state"] == "jit" and plan["validations_run"] == 0


def test_a_descent_is_restored_with_its_pins(store, monkeypatch):
    """A bad verdict is as durable as a good one: the rungs that
    diverged are not tried again, the op that diverges stays pinned."""
    monkeypatch.setenv("MOOSE_TPU_SELFCHECK_FAULT", "Mul")
    monkeypatch.setenv("MOOSE_TPU_JIT_SELFCHECK", "1")
    plans = process(evaluations=5)
    assert plans[-1]["plan_state"] == "per-op"
    pinned = plans[-1]["pinned_ops"]
    assert pinned
    _, record = the_record(store)
    assert record["mode"] == "per-op" and record["pinned"] == pinned
    assert record["level"] == len(interp._SelfCheckBase.LADDER) - 1

    before = verdict_counts()
    (plan,) = process(evaluations=1)
    assert plan["plan_state"] == "per-op" and plan["plan_mode"] == "per-op"
    assert plan["pinned_ops"] == pinned
    assert plan["validations_run"] == 0 and plan["verdict"] == "restored"
    assert counted(before) == {"hit": 1}


def test_an_exhaustion_is_restored_as_such(store, monkeypatch):
    monkeypatch.setenv("MOOSE_TPU_JIT_SELFCHECK", "1")
    # the per-op rung would pin the two ops that fail and promote
    monkeypatch.setenv("MOOSE_TPU_PEROP_MAX", "0")
    real = interp._SelfCheckRunner._invoke

    def no_candidate_runs(self, fn, *args):
        if fn is self._jit_fn:
            raise RuntimeError("injected candidate failure")
        return real(self, fn, *args)

    monkeypatch.setattr(interp._SelfCheckRunner, "_invoke", no_candidate_runs)
    comp = make_comp()
    runtime = LocalMooseRuntime(PARTIES, layout="stacked", use_jit=True)
    for _ in range(2 * len(interp._SelfCheckBase.LADDER)):
        plan = evaluate(runtime, comp)
        if plan["plan_state"] == "eager":
            break
    assert plan["plan_state"] == "eager" and plan["layout"] == "stacked"
    assert the_record(store)[1]["mode"] == "eager"

    monkeypatch.setattr(interp._SelfCheckRunner, "_invoke", real)
    before = verdict_counts()
    (plan,) = process(evaluations=1)
    assert plan["plan_state"] == "eager" and plan["validations_run"] == 0
    assert plan["verdict"] == "restored" and counted(before) == {"hit": 1}


def _spy_on_the_store(monkeypatch) -> list:
    calls = []
    for name in ("read_plan_verdict", "write_plan_verdict"):
        real = getattr(compile_cache, name)

        def spy(*args, _name=name, _real=real):
            calls.append(_name)
            return _real(*args)

        monkeypatch.setattr(compile_cache, name, spy)
    return calls


def test_without_a_cache_directory_no_file_is_touched(monkeypatch, tmp_path):
    # the suite's default (conftest): plan_verdict_dir() is None
    assert compile_cache.plan_verdict_dir() is None
    monkeypatch.setenv("MOOSE_TPU_SELFCHECK_FORCE", "1")
    calls = _spy_on_the_store(monkeypatch)
    before = verdict_counts()
    plans = process()
    assert [p["plan_state"] for p in plans] == ["validating", "jit"]
    assert [p["validations_run"] for p in plans] == [1, 2]
    assert calls == [] and counted(before) == {}
    assert compile_cache.read_plan_verdict("anything") is None
    assert compile_cache.write_plan_verdict("anything", {}) is False
    assert not list(tmp_path.iterdir())


def test_a_static_plan_never_calls_the_store(store, monkeypatch):
    monkeypatch.delenv("MOOSE_TPU_SELFCHECK_FORCE")
    asked = []
    monkeypatch.setattr(
        compile_cache, "plan_verdict_dir", lambda: asked.append(1) or store
    )
    calls = _spy_on_the_store(monkeypatch)
    plans = process()
    assert [p["plan_state"] for p in plans] == ["static", "static"]
    assert {p["verdict"] for p in plans} == {"none"}
    assert [p["validations_run"] for p in plans] == [0, 0]
    assert asked == [] and calls == [] and not store.exists()


def test_the_directory_is_the_compile_caches(monkeypatch, tmp_path):
    import jax

    was = jax.config.jax_compilation_cache_dir
    monkeypatch.undo()  # the real plan_verdict_dir, not conftest's
    try:
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        assert compile_cache.plan_verdict_dir() == tmp_path / "plan_verdicts"
        assert compile_cache.write_plan_verdict("slot", {"a": 1}) is True
        assert compile_cache.read_plan_verdict("slot") == {"a": 1}
        assert sorted(p.name for p in (tmp_path / "plan_verdicts").iterdir()) == [
            "slot.json"
        ]
        jax.config.update("jax_compilation_cache_dir", None)
        assert compile_cache.plan_verdict_dir() is None
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


# -- the in-memory registry gets the same binding ---------------------------


def test_a_promotion_is_not_inherited_by_another_binding(monkeypatch):
    """PR 22, finding 6b: a promotion at one batch served another
    unvalidated.  A second binding of the same computation validates
    for itself; the same binding on a second runtime adopts."""
    from moose_tpu.edsl import tracer

    monkeypatch.setenv("MOOSE_TPU_SELFCHECK_FORCE", "1")
    comp = tracer.trace(make_comp())  # the registry is keyed on this
    runtime = LocalMooseRuntime(PARTIES, layout="stacked", use_jit=True)
    assert evaluate(runtime, comp)["plan_state"] == "validating"
    assert evaluate(runtime, comp)["plan_state"] == "jit"

    wider = np.ones((6, 2))
    plan = evaluate(runtime, comp, wider)
    assert plan["plan_state"] == "validating" and plan["validations_run"] == 1
    plan = evaluate(runtime, comp, wider)
    assert plan["plan_state"] == "jit" and plan["verdict"] == "validated"

    again = LocalMooseRuntime(PARTIES, layout="stacked", use_jit=True)
    plan = evaluate(again, comp, wider)  # the registry's entry is wider's
    assert plan["plan_state"] == "jit" and plan["verdict"] == "restored"
    assert plan["validations_run"] == 0
    plan = evaluate(again, comp)  # and not the first binding's any more
    assert plan["plan_state"] == "validating"


def test_an_exhaustion_reroutes_its_own_binding_only(monkeypatch):
    monkeypatch.setenv("MOOSE_TPU_SELFCHECK_FORCE", "1")
    monkeypatch.setenv("MOOSE_TPU_JIT_SELFCHECK", "1")
    comp = make_comp()
    runtime = LocalMooseRuntime(PARTIES, layout="stacked", use_jit=True)
    assert evaluate(runtime, comp)["plan_state"] == "jit"
    traced = runtime._trace_cache[comp]
    state = interp._registry()[traced]["StackedDialect"]
    assert state["avals"] == interp.binding_avals({"x": X})
    state["mode"] = "eager"
    assert runtime._stacked.plan_exhausted(traced, {"x": X})
    assert not runtime._stacked.plan_exhausted(traced, {"x": np.ones((6, 2))})
    assert evaluate(runtime, comp)["layout"] == "per-host"
    assert evaluate(runtime, comp, np.ones((6, 2)))["layout"] == "stacked"
