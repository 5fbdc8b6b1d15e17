"""Validated-jit self-check gate (execution/interpreter._SelfCheckRunner).

The TPU miscompile mitigation (DEVELOP.md "Known issue") promotes gated
heavy graphs back to segmented jit after K clean jit-vs-eager runs and
demotes them down a segment-size ladder on divergence.  The backend bug
itself cannot reproduce on CPU, so these tests drive the runner's state
machine directly — clean promotion, fault-injected demotion, and the
exactness of the comparison — on a real lowered protocol graph.
"""

import numpy as np
import pytest

import moose_tpu as pm
from moose_tpu.edsl import tracer
from moose_tpu.execution import interpreter as interp


def _dot_comp(args):
    alice = pm.host_placement("alice")
    bob = pm.host_placement("bob")
    carole = pm.host_placement("carole")
    rep = pm.replicated_placement("rep", players=[alice, bob, carole])

    @pm.computation
    def comp(
        x: pm.Argument(placement=alice, dtype=pm.float64),
        w: pm.Argument(placement=bob, dtype=pm.float64),
    ):
        with alice:
            xf = pm.cast(x, dtype=pm.fixed(14, 23))
        with bob:
            wf = pm.cast(w, dtype=pm.fixed(14, 23))
        with rep:
            y = pm.dot(xf, wf)
        with carole:
            out = pm.cast(y, dtype=pm.float64)
        return out

    # the logical interpreter consumes the TRACED logical graph (its
    # dialect kernels lower during execution)
    return tracer.trace(comp)


@pytest.fixture()
def dot_setup():
    rng = np.random.default_rng(21)
    x = rng.normal(size=(3, 4))
    w = rng.normal(size=(4, 2))
    args = {"x": x, "w": w}
    comp = _dot_comp(args)
    return comp, args, x @ w


def _dyn(runner, args):
    return {
        name: np.asarray(args[name])
        for name in runner.eager_plan.dynamic_names
    }


def _mk(i=0):
    return (np.arange(4, dtype=np.uint32) + 77 + i)


def _decode_outputs(outputs):
    (val,) = [
        interp._to_user_value(v) for v in outputs.values()
    ]
    return np.asarray(val)


def test_selfcheck_promotes_after_clean_runs(dot_setup):
    comp, args, want = dot_setup
    runner = interp._SelfCheckRunner(comp, args, checks=2)
    assert runner.mode == "validating"
    dyn = _dyn(runner, args)

    out1, _ = runner.run(_mk(0), dyn)
    assert runner.mode == "validating"  # one clean run of two
    out2, _ = runner.run(_mk(1), dyn)
    assert runner.mode == "jit"  # promoted
    out3, _ = runner.run(_mk(2), dyn)  # pure jit now

    for out in (out1, out2, out3):
        np.testing.assert_allclose(_decode_outputs(out), want, atol=1e-5)


def test_selfcheck_demotes_down_ladder_on_divergence(dot_setup):
    comp, args, want = dot_setup
    runner = interp._SelfCheckRunner(comp, args, checks=1)
    dyn = _dyn(runner, args)

    # fault-inject: a candidate whose results are corrupted (the shape
    # of a value-dependent miscompile) must never be promoted
    real_jit = runner._jit_fn

    def corrupted(master_key, d):
        outputs, saves = real_jit(master_key, d)
        bad = {
            k: type(v)(
                np.asarray(v.value) + 5e13, v.plc, v.dtype
            ) if hasattr(v, "value") else v
            for k, v in outputs.items()
        }
        return bad, saves

    runner._jit_fn = corrupted
    out, _ = runner.run(_mk(3), dyn)
    # mismatch detected: returned the EAGER (correct) result and moved
    # down the ladder with a fresh (uncorrupted) candidate
    np.testing.assert_allclose(_decode_outputs(out), want, atol=1e-5)
    assert runner.mode == "validating"
    assert runner._level == 1

    # the rebuilt candidate is honest, so it now promotes
    out2, _ = runner.run(_mk(4), dyn)
    assert runner.mode == "jit"
    np.testing.assert_allclose(_decode_outputs(out2), want, atol=1e-5)


def test_selfcheck_pins_eager_when_every_rung_fails(dot_setup):
    comp, args, want = dot_setup
    runner = interp._SelfCheckRunner(comp, args, checks=1)
    dyn = _dyn(runner, args)

    def always_broken(master_key, d):
        raise RuntimeError("injected candidate failure")

    # every rebuild gets the broken candidate
    runner._jit_fn = always_broken
    orig_build = runner._build_candidate
    runner._build_candidate = lambda: setattr(
        runner, "_jit_fn", always_broken
    )

    # each rung tolerates ONE run failure (transient-OOM protection)
    # before a second failure burns it
    for i in range(2 * len(interp._SelfCheckRunner.LADDER)):
        out, _ = runner.run(_mk(10 + i), dyn)
        np.testing.assert_allclose(_decode_outputs(out), want, atol=1e-5)
    assert runner.mode == "eager"
    # every failure is on record, rung by rung, with its message
    assert len(runner.run_errors) >= 2 * (len(runner.LADDER) - 1)
    assert runner.run_errors[0] == (
        "default-segments: RuntimeError: injected candidate failure"
    )
    assert runner.plan_info()["run_errors"] == runner.run_errors
    # eager mode keeps working without a candidate
    out, _ = runner.run(_mk(20), dyn)
    np.testing.assert_allclose(_decode_outputs(out), want, atol=1e-5)


def test_candidate_run_failure_surfaces_in_last_plan(monkeypatch):
    """A jit candidate that fails to compile or run costs nothing in
    correctness — the ladder answers from its eager reference — so the
    caller can only see it in ``runtime.last_plan["run_errors"]``."""
    from moose_tpu.runtime import LocalMooseRuntime

    monkeypatch.setenv("MOOSE_TPU_SELFCHECK_FORCE", "1")
    monkeypatch.setenv("MOOSE_TPU_JIT_SELFCHECK", "1")
    real_invoke = interp._SelfCheckRunner._invoke
    refusals = []

    def refuse_first_candidate(self, fn, *args):
        if fn is self._jit_fn and not refusals:
            refusals.append(fn)
            raise RuntimeError("injected compile refusal")
        return real_invoke(self, fn, *args)

    monkeypatch.setattr(
        interp._SelfCheckRunner, "_invoke", refuse_first_candidate
    )
    rng = np.random.default_rng(5)
    args = {"x": rng.normal(size=(3, 4)), "w": rng.normal(size=(4, 2))}
    comp = _dot_comp(args)
    rt = LocalMooseRuntime(["alice", "bob", "carole"], use_jit=True)
    for expected_state in ("validating", "jit"):
        (got,) = rt.evaluate_computation(comp, arguments=args).values()
        np.testing.assert_allclose(got, args["x"] @ args["w"], atol=1e-5)
        assert rt.last_plan["plan_state"] == expected_state
        # cumulative: the retry's clean run does not erase the record
        (error,) = rt.last_plan["run_errors"]
        assert error.endswith("RuntimeError: injected compile refusal")


def test_results_equal_is_exact(dot_setup):
    comp, args, _ = dot_setup
    runner = interp._SelfCheckRunner(comp, args, checks=1)
    dyn = _dyn(runner, args)
    ref = runner._with_nonces(runner._ref_fn, _mk(30), dyn)
    assert interp._results_equal(ref, ref)
    outputs, saves = ref
    bumped = {
        k: type(v)(np.asarray(v.value) + 1e-9, v.plc, v.dtype)
        if hasattr(v, "value") else v
        for k, v in outputs.items()
    }
    assert not interp._results_equal((bumped, saves), ref)


def test_selfcheck_runs_env(monkeypatch):
    monkeypatch.setenv("MOOSE_TPU_JIT_SELFCHECK", "5")
    assert interp._selfcheck_runs() == 5
    monkeypatch.setenv("MOOSE_TPU_JIT_SELFCHECK", "0")
    assert interp._selfcheck_runs() == 0
    monkeypatch.setenv("MOOSE_TPU_JIT_SELFCHECK", "nope")
    from moose_tpu.errors import ConfigurationError

    with pytest.raises(ConfigurationError):
        interp._selfcheck_runs()


# ---------------------------------------------------------------------------
# Physical (lowered-graph) self-check — the path heavy graphs actually
# take under LocalMooseRuntime's auto-lowering
# ---------------------------------------------------------------------------


def _lowered_dot_setup():
    from moose_tpu.compilation import DEFAULT_PASSES, compile_computation
    from moose_tpu.compilation.lowering import arg_specs_from_arguments

    rng = np.random.default_rng(33)
    x = rng.normal(size=(3, 4))
    w = rng.normal(size=(4, 2))
    args = {"x": x, "w": w}

    alice = pm.host_placement("alice")
    bob = pm.host_placement("bob")
    carole = pm.host_placement("carole")
    rep = pm.replicated_placement("rep", players=[alice, bob, carole])

    @pm.computation
    def comp(
        x: pm.Argument(placement=alice, dtype=pm.float64),
        w: pm.Argument(placement=bob, dtype=pm.float64),
    ):
        with alice:
            xf = pm.cast(x, dtype=pm.fixed(14, 23))
        with bob:
            wf = pm.cast(w, dtype=pm.fixed(14, 23))
        with rep:
            y = pm.dot(xf, wf)
        with carole:
            out = pm.cast(y, dtype=pm.float64)
        return out

    passes = [p for p in DEFAULT_PASSES if p != "networking"]
    lowered = compile_computation(
        tracer.trace(comp), passes,
        arg_specs=arg_specs_from_arguments(args),
    )
    return lowered, args, x @ w


def test_physical_selfcheck_promotes_and_is_exact():
    from moose_tpu.execution import physical

    comp, args, want = _lowered_dot_setup()
    runner = interp._SelfCheckRunner(
        comp, args, checks=2,
        builder=physical._physical_plan_builder, pin_nonces=False,
    )
    assert runner.mode == "validating"

    order, key_ops, dyn_names, static_env, _ = runner.eager_plan
    dyn = {n: np.asarray(args[n]) for n in dyn_names}

    def fresh_keys(i):
        return {
            n: np.arange(4, dtype=np.uint32) + 100 + i for n in key_ops
        }

    out1, _ = runner.run(fresh_keys(0), dyn)
    assert runner.mode == "validating"
    out2, _ = runner.run(fresh_keys(1), dyn)
    assert runner.mode == "jit"
    out3, _ = runner.run(fresh_keys(2), dyn)

    for out in (out1, out2, out3):
        (val,) = [interp._to_user_value(v) for v in out.values()]
        np.testing.assert_allclose(np.asarray(val), want, atol=1e-5)


def test_physical_selfcheck_demotes_on_corruption():
    from moose_tpu.execution import physical

    comp, args, want = _lowered_dot_setup()
    runner = interp._SelfCheckRunner(
        comp, args, checks=1,
        builder=physical._physical_plan_builder, pin_nonces=False,
    )
    order, key_ops, dyn_names, static_env, _ = runner.eager_plan
    dyn = {n: np.asarray(args[n]) for n in dyn_names}
    keys = {n: np.arange(4, dtype=np.uint32) + 7 for n in key_ops}

    real_jit = runner._jit_fn

    def corrupted(ks, d):
        outputs, saves = real_jit(ks, d)
        bad = {
            k: type(v)(np.asarray(v.value) + 5e13, v.plc, v.dtype)
            if hasattr(v, "value") else v
            for k, v in outputs.items()
        }
        return bad, saves

    runner._jit_fn = corrupted
    out, _ = runner.run(keys, dyn)
    (val,) = [interp._to_user_value(v) for v in out.values()]
    np.testing.assert_allclose(np.asarray(val), want, atol=1e-5)
    assert runner.mode == "validating"
    assert runner._level == 1


# ---------------------------------------------------------------------------
# Per-op rung: after the 50-op rung fails, every op becomes its own
# validated XLA program and only the divergent ops are pinned eager.
# The MOOSE_TPU_SELFCHECK_FAULT hook injects the divergence (the real
# miscompile cannot reproduce on CPU).
# ---------------------------------------------------------------------------


def _mul_add_comp():
    """One Mul (the faulted op) plus one Add on the replicated
    placement — small protocol circuits so the ladder's repeated
    whole-graph compiles stay cheap on CPU."""
    alice = pm.host_placement("alice")
    bob = pm.host_placement("bob")
    carole = pm.host_placement("carole")
    rep = pm.replicated_placement("rep", players=[alice, bob, carole])

    @pm.computation
    def comp(
        x: pm.Argument(placement=alice, dtype=pm.float64),
        w: pm.Argument(placement=bob, dtype=pm.float64),
    ):
        with alice:
            xf = pm.cast(x, dtype=pm.fixed(8, 17))
        with bob:
            wf = pm.cast(w, dtype=pm.fixed(8, 17))
        with rep:
            y = pm.add(pm.mul(xf, wf), xf)
        with carole:
            out = pm.cast(y, dtype=pm.float64)
        return out

    return tracer.trace(comp)


def _drive_to_steady_state(runner, dyn, key_fn, max_runs=12):
    outs = []
    for i in range(max_runs):
        if runner.mode != "validating":
            break
        out, _ = runner.run(key_fn(i), dyn)
        outs.append(out)
    return outs


def test_per_op_rung_pins_exactly_the_faulted_op(monkeypatch):
    monkeypatch.setenv("MOOSE_TPU_SELFCHECK_FAULT", "Mul")
    rng = np.random.default_rng(5)
    x = rng.normal(size=(4, 3)) * 0.5
    w = rng.normal(size=(4, 3)) * 0.5
    args = {"x": x, "w": w}
    want = x * w + x
    comp = _mul_add_comp()
    mul_ops = sorted(
        n for n, op in comp.operations.items() if op.kind == "Mul"
    )
    assert len(mul_ops) == 1

    runner = interp._SelfCheckRunner(comp, args, checks=1)
    dyn = _dyn(runner, args)
    outs = _drive_to_steady_state(runner, dyn, lambda i: _mk(40 + i))

    # the whole-graph, 200-op and 50-op rungs all carry the injected
    # fault, so the ladder must land on the per-op rung with EXACTLY
    # the faulted op pinned eager and everything else (the Add included)
    # jitted
    assert runner.mode == "per-op"
    assert runner.plan_mode == "per-op"
    assert runner.pinned_ops == mul_ops

    out, _ = runner.run(_mk(99), dyn)  # steady-state mixed execution
    for o in outs + [out]:
        np.testing.assert_allclose(_decode_outputs(o), want, atol=5e-3)

    # the resolved plan is registered weak-keyed on the computation:
    # a NEW runner (fresh runtime/binding) restores the promotion and
    # the pinned set instead of re-diverging through the ladder
    runner2 = interp._SelfCheckRunner(comp, args, checks=1)
    assert runner2.mode == "per-op"
    assert runner2.pinned_ops == mul_ops
    out2, _ = runner2.run(_mk(120), _dyn(runner2, args))
    np.testing.assert_allclose(_decode_outputs(out2), want, atol=5e-3)


def _lowered_mul_setup():
    from moose_tpu.compilation import DEFAULT_PASSES, compile_computation
    from moose_tpu.compilation.lowering import arg_specs_from_arguments

    rng = np.random.default_rng(44)
    x = rng.normal(size=(3, 2))
    w = rng.normal(size=(3, 2))
    args = {"x": x, "w": w}

    alice = pm.host_placement("alice")
    bob = pm.host_placement("bob")
    carole = pm.host_placement("carole")
    rep = pm.replicated_placement("rep", players=[alice, bob, carole])

    @pm.computation
    def comp(
        x: pm.Argument(placement=alice, dtype=pm.float64),
        w: pm.Argument(placement=bob, dtype=pm.float64),
    ):
        with alice:
            xf = pm.cast(x, dtype=pm.fixed(8, 17))
        with bob:
            wf = pm.cast(w, dtype=pm.fixed(8, 17))
        with rep:
            y = pm.mul(xf, wf)
        with carole:
            out = pm.cast(y, dtype=pm.float64)
        return out

    passes = [p for p in DEFAULT_PASSES if p != "networking"]
    lowered = compile_computation(
        tracer.trace(comp), passes,
        arg_specs=arg_specs_from_arguments(args),
    )
    return lowered, args, x * w


def test_physical_per_op_rung_is_bit_exact_with_pinned_op(monkeypatch):
    """Acceptance: under injected single-op divergence exactly one op is
    pinned eager and end-to-end outputs stay bit-exact vs the all-eager
    reference (physical plans are fully deterministic given keys)."""
    from moose_tpu.execution import physical

    comp, args, want = _lowered_mul_setup()
    neg_ops = sorted(
        n for n, op in comp.operations.items() if op.kind == "Neg"
    )
    assert len(neg_ops) == 1  # the faulted kind appears exactly once

    monkeypatch.setenv("MOOSE_TPU_SELFCHECK_FAULT", "Neg")
    runner = interp._SelfCheckRunner(
        comp, args, checks=1,
        builder=physical._physical_plan_builder, pin_nonces=False,
        per_op_builder=physical._physical_per_op_builder,
        plan_key="physical",
    )
    order, key_ops, dyn_names, static_env, _ = runner.eager_plan
    dyn = {n: np.asarray(args[n]) for n in dyn_names}

    def keys(i):
        return {
            n: np.arange(4, dtype=np.uint32) + 50 + i for n in key_ops
        }

    _drive_to_steady_state(runner, dyn, keys)
    assert runner.mode == "per-op"
    assert runner.pinned_ops == neg_ops

    # bit-exactness: the mixed per-op plan from keys K must equal the
    # whole-graph all-eager reference from the SAME K, bit for bit
    k = keys(99)
    mixed = runner.run(k, dyn)
    ref = runner._eager_fn(k, dyn)
    assert interp._results_equal(mixed, ref)
    (val,) = [interp._to_user_value(v) for v in ref[0].values()]
    np.testing.assert_allclose(np.asarray(val), want, atol=1e-4)


def test_small_graph_promotes_to_segmented_via_runtime(monkeypatch):
    """The validated-jit path promotes a clean (fault-free) lowered
    graph to segmented jit and the runtime surfaces `plan_mode` —
    cheap companion of the >2000-op acceptance test below."""
    from moose_tpu.runtime import LocalMooseRuntime

    monkeypatch.setenv("MOOSE_TPU_SELFCHECK_FORCE", "1")
    monkeypatch.setenv("MOOSE_TPU_JIT_SEGMENT", "50")
    comp, args, want = _lowered_mul_setup()  # 123 ops -> 3 segments
    rt = LocalMooseRuntime(["alice", "bob", "carole"], use_jit=True)
    for _ in range(3):  # 2 validating runs (K=2 default) + 1 jitted
        (got,) = rt.evaluate_computation(comp, arguments=args).values()
        np.testing.assert_allclose(np.asarray(got), want, atol=1e-4)
    assert rt.last_plan["plan_mode"] == "segmented"
    assert rt.last_plan.get("plan_state") == "jit"
    assert rt.last_plan["pinned_ops"] == []


@pytest.mark.slow
def test_big_lowered_graph_promotes_to_segmented_on_cpu(monkeypatch):
    """Acceptance: on CPU (no miscompile), a >2000-op lowered protocol
    graph promotes past the self-check to segmented jit and `plan_mode`
    reports it."""
    from moose_tpu.compilation import DEFAULT_PASSES, compile_computation
    from moose_tpu.compilation.lowering import arg_specs_from_arguments
    from moose_tpu.runtime import LocalMooseRuntime

    monkeypatch.setenv("MOOSE_TPU_SELFCHECK_FORCE", "1")
    rng = np.random.default_rng(8)
    x = rng.normal(size=(4, 3)) * 0.5
    w = rng.normal(size=(3, 1)) * 0.5
    args = {"x": x, "w": w}
    want = 1.0 / (1.0 + np.exp(-(x @ w)))

    alice = pm.host_placement("alice")
    bob = pm.host_placement("bob")
    carole = pm.host_placement("carole")
    rep = pm.replicated_placement("rep", players=[alice, bob, carole])

    @pm.computation
    def comp(
        x: pm.Argument(placement=alice, dtype=pm.float64),
        w: pm.Argument(placement=bob, dtype=pm.float64),
    ):
        with alice:
            xf = pm.cast(x, dtype=pm.fixed(8, 17))
        with bob:
            wf = pm.cast(w, dtype=pm.fixed(8, 17))
        with rep:
            y = pm.sigmoid(pm.dot(xf, wf))
        with carole:
            out = pm.cast(y, dtype=pm.float64)
        return out

    passes = [p for p in DEFAULT_PASSES if p != "networking"]
    lowered = compile_computation(
        tracer.trace(comp), passes,
        arg_specs=arg_specs_from_arguments(args),
    )
    assert len(lowered.operations) > 2000

    rt = LocalMooseRuntime(["alice", "bob", "carole"], use_jit=True)
    for _ in range(3):  # 2 validating runs (K=2 default) + 1 jitted
        (got,) = rt.evaluate_computation(lowered, arguments=args).values()
        np.testing.assert_allclose(np.asarray(got), want, atol=5e-3)
    assert rt.last_plan["plan_mode"] == "segmented"
    assert rt.last_plan.get("plan_state") == "jit"
    assert rt.last_plan["pinned_ops"] == []


def test_per_op_limit_skips_rung_to_eager(monkeypatch):
    """Plans above MOOSE_TPU_PEROP_MAX skip the per-op rung: exhausting
    the segment rungs pins eager (and flags `exhausted` for the
    runtime's cross-layout reroute)."""
    monkeypatch.setenv("MOOSE_TPU_SELFCHECK_FAULT", "Mul")
    monkeypatch.setenv("MOOSE_TPU_PEROP_MAX", "2")
    rng = np.random.default_rng(6)
    args = {"x": rng.normal(size=(2, 2)), "w": rng.normal(size=(2, 2))}
    comp = _mul_add_comp()
    runner = interp._SelfCheckRunner(comp, args, checks=1)
    dyn = _dyn(runner, args)
    _drive_to_steady_state(runner, dyn, lambda i: _mk(70 + i))
    assert runner.mode == "eager"
    assert runner.exhausted


def test_physical_per_op_rung_chunks_above_cap(monkeypatch):
    """Lowered plans above MOOSE_TPU_PEROP_MAX no longer pin whole-plan
    eager on ladder exhaustion: the per-op
    rung falls back to validating/pinning segment-sized CHUNKS, so only
    the chunks containing the divergent op go eager and the rest stay
    jitted."""
    from moose_tpu.execution import physical

    comp, args, want = _lowered_mul_setup()  # 123 ops -> 3 50-op chunks
    neg_chunk_heads = set()
    order = comp.toposort_names()
    for i in range(0, len(order), 50):
        chunk = order[i:i + 50]
        if any(comp.operations[n].kind == "Neg" for n in chunk):
            neg_chunk_heads.add(chunk[0])
    assert len(neg_chunk_heads) == 1  # the faulted kind sits in 1 chunk

    monkeypatch.setenv("MOOSE_TPU_SELFCHECK_FAULT", "Neg")
    monkeypatch.setenv("MOOSE_TPU_PEROP_MAX", "10")  # 123 ops > cap
    runner = interp._SelfCheckRunner(
        comp, args, checks=1,
        builder=physical._physical_plan_builder, pin_nonces=False,
        per_op_builder=physical._physical_per_op_builder,
        plan_key="physical",
    )
    order_, key_ops, dyn_names, static_env, _ = runner.eager_plan
    dyn = {n: np.asarray(args[n]) for n in dyn_names}

    def keys(i):
        return {
            n: np.arange(4, dtype=np.uint32) + 60 + i for n in key_ops
        }

    _drive_to_steady_state(runner, dyn, keys)
    # the ladder lands on the (chunked) per-op rung, NOT whole-plan
    # eager, with exactly the Neg-carrying chunk pinned
    assert runner.mode == "per-op"
    assert not runner.exhausted
    assert runner._per_op.seg_size == 50
    assert runner.pinned_ops == sorted(neg_chunk_heads)
    assert not runner._per_op.all_pinned()

    # bit-exactness of the mixed chunked plan vs the all-eager
    # reference from the SAME keys
    k = keys(99)
    mixed = runner.run(k, dyn)
    ref = runner._eager_fn(k, dyn)
    assert interp._results_equal(mixed, ref)
    (val,) = [interp._to_user_value(v) for v in ref[0].values()]
    np.testing.assert_allclose(np.asarray(val), want, atol=1e-4)
