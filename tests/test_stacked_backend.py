"""User graphs on the party-stacked SPMD backend (VERDICT r4 #1).

The SAME traced/``from_onnx`` computations that run on the per-host
logical dialect execute on ``LocalMooseRuntime(layout="stacked")``
through ``dialects/stacked.py``, which maps replicated ops onto the
``parallel/spmd*`` kernels.  Cross-layout equivalence discipline follows
``tests/test_spmd.py``: exact ring ops (share/add/reveal) must agree
bit-for-bit; protocols with probabilistic truncation agree within the
2^-f trunc tolerance.
"""

import numpy as np
import pytest

import moose_tpu as pm
from moose_tpu.parallel import spmd
from moose_tpu.runtime import LocalMooseRuntime


def _players():
    alice = pm.host_placement("alice")
    bob = pm.host_placement("bob")
    carole = pm.host_placement("carole")
    rep = pm.replicated_placement("rep", players=[alice, bob, carole])
    return alice, bob, carole, rep


def _logreg_comp(fx_dtype):
    alice, bob, carole, rep = _players()

    @pm.computation
    def comp(
        x: pm.Argument(placement=alice, dtype=pm.float64),
        w: pm.Argument(placement=bob, dtype=pm.float64),
    ):
        with alice:
            x_f = pm.cast(x, dtype=fx_dtype)
        with bob:
            w_f = pm.cast(w, dtype=fx_dtype)
        with rep:
            y = pm.sigmoid(pm.dot(x_f, w_f))
        with carole:
            y_host = pm.cast(y, dtype=pm.float64)
        return y_host

    return comp


@pytest.mark.parametrize("fx_dtype", [pm.fixed(8, 27), pm.fixed(14, 23)],
                         ids=["fixed64", "fixed128"])
def test_traced_logreg_stacked_matches_per_host(fx_dtype):
    comp = _logreg_comp(fx_dtype)
    rng = np.random.default_rng(0)
    x = rng.normal(size=(16, 4)) * 0.5
    w = rng.normal(size=(4, 1)) * 0.5
    args = {"x": x, "w": w}
    want = 1.0 / (1.0 + np.exp(-(x @ w)))

    rt_h = LocalMooseRuntime(["alice", "bob", "carole"])
    (got_h,) = rt_h.evaluate_computation(comp, arguments=args).values()
    rt_s = LocalMooseRuntime(["alice", "bob", "carole"], layout="stacked")
    assert rt_s.layout == "stacked"
    (got_s,) = rt_s.evaluate_computation(comp, arguments=args).values()

    np.testing.assert_allclose(np.asarray(got_s), want, atol=1e-3)
    # both backends approximate the same protocol; difference is bounded
    # by the probabilistic-truncation tolerance
    np.testing.assert_allclose(
        np.asarray(got_s), np.asarray(got_h), atol=1e-4
    )


def test_linear_graph_bit_identical_across_layouts():
    """Share/add/sub/reveal has no truncation and no randomness in the
    revealed value: the two layouts must agree bit-for-bit."""
    alice, bob, carole, rep = _players()
    fx_dtype = pm.fixed(14, 23)

    @pm.computation
    def comp(
        x: pm.Argument(placement=alice, dtype=pm.float64),
        y: pm.Argument(placement=bob, dtype=pm.float64),
    ):
        with alice:
            x_f = pm.cast(x, dtype=fx_dtype)
        with bob:
            y_f = pm.cast(y, dtype=fx_dtype)
        with rep:
            z = pm.add(x_f, pm.sub(x_f, y_f))
        with carole:
            out = pm.cast(z, dtype=pm.float64)
        return out

    rng = np.random.default_rng(3)
    x = rng.normal(size=(8, 3))
    y = rng.normal(size=(8, 3))
    args = {"x": x, "y": y}
    rt_h = LocalMooseRuntime(["alice", "bob", "carole"])
    (got_h,) = rt_h.evaluate_computation(comp, arguments=args).values()
    rt_s = LocalMooseRuntime(["alice", "bob", "carole"], layout="stacked")
    (got_s,) = rt_s.evaluate_computation(comp, arguments=args).values()
    np.testing.assert_array_equal(np.asarray(got_h), np.asarray(got_s))


def test_negative_axis_matches_per_host():
    """axis=-1 must hit the last LOGICAL axis, not the share-slot axis
    (code-review r5 finding: a bare +2 offset mapped negative axes onto
    the pair layout, silently corrupting results)."""
    alice, bob, carole, rep = _players()
    fx_dtype = pm.fixed(14, 23)

    @pm.computation
    def comp(x: pm.Argument(placement=alice, dtype=pm.float64)):
        with alice:
            x_f = pm.cast(x, dtype=fx_dtype)
        with rep:
            s = pm.sum(x_f, axis=-1)
        with carole:
            return pm.cast(s, dtype=pm.float64)

    x = np.array([[1.0, 2.0], [3.0, 4.0], [5.0, 7.0]])
    rt_h = LocalMooseRuntime(["alice", "bob", "carole"])
    (got_h,) = rt_h.evaluate_computation(comp, arguments={"x": x}).values()
    rt_s = LocalMooseRuntime(["alice", "bob", "carole"], layout="stacked")
    (got_s,) = rt_s.evaluate_computation(comp, arguments={"x": x}).values()
    np.testing.assert_allclose(np.asarray(got_h), x.sum(axis=-1), atol=1e-6)
    np.testing.assert_array_equal(np.asarray(got_h), np.asarray(got_s))


def test_stacked_aes_decrypt_via_runtime():
    """Encrypted-input inference reaches the stacked AES path through
    the runtime (supports() must admit rep-placed Input ops)."""
    from moose_tpu.dialects import aes
    from moose_tpu.dialects import stacked as stacked_dialect
    from moose_tpu.edsl import tracer

    alice, bob, carole, rep = _players()
    FIXED = pm.fixed(14, 23)

    @pm.computation
    def secure_score(
        aes_data: pm.Argument(placement=alice,
                              vtype=pm.AesTensorType(dtype=FIXED)),
        aes_key: pm.Argument(placement=rep, vtype=pm.AesKeyType()),
    ):
        with rep:
            x = pm.decrypt(aes_key, aes_data)
        with carole:
            return pm.cast(x, dtype=pm.float64)

    traced = tracer.trace(secure_score)
    assert stacked_dialect.supports(traced)

    rng = np.random.default_rng(3)
    values = rng.normal(size=(2, 2))
    key = bytes(range(16))
    nonce = bytes([9] * 12)
    wire = aes.encrypt_fixed_array(key, nonce, values, frac_precision=23)
    rt = LocalMooseRuntime(
        ["alice", "bob", "carole"], layout="stacked", use_jit=True
    )
    (out,) = rt.evaluate_computation(
        secure_score,
        arguments={
            "aes_data": np.asarray(wire),
            "aes_key": np.asarray(aes.bytes_to_bits_be(key)),
        },
    ).values()
    np.testing.assert_allclose(np.asarray(out), values, atol=2e-6)


def test_traced_softmax_argmax_stacked():
    alice, bob, carole, rep = _players()
    fx_dtype = pm.fixed(8, 27)

    @pm.computation
    def comp(x: pm.Argument(placement=alice, dtype=pm.float64)):
        with alice:
            x_f = pm.cast(x, dtype=fx_dtype)
        with rep:
            s = pm.softmax(x_f, axis=1, upmost_index=4)
            a = pm.argmax(x_f, axis=1, upmost_index=4)
        with carole:
            s_out = pm.cast(s, dtype=pm.float64)
            a_out = pm.cast(a, dtype=pm.uint64)
        return s_out, a_out

    rng = np.random.default_rng(1)
    x = rng.normal(size=(6, 4)) * 2.0
    want_s = np.exp(x - x.max(1, keepdims=True))
    want_s /= want_s.sum(1, keepdims=True)

    rt = LocalMooseRuntime(["alice", "bob", "carole"], layout="stacked")
    out = rt.evaluate_computation(comp, arguments={"x": x})
    vals = list(out.values())
    s, a = np.asarray(vals[0]), np.asarray(vals[1])
    np.testing.assert_allclose(s, want_s, atol=5e-2)
    np.testing.assert_array_equal(a, x.argmax(1))


def test_onnx_logreg_stacked_matches_sklearn_and_per_host():
    sklearn = pytest.importorskip("sklearn")
    from sklearn import linear_model

    import onnx_fixtures as fx
    from moose_tpu import predictors

    rng = np.random.default_rng(1234)
    x = rng.normal(size=(60, 4))
    y = rng.integers(0, 2, size=60)
    x += 0.8 * np.eye(4)[y % 4]
    sk = linear_model.LogisticRegression(max_iter=300).fit(x, y)
    onnx_model = fx.logistic_regression_onnx(sk, x.shape[1])
    model = predictors.from_onnx(onnx_model)
    comp = model.predictor_factory()
    args = {"x": np.asarray(x[:8], dtype=np.float64)}

    rt_s = LocalMooseRuntime(["alice", "bob", "carole"], layout="stacked")
    (got_s,) = rt_s.evaluate_computation(comp, arguments=args).values()
    np.testing.assert_allclose(
        np.asarray(got_s), sk.predict_proba(x[:8]), atol=5e-3
    )
    rt_h = LocalMooseRuntime(["alice", "bob", "carole"])
    (got_h,) = rt_h.evaluate_computation(comp, arguments=args).values()
    np.testing.assert_allclose(
        np.asarray(got_s), np.asarray(got_h), atol=1e-4
    )


def test_onnx_forest_stacked_matches_sklearn_and_per_host():
    """Tree-ensemble predictor on the party-stacked backend: the
    oblivious tree walk exercises Less/Mux/Concat — kinds that sit in
    ``_REP_KINDS`` but were previously untested on this layout (VERDICT
    r5 "What's weak" #3) — end to end against sklearn and the per-host
    path."""
    sklearn = pytest.importorskip("sklearn")
    from sklearn import ensemble

    import onnx_fixtures as fx
    from moose_tpu import predictors
    from moose_tpu.dialects import stacked as stacked_dialect
    from moose_tpu.edsl import tracer

    rng = np.random.default_rng(21)
    x = rng.normal(size=(80, 5))
    y = (x[:, 0] + 0.5 * x[:, 1] > 0).astype(int)
    sk = ensemble.RandomForestClassifier(
        n_estimators=3, max_depth=3, random_state=0
    ).fit(x, y)
    onnx_model = fx.random_forest_classifier_onnx(sk, x.shape[1])
    model = predictors.from_onnx(onnx_model)
    comp = model.predictor_factory()
    args = {"x": np.asarray(x[:6], dtype=np.float64)}

    # the stacked dialect must CLAIM this graph (otherwise the runtime
    # silently falls back per-host and the kinds stay unexercised)
    traced = tracer.trace(comp)
    assert stacked_dialect.supports(traced), (
        "forest predictor graph no longer supported by the stacked "
        "backend"
    )
    rt_s = LocalMooseRuntime(["alice", "bob", "carole"], layout="stacked")
    (got_s,) = rt_s.evaluate_computation(comp, arguments=args).values()
    assert rt_s.last_plan.get("layout") == "stacked", rt_s.last_plan
    np.testing.assert_allclose(
        np.asarray(got_s), sk.predict_proba(x[:6]), atol=1e-3
    )
    rt_h = LocalMooseRuntime(["alice", "bob", "carole"])
    (got_h,) = rt_h.evaluate_computation(comp, arguments=args).values()
    np.testing.assert_allclose(
        np.asarray(got_s), np.asarray(got_h), atol=1e-4
    )


def test_stacked_on_party_mesh():
    """The stacked backend shards over a real (parties=3, data) mesh: the
    conftest's 12 virtual CPU devices give a (3, 4) mesh, and the user
    graph still produces correct results under the sharding constraint."""
    import jax

    if len(jax.devices()) < 3:
        pytest.skip("needs >= 3 devices")
    mesh = spmd.make_mesh(min(12, len(jax.devices())))
    comp = _logreg_comp(pm.fixed(14, 23))
    rng = np.random.default_rng(7)
    x = rng.normal(size=(16, 4)) * 0.5
    w = rng.normal(size=(4, 1)) * 0.5
    want = 1.0 / (1.0 + np.exp(-(x @ w)))
    rt = LocalMooseRuntime(
        ["alice", "bob", "carole"], layout="stacked", mesh=mesh
    )
    (got,) = rt.evaluate_computation(
        comp, arguments={"x": x, "w": w}
    ).values()
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-3)


def test_resnet_block_onnx_stacked_matches_per_host():
    """Encrypted convnet inference (Conv2D + pooling + relu + residual
    skips + softmax head) through from_onnx on the stacked backend;
    the per-host result is itself float-reference-validated in
    tests/test_conv.py, so cross-layout agreement pins both."""
    from moose_tpu import predictors
    from moose_tpu.predictors.sklearn_export import resnet_block_onnx

    model_proto, _ = resnet_block_onnx(
        seed=3, in_ch=2, mid_ch=3, size=6, n_classes=2
    )
    model = predictors.from_onnx(model_proto.encode())
    assert isinstance(model, predictors.ConvNet)
    comp = model.predictor_factory(fixedpoint_dtype=pm.fixed(24, 40))
    rng = np.random.default_rng(11)
    x = rng.normal(size=(2, 2, 6, 6)) * 0.5  # NCHW like the export
    args = {"x": x}

    rt_s = LocalMooseRuntime(["alice", "bob", "carole"], layout="stacked")
    (got_s,) = rt_s.evaluate_computation(comp, arguments=args).values()
    rt_h = LocalMooseRuntime(["alice", "bob", "carole"])
    (got_h,) = rt_h.evaluate_computation(comp, arguments=args).values()
    np.testing.assert_allclose(
        np.asarray(got_s), np.asarray(got_h), atol=2e-3
    )
    # probabilities: rows sum to 1
    np.testing.assert_allclose(
        np.asarray(got_s).sum(axis=1), 1.0, atol=1e-2
    )


def test_unsupported_graph_falls_back_to_per_host():
    """Graphs with replicated ops outside the stacked dialect's coverage
    still run (per-host fallback), so layout='stacked' is always safe."""
    from moose_tpu.dialects import stacked as stacked_dialect
    from moose_tpu.edsl import tracer

    alice, bob, carole, rep = _players()
    fx_dtype = pm.fixed(8, 27)

    @pm.computation
    def comp(x: pm.Argument(placement=alice, dtype=pm.float64)):
        with alice:
            x_f = pm.cast(x, dtype=fx_dtype)
            mask = pm.constant(
                np.array([True, False, True]), dtype=pm.bool_
            )
        with rep:
            y = pm.mul(x_f, x_f)
        with carole:
            y_h = pm.cast(y, dtype=pm.float64)
            out = pm.select(y_h, 0, mask)
        return out

    traced = tracer.trace(comp)
    assert not stacked_dialect.supports(traced)  # Select is dynamic-shape
    x = np.array([1.0, 2.0, 3.0])
    rt = LocalMooseRuntime(["alice", "bob", "carole"], layout="stacked")
    (got,) = rt.evaluate_computation(comp, arguments={"x": x}).values()
    np.testing.assert_allclose(
        np.asarray(got), [1.0, 9.0], atol=1e-3
    )  # executed via the per-host fallback


# ---------------------------------------------------------------------------
# Cross-layout demotion routing + per-op ladder surfacing (ISSUE 2)
# ---------------------------------------------------------------------------


def _linear_comp():
    alice, bob, carole, rep = _players()
    fx_dtype = pm.fixed(14, 23)

    @pm.computation
    def comp(
        x: pm.Argument(placement=alice, dtype=pm.float64),
        y: pm.Argument(placement=bob, dtype=pm.float64),
    ):
        with alice:
            x_f = pm.cast(x, dtype=fx_dtype)
        with bob:
            y_f = pm.cast(y, dtype=fx_dtype)
        with rep:
            z = pm.add(x_f, pm.sub(x_f, y_f))
        with carole:
            out = pm.cast(z, dtype=pm.float64)
        return out

    return comp


def test_stacked_ladder_exhaustion_reroutes_to_per_host(monkeypatch):
    """Acceptance: LocalMooseRuntime(layout='stacked') never settles on
    a plan slower than the per-host route — ladder exhaustion reroutes
    instead of pinning stacked-eager, preserving outputs bit-for-bit
    (the linear graph is exact, so the layouts agree exactly)."""
    monkeypatch.setenv("MOOSE_TPU_SELFCHECK_FORCE", "1")
    monkeypatch.setenv("MOOSE_TPU_JIT_SELFCHECK", "1")
    comp = _linear_comp()
    rng = np.random.default_rng(9)
    args = {"x": rng.normal(size=(8, 3)), "y": rng.normal(size=(8, 3))}

    rt = LocalMooseRuntime(
        ["alice", "bob", "carole"], layout="stacked", use_jit=True
    )
    (got1,) = rt.evaluate_computation(comp, arguments=args).values()
    assert rt.last_plan.get("layout") == "stacked"

    # force ladder exhaustion through the plan registry, where the
    # stacked ladder's one clean run just recorded its promotion (the
    # real miscompile cannot reproduce on CPU)
    from moose_tpu.execution import interpreter as interp

    traced = rt._trace_cache[comp]
    state = interp._registry()[traced]["StackedDialect"]
    assert state["mode"] == "jit" and rt.last_plan["plan_state"] == "jit"
    state["mode"] = "eager"
    assert rt._stacked.plan_exhausted(traced, args)

    (got2,) = rt.evaluate_computation(comp, arguments=args).values()
    assert rt.last_plan.get("layout") == "per-host"  # rerouted
    assert rt.last_plan.get("plan_mode") is not None
    np.testing.assert_array_equal(np.asarray(got1), np.asarray(got2))


def test_stacked_userpath_per_op_plan_mode_via_runtime(monkeypatch):
    """The full user path under a single divergent op: the runtime
    surfaces the resolved per-op plan (`plan_mode`, pinned op names)
    through last_timings/last_plan, and results stay correct at every
    ladder stage."""
    monkeypatch.setenv("MOOSE_TPU_SELFCHECK_FORCE", "1")
    monkeypatch.setenv("MOOSE_TPU_SELFCHECK_FAULT", "Mul")
    monkeypatch.setenv("MOOSE_TPU_JIT_SELFCHECK", "1")
    alice, bob, carole, rep = _players()

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

    rng = np.random.default_rng(2)
    x = rng.normal(size=(4, 3)) * 0.5
    w = rng.normal(size=(4, 3)) * 0.5
    args = {"x": x, "w": w}
    want = x * w + x

    rt = LocalMooseRuntime(
        ["alice", "bob", "carole"], layout="stacked", use_jit=True
    )
    for _ in range(8):
        (got,) = rt.evaluate_computation(comp, arguments=args).values()
        np.testing.assert_allclose(np.asarray(got), want, atol=5e-3)
        if rt.last_plan.get("plan_state") == "per-op":
            break
    assert rt.last_plan["plan_mode"] == "per-op"
    traced = rt._trace_cache[comp]
    pinned = rt.last_plan["pinned_ops"]
    assert [traced.operations[n].kind for n in pinned] == ["Mul"]
    assert rt.last_plan.get("layout") == "stacked"


def test_stacked_runtime_falls_back_on_typed_rejection():
    """A typed TypeMismatchError out of the stacked dialect (value shape
    supports() could not see) falls back to the per-host path instead of
    failing the evaluation, and later calls skip the stacked attempt."""
    from moose_tpu.errors import TypeMismatchError

    comp = _logreg_comp(pm.fixed(14, 23))
    rng = np.random.default_rng(0)
    x = rng.normal(size=(8, 4)) * 0.5
    w = rng.normal(size=(4, 1)) * 0.5
    args = {"x": x, "w": w}
    want = 1.0 / (1.0 + np.exp(-(x @ w)))

    rt = LocalMooseRuntime(["alice", "bob", "carole"], layout="stacked")

    def boom(*a, **k):
        raise TypeMismatchError("injected dispatch rejection")

    rt._stacked._dialect.execute_op = boom
    (got,) = rt.evaluate_computation(comp, arguments=args).values()
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-3)
    assert rt.last_plan.get("layout") == "per-host"
    traced = rt._trace_cache[comp]
    assert traced in rt._stacked_rejected
    # second call routes straight to per-host without re-raising
    (got2,) = rt.evaluate_computation(comp, arguments=args).values()
    np.testing.assert_allclose(np.asarray(got2), want, atol=1e-3)


def test_to_rep_integer_lift_width_follows_signature():
    """ADVICE r5 low #1: secret integer lifts pick their ring from the
    consuming op's signature instead of hard-coded 64."""
    import importlib

    C = importlib.import_module("moose_tpu.computation")
    from moose_tpu import dtypes as dt
    from moose_tpu.dialects import stacked as stacked_dialect
    from moose_tpu.values import HostTensor

    sess = stacked_dialect.StackedSession(
        np.arange(4, dtype=np.uint32) + 3
    )
    v = HostTensor(np.arange(6, dtype=np.uint64).reshape(2, 3),
                   "alice", dt.uint64)
    assert stacked_dialect.to_rep(sess, v).width == 64  # native default
    assert stacked_dialect.to_rep(sess, v, width=128).width == 128

    # the width derives from the op signature: fixed128 inputs/returns
    # force a 128-bit lift, fixed64 a 64-bit one
    op128 = C.Operation(
        name="c", kind="Cast", inputs=["a"], placement_name="rep",
        signature=C.signature(
            [C.tensor_ty(dt.uint64)], C.tensor_ty(dt.fixed128(14, 23))
        ),
    )
    assert stacked_dialect._op_ring_width(op128) == 128
    op64 = C.Operation(
        name="c", kind="Cast", inputs=["a"], placement_name="rep",
        signature=C.signature(
            [C.tensor_ty(dt.uint64)], C.tensor_ty(dt.fixed64(8, 17))
        ),
    )
    assert stacked_dialect._op_ring_width(op64) == 64

    # float tensors still cannot be shared — but now with a TYPED error
    from moose_tpu.errors import TypeMismatchError

    fv = HostTensor(np.ones((2, 2)), "alice", dt.float64)
    with pytest.raises(TypeMismatchError):
        stacked_dialect.to_rep(sess, fv)


def test_stacked_cast_int_to_fixed_lifts_at_target_ring():
    """Replicated Cast of a secret integer to a fixed dtype lifts at the
    TARGET ring (the ADVICE r5 low #1 scenario made workable), and a
    sharing already produced at another width is rejected with a typed
    error instead of silently relabelled."""
    import importlib

    C = importlib.import_module("moose_tpu.computation")
    from moose_tpu import dtypes as dt
    from moose_tpu.dialects import stacked as stacked_dialect
    from moose_tpu.errors import TypeMismatchError
    from moose_tpu.values import HostTensor

    sess = stacked_dialect.StackedSession(
        np.arange(4, dtype=np.uint32) + 11
    )
    rep = C.ReplicatedPlacement("rep", ("alice", "bob", "carole"))
    comp = C.Computation()
    fx128 = dt.fixed128(14, 23)
    op = C.Operation(
        name="c", kind="Cast", inputs=["a"], placement_name="rep",
        signature=C.signature(
            [C.tensor_ty(dt.uint64)], C.tensor_ty(fx128)
        ),
    )
    ints = np.array([[1, 2], [3, 40]], dtype=np.uint64)
    v = HostTensor(ints, "alice", dt.uint64)
    out = stacked_dialect._execute_rep(sess, comp, op, rep, [v])
    assert out.tensor.width == 128  # lifted at the target ring
    host = stacked_dialect.to_host(sess, "alice", out)
    from moose_tpu.dialects import host as host_ops

    decoded = np.asarray(
        host_ops.fixedpoint_decode(host, "alice").value
    )
    np.testing.assert_allclose(decoded, ints.astype(np.float64))

    # a sharing already at ring64 cannot be relabelled as fixed128
    r64 = stacked_dialect.to_rep(sess, v, width=64)
    with pytest.raises(TypeMismatchError):
        stacked_dialect._execute_rep(sess, comp, op, rep, [r64])


def test_supports_screens_dispatch_rejections():
    """ADVICE r5 low #2: graphs _execute_rep/to_rep would reject at
    dispatch time (float constants on replicated placements, non-fixed
    Cast targets, mixed secret integer/fixed arithmetic) are screened
    out by supports() so the runtime falls back up front."""
    import importlib

    C = importlib.import_module("moose_tpu.computation")
    from moose_tpu import dtypes as dt
    from moose_tpu.dialects import stacked as stacked_dialect

    def base_comp():
        comp = C.Computation()
        comp.add_placement(C.HostPlacement("alice"))
        comp.add_placement(C.HostPlacement("bob"))
        comp.add_placement(C.HostPlacement("carole"))
        comp.add_placement(
            C.ReplicatedPlacement("rep", ("alice", "bob", "carole"))
        )
        return comp

    f64 = C.tensor_ty(dt.float64)
    fx = C.tensor_ty(dt.fixed128(14, 23))
    u64 = C.tensor_ty(dt.uint64)

    # float Constant on the replicated placement: to_rep cannot share it
    comp = base_comp()
    comp.add_operation(C.Operation(
        name="c", kind="Constant", inputs=[], placement_name="rep",
        signature=C.signature([], f64),
        attributes={"value": np.ones((2, 2))},
    ))
    assert not stacked_dialect.supports(comp)

    # Cast to a non-fixed dtype on the replicated placement
    comp = base_comp()
    comp.add_operation(C.Operation(
        name="x", kind="Input", inputs=[], placement_name="alice",
        signature=C.signature([], fx),
    ))
    comp.add_operation(C.Operation(
        name="c", kind="Cast", inputs=["x"], placement_name="rep",
        signature=C.signature([fx], f64),
    ))
    assert not stacked_dialect.supports(comp)

    # mixed secret integer / fixed arithmetic has no stacked kernel
    comp = base_comp()
    comp.add_operation(C.Operation(
        name="a", kind="Input", inputs=[], placement_name="alice",
        signature=C.signature([], u64),
    ))
    comp.add_operation(C.Operation(
        name="b", kind="Input", inputs=[], placement_name="bob",
        signature=C.signature([], fx),
    ))
    comp.add_operation(C.Operation(
        name="m", kind="Mul", inputs=["a", "b"], placement_name="rep",
        signature=C.signature([u64, fx], fx),
    ))
    assert not stacked_dialect.supports(comp)

    # ...while the all-fixed equivalent stays supported
    comp = base_comp()
    comp.add_operation(C.Operation(
        name="a", kind="Input", inputs=[], placement_name="alice",
        signature=C.signature([], fx),
    ))
    comp.add_operation(C.Operation(
        name="b", kind="Input", inputs=[], placement_name="bob",
        signature=C.signature([], fx),
    ))
    comp.add_operation(C.Operation(
        name="m", kind="Mul", inputs=["a", "b"], placement_name="rep",
        signature=C.signature([fx, fx], fx),
    ))
    assert stacked_dialect.supports(comp)


def _gather_mux_comp():
    """A static gather of a fixed tensor and of a bit tensor, a mux
    between two mirrored constants (the local road) and one between
    secret branches, as the tree-ensemble predictor combines them."""
    alice, bob, carole, rep = _players()
    mir = pm.mirrored_placement("mir", players=[alice, bob, carole])
    fx_dtype = pm.fixed(14, 23)

    def public(values):
        return pm.cast(
            pm.constant(np.asarray(values), dtype=pm.float64, placement=mir),
            dtype=fx_dtype, placement=mir,
        )

    @pm.computation
    def comp(x: pm.Argument(placement=alice, dtype=pm.float64)):
        with alice:
            x_f = pm.cast(x, dtype=fx_dtype)
        with rep:
            g = pm.gather(x_f, axis=1, indices=(2, 0, 0, 1))
            bits = pm.less(g, public([0.0, 0.5, -0.5, 0.0]))
            local = pm.mux(
                pm.gather(bits, axis=1, indices=(0, 3)),
                public([1.5, -2.0]), public([-0.25, 4.0]),
            )
            secure = pm.mux(
                pm.gather(bits, axis=1, indices=(1, 2)),
                pm.gather(g, axis=1, indices=(3, 3)), local,
            )
            out = pm.concatenate([local, secure], axis=1)
        with carole:
            return pm.cast(out, dtype=pm.float64)

    return comp


def _gather_mux_expected(x):
    g = x[:, [2, 0, 0, 1]]
    bits = g < np.array([0.0, 0.5, -0.5, 0.0])
    local = np.where(bits[:, [0, 3]], [1.5, -2.0], [-0.25, 4.0])
    secure = np.where(bits[:, [1, 2]], g[:, [3, 3]], local)
    return np.concatenate([local, secure], axis=1)


@pytest.mark.parametrize(
    "road", ["stacked", "per-host", "lowered", "msgpack", "textual"]
)
def test_gather_and_public_mux_on_every_road(road):
    from moose_tpu.compilation import DEFAULT_PASSES, compile_computation
    from moose_tpu.compilation.lowering import arg_specs_from_arguments
    from moose_tpu.edsl import tracer
    from moose_tpu.execution.physical import execute_physical
    from moose_tpu.serde import deserialize_computation, serialize_computation
    from moose_tpu.textual import parse_computation, to_textual

    rng = np.random.default_rng(5)
    x = rng.normal(size=(6, 3))
    args = {"x": x}
    traced = tracer.trace(_gather_mux_comp())
    gathers = [
        op for op in traced.operations.values()
        if op.kind == "IndexAxis"
    ]
    assert [op.attributes["index"] for op in gathers] == [
        (2, 0, 0, 1), (0, 3), (1, 2), (3, 3),
    ]
    if road == "lowered":
        compiled = compile_computation(
            traced, DEFAULT_PASSES, arg_specs=arg_specs_from_arguments(args)
        )
        (got,) = execute_physical(compiled, {}, args, use_jit=False).values()
    else:
        if road == "msgpack":
            traced = deserialize_computation(serialize_computation(traced))
        elif road == "textual":
            traced = parse_computation(to_textual(traced))
        layout = "per-host" if road == "per-host" else "stacked"
        runtime = LocalMooseRuntime(["alice", "bob", "carole"], layout=layout)
        (got,) = runtime.evaluate_computation(traced, arguments=args).values()
        assert runtime.last_plan["layout"] == layout
        assert runtime.last_plan["ops"] == len(traced.operations)
    # no truncation anywhere on this path: exact to the encoding
    np.testing.assert_allclose(
        np.asarray(got), _gather_mux_expected(x), atol=2.0 ** -22
    )


def test_public_mux_pays_no_secure_multiplication():
    """``mux`` between mirrored constants draws for the selector's
    conversion only (two multiplications' zero shares); between secret
    branches it draws for a third."""
    import jax

    from moose_tpu.dialects import ring
    from moose_tpu.execution import drawledger
    from moose_tpu.parallel import spmd_math as sm

    sess = spmd.SpmdSession(jax.numpy.arange(4, dtype=jax.numpy.uint32))
    bits = sm.share_bits(sess, np.array([[1, 0, 1]], dtype=np.uint8))

    class Public:
        width = 128

        def __init__(self, value):
            self.lo, self.hi = ring.fill_like_shape((3,), 128, value)

    with drawledger.recording() as public_draws:
        out = sm.mux_bit_public(sess, bits, Public(7), Public(2))
    lo, _ = spmd.reveal(out)
    np.testing.assert_array_equal(np.asarray(lo), [[7, 2, 7]])
    x = spmd.public_to_rep(*ring.fill_like_shape((1, 3), 128, 7), 128)
    y = spmd.public_to_rep(*ring.fill_like_shape((1, 3), 128, 2), 128)
    with drawledger.recording() as secret_draws:
        sm.mux_bit(sess, bits, x, y)
    assert len(public_draws.stacked_trace()) == 2
    assert len(secret_draws.stacked_trace()) == 3


# --- the dense stack at a tiny preset (16-8-8-3): what `mlp-score-batch`
# runs at 784-128-128-10 (tests/test_mlp_deployment.py) ------------------


def _tiny_mlp(seed=4):
    from moose_tpu.predictors import multilayer_perceptron_predictor as mlp

    rng = np.random.default_rng(seed)
    widths = [16, 8, 8, 3]
    weights = [
        rng.normal(size=(a, b)) / np.sqrt(a) for a, b in zip(widths, widths[1:])
    ]
    biases = [0.1 * rng.normal(size=(b,)) for b in widths[1:]]
    return mlp.MLPClassifier(weights, biases, mlp.Activation.RELU)


def test_softmax_clamp_is_the_references():
    """A lane 20 under its row's largest is past the clamp (ln 2 x
    min(i - 1, f - 1): 9.01 at fixed(14, 23)) and gives exactly 0, as
    the benchmark's plain reference has it; the other lanes match it."""
    from chipbench.reference import mlp_onnx as reference

    model = _tiny_mlp()
    # the head's last class pushed 20 down for every row
    model.biases[2] = model.biases[2] - np.array([0.0, 0.0, 20.0])
    model = type(model)(model.weights, model.biases, model.activation)
    fixed = (14, 23)
    comp = model.predictor_factory(pm.fixed(*fixed))
    x = np.random.default_rng(6).random(size=(5, 16))
    rt = LocalMooseRuntime(["alice", "bob", "carole"], layout="stacked")
    (got,) = rt.evaluate_computation(comp, arguments={"x": x}).values()
    got = np.asarray(got)
    assert rt.last_plan["layout"] == "stacked"
    plain = {"weights": model.weights, "biases": model.biases}
    z = reference.logits(plain, x)
    edge = reference.clamp_edge(fixed)
    assert (z.max(axis=1) - z[:, 2] > edge).all()
    want = reference.softmax(z, edge)
    assert (want[:, 2] == 0.0).all() and (got[:, 2] == 0.0).all()
    np.testing.assert_allclose(got, want, atol=2e-5)
    # without the clamp the lane is small, not zero
    assert (np.exp(z - z.max(axis=1, keepdims=True))[:, 2] > 0).all()


def test_relu_stacked_is_the_per_host_relu_bit_for_bit():
    """``relu`` has no truncation on its path: msb, the bit's
    conversion, a mux.  So the stacked layout and the per-host dialect
    reveal the same ring element, whatever their masks."""
    alice, bob, carole, rep = _players()
    fx_dtype = pm.fixed(24, 40)

    @pm.computation
    def comp(x: pm.Argument(placement=alice, dtype=pm.float64)):
        with alice:
            x_f = pm.cast(x, dtype=fx_dtype)
        with rep:
            y = pm.relu(x_f)
        with bob:
            return pm.cast(y, dtype=pm.float64)

    rng = np.random.default_rng(8)
    x = rng.normal(size=(4, 8)) * 3.0
    x[0, :3] = [0.0, -(2.0 ** -40), 2.0 ** -40]
    outs = {}
    for layout in ("stacked", "per-host"):
        rt = LocalMooseRuntime(["alice", "bob", "carole"], layout=layout)
        (out,) = rt.evaluate_computation(comp, arguments={"x": x}).values()
        assert rt.last_plan["layout"] == layout
        outs[layout] = np.asarray(out)
    np.testing.assert_array_equal(outs["stacked"], outs["per-host"])
    encoded = np.round(x * 2.0 ** 40) / 2.0 ** 40
    np.testing.assert_array_equal(outs["stacked"], np.maximum(encoded, 0.0))


def test_dense_stack_scopes_are_in_the_lowered_module():
    """`scripts/xplane_scopes.py` splits the device's time by the
    `moose/` scopes of the lowered program: the dense stack's are there,
    each round what it names."""
    import jax

    from moose_tpu.dialects.stacked import StackedDialect
    from moose_tpu.edsl import tracer
    from moose_tpu.execution import interpreter

    comp = _tiny_mlp().predictor_factory(pm.fixed(14, 23))
    traced = tracer.trace(comp)
    args = {"x": np.zeros((4, 16))}
    plan = interpreter.build_plan(
        traced, args, use_jit=True, dialect=StackedDialect()
    )
    lowered = jax.jit(plan.core).lower(
        interpreter.master_key_words(),
        {name: args[name] for name in plan.dynamic_names},
    )
    text = lowered.as_text(debug_info=True)
    for scope in ("dense", "relu", "softmax", "max", "exp"):
        assert f"moose/{scope}" in text, scope
    # nested as the protocol nests them
    assert "moose/softmax/moose/max/moose/msb" in text
    assert "moose/softmax/moose/exp/moose/pow2" in text
    assert "moose/relu/moose/msb" in text
    assert "moose/dense/moose/trunc_pr" in text
    assert "moose/softmax/moose/fx_div" in text
