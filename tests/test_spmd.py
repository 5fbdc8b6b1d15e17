"""Party-stacked SPMD executor tests on a virtual CPU device mesh.

The conftest forces 12 virtual CPU devices; make_mesh(6) gives a genuine
(parties=3, data=2) mesh so the share axis is actually sharded and
resharing rolls become collective-permutes.  Also covers the stacked
nonlinear protocol library (``parallel/spmd_math.py``) and its
cross-layout equivalence against the per-host dialect
(``dialects/{replicated,fixedpoint}.py``) on identical inputs.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import moose_tpu  # noqa: F401
from moose_tpu.dialects import ring
from moose_tpu.parallel import spmd
from moose_tpu.parallel import spmd_math as sm

I, F, W = 14, 20, 128
MK = np.arange(4, dtype=np.uint32) + 11


def _sess():
    return spmd.SpmdSession(MK)


def _enc_share(sess, x, width=W):
    return spmd.fx_encode_share(sess, np.asarray(x, np.float64), I, F, width)


@pytest.mark.parametrize("width", [64, 128])
def test_share_reveal_roundtrip(width):
    sess = _sess()
    x = np.array([[1.5, -2.25], [0.0, 100.0]])
    xs = _enc_share(sess, x, width)
    got = np.asarray(spmd.fx_reveal_decode(xs))
    np.testing.assert_allclose(got, x)


@pytest.mark.parametrize("width", [64, 128])
def test_mul_trunc(width):
    sess = _sess()
    x = np.array([1.5, -2.0, 3.25, -0.5])
    y = np.array([2.0, 2.5, -1.5, 8.0])
    xs = _enc_share(sess, x, width)
    ys = _enc_share(sess, y, width)
    z = spmd.fx_mul(sess, xs, ys)
    got = np.asarray(spmd.fx_reveal_decode(z))
    np.testing.assert_allclose(got, x * y, atol=2e-6)


@pytest.mark.parametrize("width", [64, 128])
def test_dot(width):
    sess = _sess()
    rng = np.random.default_rng(0)
    a = rng.normal(size=(6, 5))
    b = rng.normal(size=(5, 3))
    za = _enc_share(sess, a, width)
    zb = _enc_share(sess, b, width)
    z = spmd.fx_dot(sess, za, zb)
    got = np.asarray(spmd.fx_reveal_decode(z))
    np.testing.assert_allclose(got, a @ b, atol=1e-5)


def test_sigmoid_poly():
    sess = _sess()
    x = np.linspace(-4.0, 4.0, 9)
    xs = _enc_share(sess, x)
    z = spmd.fx_sigmoid_poly(sess, xs)
    got = np.asarray(spmd.fx_reveal_decode(z))
    want = 1.0 / (1.0 + np.exp(-x))
    np.testing.assert_allclose(got, want, atol=0.08)


def test_zero_share_sums_to_zero():
    sess = _sess()
    lo, hi = spmd.zero_share(sess, (4,), 128)
    s_lo, s_hi = ring.add(lo[0], hi[0], lo[1], hi[1])
    s_lo, s_hi = ring.add(s_lo, s_hi, lo[2], hi[2])
    assert not np.asarray(s_lo).any()
    assert not np.asarray(s_hi).any()


def test_logreg_step_unsharded_matches_numpy():
    sess = _sess()
    rng = np.random.default_rng(1)
    xv = rng.normal(size=(8, 3)) * 0.5
    yv = (rng.uniform(size=(8, 1)) > 0.5).astype(np.float64)
    wv = rng.normal(size=(3, 1)) * 0.1
    lr = 0.1

    xs = _enc_share(sess, xv)
    ys = _enc_share(sess, yv)
    ws = _enc_share(sess, wv)
    w1 = spmd.logreg_train_step(sess, xs, ys, ws, lr)
    got = np.asarray(spmd.fx_reveal_decode(w1))

    def sig_poly(t):
        return 0.5 + 0.19828547 * t - 0.00446928 * t**3

    preds = sig_poly(xv @ wv)
    want = wv - lr * (xv.T @ (preds - yv)) / xv.shape[0]
    np.testing.assert_allclose(got, want, atol=1e-4)


def test_make_mesh_8_devices_keeps_party_axis():
    """v5e-8-style device counts must still get a real parties=3 axis
    (VERDICT r1 #2): 8 devices -> (3, 2) mesh over 6 of them."""
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    mesh = spmd.make_mesh(8)
    assert dict(zip(mesh.axis_names, mesh.devices.shape)) == {
        "parties": 3,
        "data": 2,
    }
    # and the stacked share sharding actually splits the party axis
    sh = spmd.rep_sharding(mesh, batch_axis=0, ndim=2)
    assert sh.spec[0] == "parties"


@pytest.mark.parametrize("n,want", [(1, (1, 1)), (2, (1, 2)), (3, (3, 1)),
                                    (4, (3, 1)), (6, (3, 2)), (7, (3, 2))])
def test_make_mesh_shapes(n, want):
    if len(jax.devices()) < n:
        pytest.skip("not enough virtual devices")
    mesh = spmd.make_mesh(n)
    assert mesh.devices.shape == want


def test_logreg_step_sharded_party_mesh():
    """Full train step jitted over a genuine (parties=3, data=2) mesh."""
    if len(jax.devices()) < 6:
        pytest.skip("needs 6 virtual devices")
    mesh = spmd.make_mesh(6)
    assert dict(zip(mesh.axis_names, mesh.devices.shape)) == {
        "parties": 3,
        "data": 2,
    }

    rng = np.random.default_rng(2)
    xv = rng.normal(size=(8, 3)) * 0.5
    yv = (rng.uniform(size=(8, 1)) > 0.5).astype(np.float64)
    wv = rng.normal(size=(3, 1)) * 0.1

    def step(mk, x_f, y_f, w_f):
        sess = spmd.SpmdSession(mk)
        xs = spmd.fx_encode_share(sess, x_f, I, F, W)
        ys = spmd.fx_encode_share(sess, y_f, I, F, W)
        ws = spmd.fx_encode_share(sess, w_f, I, F, W)
        w1 = spmd.logreg_train_step(sess, xs, ys, ws, 0.1, mesh=mesh)
        return spmd.fx_reveal_decode(w1)

    with mesh:
        got = np.asarray(jax.jit(step)(MK, xv, yv, wv))

    def sig_poly(t):
        return 0.5 + 0.19828547 * t - 0.00446928 * t**3

    preds = sig_poly(xv @ wv)
    want = wv - 0.1 * (xv.T @ (preds - yv)) / xv.shape[0]
    np.testing.assert_allclose(got, want, atol=1e-4)


def test_sharded_dot_mixed_consumer_repro(monkeypatch):
    """The CPU SPMD-partitioner miscompile that motivates
    ``_pin_contract_rhs``: a secure dot whose lhs shares are data-sharded
    while the rhs share slices stay unconstrained, with the rhs consumed
    by both the batched contraction and the pair-sum, returns garbage on
    jax 0.4.37 with 12 virtual CPU devices unless the rhs is pinned
    replicated.  The pinned path (the default on CPU) must stay exact;
    the unpinned run documents the corruption when the backend still
    exhibits it (constants alone do NOT trigger it — the PRF-drawn share
    banks are part of the repro, so this drives the real protocol)."""
    if len(jax.devices()) < 6:
        pytest.skip("needs 6 virtual devices")
    mesh = spmd.make_mesh(6)
    rng = np.random.default_rng(2)
    xv = rng.normal(size=(8, 3)) * 0.5
    wv = rng.normal(size=(3, 1)) * 0.1

    def run(pin_mode):
        monkeypatch.setenv("MOOSE_TPU_SPMD_PIN", pin_mode)

        def f(mk, x_f, w_f):
            s = spmd.SpmdSession(mk)
            xf = spmd.fx_encode_share(s, x_f, I, F, W)
            wf = spmd.fx_encode_share(s, w_f, I, F, W)
            xf = spmd.SpmdFixed(spmd.constrain(xf.tensor, mesh, 0), I, F)
            return spmd.fx_reveal_decode(spmd.fx_dot(s, xf, wf))

        with mesh:
            return np.asarray(jax.jit(f)(MK, xv, wv))

    want = xv @ wv
    np.testing.assert_allclose(run("always"), want, atol=1e-5)
    unpinned_err = float(np.max(np.abs(run("never") - want)))
    # on the affected backend the unpinned error is astronomically large
    # (~1e13 — uniform ring garbage, not rounding); a future XLA may fix
    # the partitioner, in which case both paths are exact and the pinned
    # assertion above remains the regression guard
    if unpinned_err > 1e-3:
        assert unpinned_err > 1e6, (
            "unpinned path is inexact but not catastrophically so: "
            f"{unpinned_err} — a new, different miscompile?"
        )


# ---------------------------------------------------------------------------
# Stacked nonlinear protocol library (parallel/spmd_math.py)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("width", [64, 128])
def test_stacked_bits_roundtrip(width):
    """bit_decompose o bit_compose is the identity (jitted; the stacked
    Kogge-Stone adder must reconstruct every bit exactly)."""
    vals = [3, 5, (1 << (width - 10)) + 7, (1 << width) - 9]
    lo, hi = ring.from_python_ints(np.asarray(vals, object), width)

    @jax.jit
    def f(mk, lo, hi):
        s = spmd.SpmdSession(mk)
        xs = (
            spmd.share(s, lo, hi, width)
            if width == 128
            else spmd.share(s, lo, None, width)
        )
        bits = sm.bit_decompose(s, xs)
        xc = sm.bit_compose(s, bits, width)
        return sm.reveal_bits(bits), spmd.reveal(xc)

    rb, (rlo, rhi) = f(MK, lo, hi)
    rb = np.asarray(rb)
    got_bits = [
        sum(int(rb[k, i]) << k for k in range(width))
        for i in range(len(vals))
    ]
    assert got_bits == [v % (1 << width) for v in vals]
    got = [
        int(l) | ((int(h) << 64) if rhi is not None else 0)
        for l, h in zip(
            np.asarray(rlo), np.asarray(rhi) if rhi is not None else [0] * 4
        )
    ]
    assert got == [v % (1 << width) for v in vals]


def test_stacked_bits_and_or_not():
    s = spmd.SpmdSession(MK)
    a = jnp.asarray(np.array([0, 0, 1, 1], np.uint8))
    b = jnp.asarray(np.array([0, 1, 0, 1], np.uint8))
    sa, sb = sm.share_bits(s, a), sm.share_bits(s, b)
    assert (np.asarray(sm.reveal_bits(sm.bits_and(s, sa, sb))) == [0, 0, 0, 1]).all()
    assert (np.asarray(sm.reveal_bits(sm.bits_or(s, sa, sb))) == [0, 1, 1, 1]).all()
    assert (np.asarray(sm.reveal_bits(sm.bits_xor(sa, sb))) == [0, 1, 1, 0]).all()
    assert (np.asarray(sm.reveal_bits(sm.bits_not(sa))) == [1, 1, 0, 0]).all()


@pytest.mark.parametrize("width", [64, 128])
def test_stacked_compare(width):
    i_p, f_p = (8, 20) if width == 64 else (I, F)
    xv = np.array([1.5, -2.0, 0.0, -9.0, 3.25])
    yv = np.array([2.0, -3.0, 0.25, 4.0, 3.25])

    @jax.jit
    def f(mk, xv, yv):
        s = spmd.SpmdSession(mk)
        xf = spmd.fx_encode_share(s, xv, i_p, f_p, width)
        yf = spmd.fx_encode_share(s, yv, i_p, f_p, width)
        return (
            sm.reveal_bits(sm.msb(s, xf.tensor)),
            sm.reveal_bits(sm.less(s, xf.tensor, yf.tensor)),
            sm.reveal_bits(sm.greater(s, xf.tensor, yf.tensor)),
            sm.reveal_bits(sm.equal_zero_bit(s, xf.tensor)),
            sm.reveal_bits(sm.equal_bit(s, xf.tensor, yf.tensor)),
        )

    m, lt, gt, ez, eq = (np.asarray(v) for v in f(MK, xv, yv))
    np.testing.assert_array_equal(m, (xv < 0).astype(np.uint8))
    np.testing.assert_array_equal(lt, (xv < yv).astype(np.uint8))
    np.testing.assert_array_equal(gt, (xv > yv).astype(np.uint8))
    np.testing.assert_array_equal(ez, (xv == 0).astype(np.uint8))
    np.testing.assert_array_equal(eq, (xv == yv).astype(np.uint8))


@pytest.mark.parametrize("width,i_p,f_p", [(64, 8, 20), (128, I, F)])
def test_stacked_division(width, i_p, f_p):
    a = np.array([1.0, 3.5, -2.25, 10.0, 0.125])
    b = np.array([2.0, 0.5, 3.0, 7.0, -4.0])

    @jax.jit
    def f(mk, av, bv):
        s = spmd.SpmdSession(mk)
        af = spmd.fx_encode_share(s, av, i_p, f_p, width)
        bf = spmd.fx_encode_share(s, bv, i_p, f_p, width)
        return spmd.fx_reveal_decode(sm.fx_div(s, af, bf))

    np.testing.assert_allclose(np.asarray(f(MK, a, b)), a / b, atol=4e-3)


def test_stacked_exp_sigmoid():
    ev = np.array([0.0, 1.0, -1.0, 2.5, -3.5])
    sv = np.array([-3.0, -0.5, 0.0, 0.5, 3.0])

    @jax.jit
    def f(mk, ev, sv):
        s = spmd.SpmdSession(mk)
        e = sm.fx_exp(s, spmd.fx_encode_share(s, ev, I, F, W))
        sg = sm.fx_sigmoid(s, spmd.fx_encode_share(s, sv, I, F, W))
        return spmd.fx_reveal_decode(e), spmd.fx_reveal_decode(sg)

    e, sg = f(MK, ev, sv)
    np.testing.assert_allclose(np.asarray(e), np.exp(ev), rtol=2e-3, atol=1e-4)
    np.testing.assert_allclose(
        np.asarray(sg), 1.0 / (1.0 + np.exp(-sv)), atol=2e-3
    )


def test_stacked_log_sqrt_pow2():
    lv = np.array([1.0, 2.0, 8.0, 0.5, 100.0])
    qv = np.array([4.0, 2.0, 9.0, 0.25])
    pv = np.array([0.0, 1.0, -1.0, 3.5])

    @jax.jit
    def f(mk, lv, qv, pv):
        s = spmd.SpmdSession(mk)
        lg = sm.fx_log2(s, spmd.fx_encode_share(s, lv, I, F, W))
        ln = sm.fx_log(s, spmd.fx_encode_share(s, lv, I, F, W))
        sq = sm.fx_sqrt(s, spmd.fx_encode_share(s, qv, I, F, W))
        p2 = sm.fx_pow2(s, spmd.fx_encode_share(s, pv, I, F, W))
        return tuple(
            spmd.fx_reveal_decode(v) for v in (lg, ln, sq, p2)
        )

    lg, ln, sq, p2 = f(MK, lv, qv, pv)
    np.testing.assert_allclose(np.asarray(lg), np.log2(lv), atol=5e-3)
    np.testing.assert_allclose(np.asarray(ln), np.log(lv), atol=5e-3)
    np.testing.assert_allclose(np.asarray(sq), np.sqrt(qv), atol=5e-3)
    np.testing.assert_allclose(np.asarray(p2), 2.0 ** pv, rtol=3e-3, atol=1e-4)


@pytest.mark.parametrize("axis", [0, 1])
def test_stacked_max_argmax_softmax(axis):
    rng = np.random.default_rng(3)
    xv = rng.normal(size=(4, 5)) * 2

    @jax.jit
    def f(mk, xv):
        s = spmd.SpmdSession(mk)
        xf = spmd.fx_encode_share(s, xv, I, F, W)
        mx = spmd.fx_reveal_decode(sm.fx_max(s, xf, axis))
        am = spmd.reveal(sm.fx_argmax(s, xf, axis))[0]
        sf = spmd.fx_reveal_decode(sm.fx_softmax(s, xf, axis))
        return mx, am, sf

    mx, am, sf = f(MK, xv)
    np.testing.assert_allclose(np.asarray(mx), xv.max(axis), atol=1e-4)
    np.testing.assert_array_equal(np.asarray(am), xv.argmax(axis))
    want = np.exp(xv - xv.max(axis, keepdims=True))
    want = want / want.sum(axis, keepdims=True)
    np.testing.assert_allclose(np.asarray(sf), want, atol=2e-3)


def test_stacked_maximum_list():
    xs_np = [np.array([1.0, -2.0]), np.array([0.5, 7.0]),
             np.array([3.0, -1.0])]
    s = spmd.SpmdSession(MK)
    xs = [spmd.fx_encode_share(s, v, I, F, W) for v in xs_np]
    got = np.asarray(spmd.fx_reveal_decode(sm.fx_maximum(s, xs)))
    np.testing.assert_allclose(got, np.max(xs_np, axis=0), atol=1e-4)


# ---------------------------------------------------------------------------
# TruncPr statistical bound in the stacked layout (additive/trunc.rs
# contract: result in {floor(x/2^m) + delta, delta in {0, 1}}, sign-safe)
# ---------------------------------------------------------------------------


def test_stacked_trunc_pr_bound():
    amount = F
    rng = np.random.default_rng(7)
    vals = np.concatenate(
        [rng.uniform(-30, 30, 200), [0.0, 1.0, -1.0, 2.0 ** -F]]
    )
    # the secure square operates on the ENCODED operands; compare against
    # their exact square (raw products fit float64: (30*2^20)^2 < 2^50)
    enc = np.round(vals * 2.0 ** F) / 2.0 ** F

    @jax.jit
    def f(mk, v):
        s = spmd.SpmdSession(mk)
        xf = spmd.fx_encode_share(s, v, I, F, W)
        doubled = spmd.mul(s, xf.tensor, xf.tensor)  # scale 2F
        t = spmd.trunc_pr(s, doubled, amount)
        lo, hi = spmd.reveal(t)
        return ring.fixedpoint_decode(lo, hi, F)

    got = np.asarray(f(MK, vals))
    np.testing.assert_allclose(got, enc * enc, atol=2.0 ** -F * 1.001)


def test_stacked_trunc_pr_probabilistic_rounding():
    """Repeated truncations of the same value must land within one ulp
    of the exact quotient, and the sub-ulp remainder must actually round
    probabilistically (not always down) over many masks."""
    # 1.1 encodes to raw 1153434; its square's low F bits are nonzero,
    # so trunc_pr rounds up with probability = remainder / 2^F (~0.59)
    x = np.round(1.1 * 2.0 ** F) / 2.0 ** F
    v = np.full((256,), 1.1)

    @jax.jit
    def f(mk):
        s = spmd.SpmdSession(mk)
        xf = spmd.fx_encode_share(s, v, I, F, W)
        sq = spmd.mul(s, xf.tensor, xf.tensor)
        t = spmd.trunc_pr(s, sq, F)
        lo, hi = spmd.reveal(t)
        return lo, hi

    lo, hi = f(MK)
    got = np.asarray(ring.fixedpoint_decode(lo, hi, F))
    raw_sq = int(round(x * 2.0 ** F)) ** 2
    floor_val = (raw_sq >> F) / 2.0 ** F
    ulp = 2.0 ** -F
    # every draw is floor or floor + 1 ulp...
    assert np.all(
        (np.abs(got - floor_val) < 1e-12)
        | (np.abs(got - (floor_val + ulp)) < 1e-12)
    ), got[:8]
    # ...and both outcomes occur (remainder is ~0.59 of an ulp)
    assert (np.abs(got - floor_val) < 1e-12).any()
    assert (np.abs(got - (floor_val + ulp)) < 1e-12).any()


# ---------------------------------------------------------------------------
# Cross-layout equivalence: per-host dialect vs stacked SPMD on identical
# inputs (the sync/async parity discipline of the reference,
# execution/mod.rs:107-167, restated for the two TPU layouts)
# ---------------------------------------------------------------------------


def _perhost_setup(width):
    from moose_tpu.computation import ReplicatedPlacement
    from moose_tpu.execution.session import EagerSession
    from moose_tpu.values import HostRingTensor

    rep = ReplicatedPlacement("rep", ("alice", "bob", "carole"))
    sess = EagerSession()
    return sess, rep, HostRingTensor


@pytest.mark.parametrize("width", [64, 128])
def test_cross_layout_mul_dot_exact(width):
    """mul/dot reveal is a DETERMINISTIC function of the inputs (zero
    shares cancel), so per-host and stacked must agree bit-for-bit."""
    from moose_tpu.dialects import replicated as rp
    from moose_tpu.values import to_numpy

    i_p, f_p = (8, 20) if width == 64 else (I, F)
    rng = np.random.default_rng(5)
    a = rng.normal(size=(3, 4))
    b = rng.normal(size=(4, 2))

    # stacked
    s = spmd.SpmdSession(MK)
    za = spmd.fx_encode_share(s, a, i_p, f_p, width)
    zb = spmd.fx_encode_share(s, b, i_p, f_p, width)
    prod = spmd.mul(s, za.tensor, za.tensor)
    dot = spmd.dot(s, za.tensor, zb.tensor)
    st_mul = spmd.reveal(prod)
    st_dot = spmd.reveal(dot)

    # per-host
    sess, rep, HostRingTensor = _perhost_setup(width)
    lo_a, hi_a = ring.fixedpoint_encode(jnp.asarray(a), f_p, width)
    lo_b, hi_b = ring.fixedpoint_encode(jnp.asarray(b), f_p, width)
    xa = HostRingTensor(lo_a, hi_a, width, "alice")
    xb = HostRingTensor(lo_b, hi_b, width, "bob")
    ra = rp.share(sess, rep, xa)
    rb = rp.share(sess, rep, xb)
    ph_mul = rp.reveal(sess, rep, rp.mul(sess, rep, ra, ra), "alice")
    ph_dot = rp.reveal(sess, rep, rp.dot(sess, rep, ra, rb), "alice")

    np.testing.assert_array_equal(np.asarray(st_mul[0]), np.asarray(ph_mul.lo))
    np.testing.assert_array_equal(np.asarray(st_dot[0]), np.asarray(ph_dot.lo))
    if width == 128:
        np.testing.assert_array_equal(
            np.asarray(st_mul[1]), np.asarray(ph_mul.hi)
        )
        np.testing.assert_array_equal(
            np.asarray(st_dot[1]), np.asarray(ph_dot.hi)
        )


@pytest.mark.parametrize("width", [64, 128])
def test_cross_layout_msb_exact(width):
    """msb is deterministic too: both layouts must produce identical
    bits for identical inputs."""
    from moose_tpu.dialects import replicated as rp
    from moose_tpu.values import to_numpy

    i_p, f_p = (8, 20) if width == 64 else (I, F)
    xv = np.array([1.5, -2.0, 0.0, -0.25, 9.0])

    s = spmd.SpmdSession(MK)
    xf = spmd.fx_encode_share(s, xv, i_p, f_p, width)
    st = np.asarray(sm.reveal_bits(sm.msb(s, xf.tensor)))

    sess, rep, HostRingTensor = _perhost_setup(width)
    lo, hi = ring.fixedpoint_encode(jnp.asarray(xv), f_p, width)
    x = HostRingTensor(lo, hi, width, "alice")
    xs = rp.share(sess, rep, x)
    m = rp.msb(sess, rep, xs)
    ph = np.asarray(to_numpy(rp.reveal(sess, rep, m, "alice")))

    np.testing.assert_array_equal(st, ph.astype(st.dtype))
    np.testing.assert_array_equal(st, (xv < 0).astype(st.dtype))


@pytest.mark.parametrize("width", [64, 128])
def test_cross_layout_trunc_pr_one_ulp(width):
    """trunc_pr is probabilistic in the last bit: layouts agree to 1 ulp
    (they draw different masks), and both stay within 1 ulp of exact."""
    from moose_tpu.dialects import replicated as rp

    i_p, f_p = (8, 20) if width == 64 else (I, F)
    xv = np.array([1.5, -2.25, 0.125, -9.5])

    s = spmd.SpmdSession(MK)
    xf = spmd.fx_encode_share(s, xv, i_p, f_p, width)
    sq = spmd.mul(s, xf.tensor, xf.tensor)
    st_lo, st_hi = spmd.reveal(spmd.trunc_pr(s, sq, f_p))
    st = np.asarray(ring.fixedpoint_decode(st_lo, st_hi, f_p))

    sess, rep, HostRingTensor = _perhost_setup(width)
    lo, hi = ring.fixedpoint_encode(jnp.asarray(xv), f_p, width)
    x = HostRingTensor(lo, hi, width, "alice")
    xs = rp.share(sess, rep, x)
    sq_ph = rp.mul(sess, rep, xs, xs)
    t_ph = rp.trunc_pr(sess, rep, sq_ph, f_p)
    out = rp.reveal(sess, rep, t_ph, "alice")
    ph = np.asarray(
        ring.fixedpoint_decode(
            jnp.asarray(out.lo), None if out.hi is None else jnp.asarray(out.hi),
            f_p,
        )
    )

    ulp = 2.0 ** -f_p
    np.testing.assert_allclose(st, xv * xv, atol=ulp * 1.001)
    np.testing.assert_allclose(ph, xv * xv, atol=ulp * 1.001)
    np.testing.assert_allclose(st, ph, atol=2 * ulp * 1.001)


def test_cross_layout_sigmoid():
    """The exact protocol sigmoid in both layouts tracks the true
    sigmoid within fixed-point tolerance on the same inputs."""
    from moose_tpu.computation import ReplicatedPlacement
    from moose_tpu.dialects import fixedpoint as fx
    from moose_tpu.dialects import replicated as rp
    from moose_tpu.execution.session import EagerSession
    from moose_tpu.values import HostRingTensor, RepFixedTensor

    xv = np.array([-2.0, -0.5, 0.5, 2.0])
    want = 1.0 / (1.0 + np.exp(-xv))

    @jax.jit
    def f(mk, xv):
        s = spmd.SpmdSession(mk)
        xf = spmd.fx_encode_share(s, xv, I, F, W)
        return spmd.fx_reveal_decode(sm.fx_sigmoid(s, xf))

    st = np.asarray(f(MK, xv))

    sess = EagerSession()
    rep = ReplicatedPlacement("rep", ("alice", "bob", "carole"))
    lo, hi = ring.fixedpoint_encode(jnp.asarray(xv), F, W)
    x = HostRingTensor(lo, hi, W, "alice")
    xs = RepFixedTensor(rp.share(sess, rep, x), I, F)
    sg = fx.sigmoid(sess, rep, xs)
    out = rp.reveal(sess, rep, sg.tensor, "alice")
    ph = np.asarray(
        ring.fixedpoint_decode(jnp.asarray(out.lo), jnp.asarray(out.hi), F)
    )

    np.testing.assert_allclose(st, want, atol=2e-3)
    np.testing.assert_allclose(ph, want, atol=2e-3)
    np.testing.assert_allclose(st, ph, atol=4e-3)


# ---------------------------------------------------------------------------
# Mesh-size sweep: the party-axis layout must compile and produce correct
# results on meshes of {3, 6, 8, 12} devices
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n_devices", [3, 6, 8, 12])
def test_mesh_size_sweep(n_devices):
    if len(jax.devices()) < n_devices:
        pytest.skip(f"needs {n_devices} virtual devices")
    mesh = spmd.make_mesh(n_devices)
    assert mesh.devices.shape[0] == 3  # party axis always 3 when n >= 3

    rng = np.random.default_rng(n_devices)
    data = mesh.devices.shape[1]
    batch = 4 * data
    xv = rng.normal(size=(batch, 3)) * 0.5
    yv = rng.normal(size=(3, 1)) * 0.5

    def f(mk, xv, yv):
        s = spmd.SpmdSession(mk)
        xf = spmd.fx_encode_share(s, xv, I, F, W)
        yf = spmd.fx_encode_share(s, yv, I, F, W)
        xf = spmd.SpmdFixed(
            spmd.constrain(xf.tensor, mesh, 0), I, F
        )
        z = spmd.fx_dot(s, xf, yf)
        return spmd.fx_reveal_decode(z)

    with mesh:
        got = np.asarray(jax.jit(f)(MK, xv, yv))
    np.testing.assert_allclose(got, xv @ yv, atol=1e-5)


def test_stacked_softmax_on_party_mesh():
    """Secure softmax — the protocol library, not just logreg — jitted
    over a genuine (parties=3, data) mesh (VERDICT r3 item 1)."""
    if len(jax.devices()) < 6:
        pytest.skip("needs 6 virtual devices")
    mesh = spmd.make_mesh(6)
    rng = np.random.default_rng(17)
    xv = rng.normal(size=(4, 5)) * 2

    def f(mk, xv):
        s = spmd.SpmdSession(mk)
        xf = spmd.fx_encode_share(s, xv, I, F, W)
        xf = spmd.SpmdFixed(spmd.constrain(xf.tensor, mesh, 0), I, F)
        return spmd.fx_reveal_decode(sm.fx_softmax(s, xf, 1))

    with mesh:
        got = np.asarray(jax.jit(f)(MK, xv))
    want = np.exp(xv - xv.max(1, keepdims=True))
    want = want / want.sum(1, keepdims=True)
    np.testing.assert_allclose(got, want, atol=2e-3)


@pytest.mark.parametrize("width", [64, 128])
def test_fused_mul_trunc_bit_exact_vs_unfused(width):
    """The fused multiply+truncate path (_mul_like_trunc) is BIT-IDENTICAL
    to the explicit dot() -> trunc_pr() sequence: same PRF draw order,
    only pure data movement (the intermediate pair layout) skipped.
    This equality is what licenses the fusion's perf claim."""
    rng = np.random.default_rng(23)
    x = rng.normal(size=(6, 7)) * 0.5
    y = rng.normal(size=(7, 4)) * 0.5

    def fused(mk):
        sess = spmd.SpmdSession(mk)
        xs = spmd.fx_encode_share(sess, x, I, F, width)
        ys = spmd.fx_encode_share(sess, y, I, F, width)
        return spmd.fx_dot(sess, xs, ys).tensor

    def unfused(mk):
        sess = spmd.SpmdSession(mk)
        xs = spmd.fx_encode_share(sess, x, I, F, width)
        ys = spmd.fx_encode_share(sess, y, I, F, width)
        z = spmd.dot(sess, xs.tensor, ys.tensor)
        return spmd.trunc_pr(sess, z, F)

    a = jax.jit(fused)(MK)
    b = jax.jit(unfused)(MK)
    assert np.array_equal(np.asarray(a.lo), np.asarray(b.lo))
    if width == 128:
        assert np.array_equal(np.asarray(a.hi), np.asarray(b.hi))


# ---------------------------------------------------------------------------
# The elementwise fixed-point functions work on a lane-dense view of their
# operand (spmd_math._flat_view): rank >= 2 is reshaped to (n / 128, 128),
# or (n,) where 128 does not divide the lanes, and restored; rank <= 1 or
# a minor axis of 128 lanes already passes through with no op emitted
# ---------------------------------------------------------------------------


def _np_softmax(v):
    e = np.exp(v - v.max(0, keepdims=True))
    return e / e.sum(0, keepdims=True)


_FLAT_FNS = {
    # name (the counter's ``fn``): stacked function, float reference, the
    # tolerance of the function's own test above
    "exp": (sm.fx_exp, np.exp, dict(rtol=2e-3, atol=1e-4)),
    "pow2": (sm.fx_pow2, lambda v: 2.0 ** v, dict(rtol=3e-3, atol=1e-4)),
    "sigmoid": (
        sm.fx_sigmoid, lambda v: 1.0 / (1.0 + np.exp(-v)), dict(atol=2e-3)
    ),
    "softmax": (
        lambda s, x: sm.fx_softmax(s, x, 0), _np_softmax, dict(atol=2e-3)
    ),
}


@pytest.mark.parametrize(
    "name,shape",
    [
        (name, shape) for name in _FLAT_FNS
        for shape in [(6, 10), (4, 3, 5), (60,), (3, 20)]
    ] + [
        # 128 divides the lanes: (n / 128, 128), the cells' form; and a
        # minor axis of 128 already: taken as it came, whatever the rank
        ("exp", (16, 8)), ("sigmoid", (16, 8)), ("exp", (1, 128)),
    ],
)
def test_elementwise_functions_work_on_the_flat_view(name, shape, monkeypatch):
    """The first four shapes have 60 lanes and the others 128, so the
    flat work of all the cases is two sets of eager programs (the
    suite's clock, ROADMAP D20); ring64 for the same reason: the helper
    does not read the width."""
    from moose_tpu import metrics
    from moose_tpu.execution import drawledger

    fn, reference, tolerance = _FLAT_FNS[name]
    xv = np.random.default_rng(len(shape) + shape[0]).normal(size=shape)

    def go(mk, xv):
        s = spmd.SpmdSession(mk)
        xf = spmd.fx_encode_share(s, xv, 8, 20, 64)
        return spmd.fx_reveal_decode(fn(s, xf))

    def counted(form):
        return metrics.REGISTRY.value(
            "moose_tpu_elementwise_flat_total", fn=name, form=form
        )

    def traced(xv):
        # a function of its own each time: a trace is cached by function
        with drawledger.recording() as ledger:
            jaxpr = jax.make_jaxpr(lambda mk, xv: go(mk, xv))(MK, xv)
        return jaxpr, ledger.stacked_trace()

    before = counted("flat"), counted("as_is")
    if len(shape) == 1 or shape[-1] % 128 == 0:
        # rank 1 (and a minor axis of 128 lanes already) passes through
        # and emits nothing: the program is the one with the helper
        # bypassed (``logreg-score-64k``'s sigmoid), whose values the
        # functions' own tests above hold
        jaxpr, draws = traced(xv)
        assert [v.aval.shape for v in jaxpr.jaxpr.outvars] == [shape]
        assert (counted("flat"), counted("as_is") - 1) == before
        monkeypatch.setattr(sm, "_flat_view", lambda fn, x: (x, lambda y: y))
        bypassed, draws_bypassed = traced(xv)
        assert str(jaxpr) == str(bypassed) and draws == draws_bypassed
        return

    with drawledger.recording() as ledger:
        got = np.asarray(go(MK, xv))
    assert got.shape == shape
    np.testing.assert_allclose(got, reference(xv), **tolerance)
    assert (counted("flat") - 1, counted("as_is")) == before
    lanes = int(np.prod(shape))
    form = [lanes // 128, 128] if lanes % 128 == 0 else [lanes]
    assert f"u64[3,2,{','.join(map(str, form))}]" in str(traced(xv)[0])
    draws = ledger.stacked_trace()
    # the same draws of the same elements in the same session order as ...
    if name == "softmax":
        # ... the program with the helper bypassed (the parent's)
        monkeypatch.setattr(sm, "_flat_view", lambda fn, x: (x, lambda y: y))
        assert draws == traced(xv)[1]
    else:
        # ... a caller who flattened the values records
        with drawledger.recording() as ledger:
            go(MK, xv.reshape(-1))
        assert draws == ledger.stacked_trace()
