"""Kernel-vs-lax bit-exactness for the ring64/ring128 Pallas kernels
(ISSUE 9): every kernel in ``native/ring128_kernels.py`` runs in
interpret mode on CPU — the IDENTICAL kernel code real TPUs compile
with Mosaic — and must agree bit-for-bit with its lax twin on
randomized shapes including non-aligned trailing dims.  End-to-end:
whole protocol primitives (trunc_pr, msb, polynomial_eval, fx_sigmoid,
fx_dot) must be bit-identical with kernels on, off, or falling back
mid-path, because the PRF-draw order is shared across all three paths.
Plus the fixed(24,40) sigmoid regression pin (the exact miscompile
reproducer of ``repro_miscompile.py``) and the stacked-by-default
``layout='auto'`` routing with zero pinned ops."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import moose_tpu as pm  # noqa: F401  (x64 setup)
from moose_tpu import metrics
from moose_tpu.dialects import ring
from moose_tpu.native import ring128_kernels as rk
from moose_tpu.parallel import spmd
from moose_tpu.parallel import spmd_math as sm
from moose_tpu.runtime import LocalMooseRuntime

RNG = np.random.default_rng(0x5EED)
MK = np.arange(4, dtype=np.uint32) + 77

WIDTHS = (64, 128)
# deliberately un-tiled shapes: odd sizes, rank 1..3
SHAPES = ((3, 5), (17,), (2, 3, 33))


@pytest.fixture
def pallas_on():
    """Force kernels on WITHOUT wiping the first-use check verdicts:
    checks are jitted but still cost seconds each, so the module shares
    one verdict cache across tests (tests that poison the cache
    snapshot and restore it themselves)."""
    rk.set_enabled(True)
    yield
    rk.set_enabled(None)


def _rand_ring(shape, width):
    lo = jnp.asarray(
        RNG.integers(0, 1 << 64, size=shape, dtype=np.uint64)
    )
    if width == 64:
        return lo, None
    hi = jnp.asarray(
        RNG.integers(0, 1 << 64, size=shape, dtype=np.uint64)
    )
    return lo, hi


def _assert_ring_equal(got, want, label=""):
    assert np.array_equal(np.asarray(got[0]), np.asarray(want[0])), (
        f"{label}: lo diverged"
    )
    if want[1] is not None:
        assert np.array_equal(np.asarray(got[1]), np.asarray(want[1])), (
            f"{label}: hi diverged"
        )


# ---------------------------------------------------------------------------
# Direct kernel-vs-lax property tests
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("width", WIDTHS)
def test_ring_mul_matches_lax(pallas_on, width):
    for shape in SHAPES:
        x = _rand_ring(shape, width)
        y = _rand_ring(shape, width)
        _assert_ring_equal(
            rk.ring_mul(*x, *y, width), ring.mul(*x, *y),
            f"ring_mul{shape}/ring{width}",
        )


@pytest.mark.parametrize("width", WIDTHS)
def test_cross_terms_mul_matches_lax(pallas_on, width):
    for shape in ((3, 4, 5), (3, 11)):
        x0, x1, y0, y1 = (_rand_ring(shape, width) for _ in range(4))
        ys = ring.add(*y0, *y1)
        want = ring.add(*ring.mul(*x0, *ys), *ring.mul(*x1, *y0))
        _assert_ring_equal(
            rk.cross_terms_mul(x0, x1, y0, y1, width), want,
            f"cross{shape}/ring{width}",
        )


@pytest.mark.parametrize("width", WIDTHS)
@pytest.mark.parametrize("amount", (7,))
def test_trunc_combine_matches_lax(pallas_on, width, amount):
    for shape in ((4, 5), (9,)):
        a0 = _rand_ring(shape, width)
        a1 = _rand_ring(shape, width)
        draws = tuple(_rand_ring(shape, width) for _ in range(5))
        want = spmd._trunc_combine_lax(a0, a1, draws, width, amount)
        got = rk.trunc_combine(a0, a1, draws, width, amount, shape)
        _assert_ring_equal(got, want, f"trunc{shape}/{amount}")


@pytest.mark.parametrize("width", WIDTHS)
def test_bit_decompose_and_msb_match_lax(pallas_on, width):
    n_ands = rk.adder_bank_count(width)
    for shape in ((2, 5),):
        lo = jnp.asarray(RNG.integers(
            0, 1 << 64, size=(3, 2) + shape, dtype=np.uint64
        ))
        hi = (
            jnp.asarray(RNG.integers(
                0, 1 << 64, size=(3, 2) + shape, dtype=np.uint64
            )) if width == 128 else None
        )
        banks = jnp.asarray(RNG.integers(
            0, 2, size=(n_ands, 3, width) + shape, dtype=np.uint8
        ))
        want = sm._bit_decompose_with_banks(lo, hi, width, banks)
        got = rk.bit_decompose(lo, hi, width, banks)
        assert np.array_equal(np.asarray(got), np.asarray(want))
        got_msb = rk.msb(lo, hi, width, banks)
        assert np.array_equal(
            np.asarray(got_msb), np.asarray(want)[:, :, width - 1]
        )


def test_adder_bank_count_matches_lax_consumption():
    """The pre-draw size must equal EXACTLY what the unfused path
    consumes: one bank short raises, one extra means a silently skewed
    PRF stream (the banks iterator consumes banks[0..n) in order)."""
    for width in WIDTHS:
        n = rk.adder_bank_count(width)
        shape = (3,)
        lo = jnp.asarray(RNG.integers(
            0, 1 << 64, size=(3, 2) + shape, dtype=np.uint64
        ))
        hi = None if width == 64 else jnp.zeros_like(lo)
        banks = jnp.asarray(RNG.integers(
            0, 2, size=(n, 3, width) + shape, dtype=np.uint8
        ))
        sm._bit_decompose_with_banks(lo, hi, width, banks)  # exact fit
        short = banks[: n - 1]
        with pytest.raises(Exception):
            sm._bit_decompose_with_banks(lo, hi, width, short)


# ---------------------------------------------------------------------------
# End-to-end: kernels on vs off must be BIT-identical (shared PRF-draw
# order is the contract that makes the ladder, tests, and fallbacks
# interchangeable)
# ---------------------------------------------------------------------------


def _fresh_session():
    return spmd.SpmdSession(MK)


def _run_both(fn):
    """Run ``fn(sess)`` with kernels forced on and forced off from the
    same master key; returns the two results."""
    rk.set_enabled(True)
    try:
        on = fn(_fresh_session())
    finally:
        rk.set_enabled(None)
    rk.set_enabled(False)
    try:
        off = fn(_fresh_session())
    finally:
        rk.set_enabled(None)
    return on, off


def _assert_rep_equal(a: spmd.SpmdRep, b: spmd.SpmdRep):
    assert np.array_equal(np.asarray(a.lo), np.asarray(b.lo))
    if b.hi is not None:
        assert np.array_equal(np.asarray(a.hi), np.asarray(b.hi))


@pytest.mark.parametrize("width", WIDTHS)
def test_trunc_pr_bit_identical_on_off(width):
    x = RNG.normal(size=(3, 4))

    def go(sess):
        xs = spmd.fx_encode_share(sess, x, 8, 12, width)
        return spmd.trunc_pr(sess, xs.tensor, 5)

    on, off = _run_both(go)
    _assert_rep_equal(on, off)


@pytest.mark.parametrize(
    "width", [64, pytest.param(128, marks=pytest.mark.slow)]
)
def test_msb_bit_identical_on_off(width):
    x = RNG.normal(size=(2, 5))

    def go(sess):
        xs = spmd.fx_encode_share(sess, x, 8, 12, width)
        return sm.msb(sess, xs.tensor).arr

    on, off = _run_both(go)
    assert np.array_equal(np.asarray(on), np.asarray(off))


@pytest.mark.parametrize("width", (64,))
def test_polynomial_eval_bit_identical_on_off(width):
    # width 64 only: the eager interpret walk at ring128 costs tens of
    # seconds; the 128-bit ladder is pinned by the jitted first-use
    # self-check and the slow-marked sigmoid test below
    x = RNG.normal(size=(2, 3)) * 0.5
    integ, frac = (8, 12) if width == 64 else (14, 23)

    def go(sess):
        xs = spmd.fx_encode_share(sess, x, integ, frac, width)
        return sm.polynomial_eval(
            sess, [1.0, 0.5, -0.25, 0.125], xs
        ).tensor

    on, off = _run_both(go)
    _assert_rep_equal(on, off)


@pytest.mark.slow  # ~1 min eager-interpret walk per precision on CPU;
# the per-primitive on/off tests above cover every kernel in tier-1
@pytest.mark.parametrize(
    "width,integ,frac", ((64, 8, 17), (128, 24, 40))
)
def test_fx_sigmoid_bit_identical_on_off(width, integ, frac):
    """The whole protocol sigmoid — msb, b2a, bit_decompose, pow2,
    polynomial, Goldschmidt — bit-identical with kernels on vs off.
    fixed(24,40) at ring128 is the known-miscompile precision."""
    x = RNG.normal(size=(2, 3)) * 1.5

    def go(sess):
        xs = spmd.fx_encode_share(sess, x, integ, frac, width)
        return sm.fx_sigmoid(sess, xs).tensor

    on, off = _run_both(go)
    _assert_rep_equal(on, off)


def test_horner_error_fallback_replays_same_draws(monkeypatch):
    """A kernel that dies AFTER its draws were made must not skew the
    stream: the fallback replays the SAME draws through the unfused
    ladder, so the result equals the kernels-off run bit-for-bit."""
    x = RNG.normal(size=(2, 3)) * 0.5

    def go(sess):
        xs = spmd.fx_encode_share(sess, x, 8, 12, 64)
        return sm.polynomial_eval(sess, [1.0, 0.5, -0.25], xs).tensor

    rk.reset_state()
    rk.set_enabled(False)
    try:
        want = go(_fresh_session())
    finally:
        rk.set_enabled(None)
    rk.reset_state()
    rk.set_enabled(True)
    before = metrics.REGISTRY.value(
        "moose_tpu_pallas_fallback_total", kernel="horner", reason="error"
    )

    def boom(*a, **k):
        raise RuntimeError("synthetic kernel failure")

    monkeypatch.setattr(rk, "horner", boom)
    try:
        got = go(_fresh_session())
    finally:
        rk.set_enabled(None)
        rk.reset_state()
    _assert_rep_equal(got, want)
    after = metrics.REGISTRY.value(
        "moose_tpu_pallas_fallback_total", kernel="horner", reason="error"
    )
    assert after == before + 1


def test_dot_kernel_off_by_default(pallas_on):
    """MOOSE_TPU_PALLAS_DOT unset -> the dot kernel never dispatches,
    even with the family knob forced on (cheap tier-1 pin of the
    documented default; the end-to-end opt-in test below is slow)."""
    assert not rk.dispatch("dot_cross_terms", 64)


@pytest.mark.slow
def test_dot_kernel_opt_in_bit_identical(monkeypatch):
    """The dot kernel is OFF by default and opt-in via
    MOOSE_TPU_PALLAS_DOT=1; when selected, fx_dot is bit-identical to
    the XLA limb path."""
    rk.reset_state()
    rk.set_enabled(True)
    try:
        assert not rk.dispatch("dot_cross_terms", 64)
    finally:
        rk.set_enabled(None)
        rk.reset_state()

    monkeypatch.setenv("MOOSE_TPU_PALLAS_DOT", "1")
    x = RNG.normal(size=(4, 6)) * 0.5
    w = RNG.normal(size=(6, 2)) * 0.5

    def go(sess):
        xs = spmd.fx_encode_share(sess, x, 8, 12, 64)
        ws = spmd.fx_encode_share(sess, w, 8, 12, 64)
        return spmd.fx_dot(sess, xs, ws).tensor

    on, off = _run_both(go)
    _assert_rep_equal(on, off)


# ---------------------------------------------------------------------------
# Dispatch machinery: knob, self-check fallback, metrics
# ---------------------------------------------------------------------------


def test_knob_env_parsing(monkeypatch):
    rk.set_enabled(None)
    monkeypatch.setenv("MOOSE_TPU_PALLAS", "1")
    assert rk.enabled()
    monkeypatch.setenv("MOOSE_TPU_PALLAS", "0")
    assert not rk.enabled()
    monkeypatch.setenv("MOOSE_TPU_PALLAS", "yes")
    from moose_tpu.errors import ConfigurationError

    with pytest.raises(ConfigurationError):
        rk.enabled()
    monkeypatch.delenv("MOOSE_TPU_PALLAS")
    # auto: off on CPU (interpret kernels are a correctness tool there)
    assert rk.enabled() == (jax.default_backend() == "tpu")


@pytest.mark.parametrize(
    "exc,reason",
    [
        (AssertionError("synthetic divergence"), "diverged"),
        # what a compile refusal or a crash on the backend looks like
        (RuntimeError("synthetic Mosaic refusal"), "error"),
    ],
    ids=["diverged", "error"],
)
def test_first_use_failure_pins_fallback(pallas_on, monkeypatch, exc, reason):
    """A kernel whose first-use self-check diverges from its lax twin,
    or fails to compile or run, is pinned to the XLA path for the
    process; the fallback metric increments, ``report()`` keeps the
    exception's text, and the protocol math stays correct."""
    saved = dict(rk._STATE)
    rk.reset_state()

    def bad_check(width):
        raise exc

    monkeypatch.setitem(rk._CHECKS, "trunc_combine", bad_check)
    before = metrics.REGISTRY.value(
        "moose_tpu_pallas_fallback_total",
        kernel="trunc_combine", reason=reason,
    )
    assert not rk.dispatch("trunc_combine", 64)
    after = metrics.REGISTRY.value(
        "moose_tpu_pallas_fallback_total",
        kernel="trunc_combine", reason=reason,
    )
    assert after == before + 1
    report = rk.report()
    assert report["kernels"]["trunc_combine/64"] == f"fallback:{reason}"
    assert report["errors"]["trunc_combine/64"] == (
        f"{type(exc).__name__}: {exc}"
    )
    # the protocol path still runs (XLA) and stays correct
    sess = _fresh_session()
    x = RNG.normal(size=(2, 2))
    xs = spmd.fx_encode_share(sess, x, 8, 12, 64)
    z = spmd.trunc_pr(sess, xs.tensor, 6)
    dec = ring.fixedpoint_decode(*spmd.reveal(z), 6)
    assert np.abs(np.asarray(dec) - x).max() < 2.0 ** -5
    rk.reset_state()
    rk._STATE.update(saved)


def test_check_twins_run_on_the_cpu_backend():
    """A first-use check's lax twin must not share the kernel's
    compiler: it runs on the CPU backend and comes back as host
    arrays (on the chip XLA:TPU compiled the bit kernels' twin wrong
    and two right kernels were pinned diverged)."""
    x = _rand_ring((3, 5), 128)
    y = _rand_ring((3, 5), 128)
    lo, hi = rk._twin_eval(lambda: ring.mul(*x, *y))
    assert isinstance(lo, np.ndarray) and isinstance(hi, np.ndarray)
    _assert_ring_equal((lo, hi), ring.mul(*x, *y), "twin on cpu")


def test_kernels_decline_under_a_device_mesh(pallas_on):
    """GSPMD cannot partition a Mosaic kernel (the chip's compiler
    refuses the program), so under a mesh of more than one device every
    primitive keeps the XLA path — ambient ``with mesh:`` programs and
    the stacked runtime's mesh alike, on every platform."""
    mesh = spmd.make_mesh(3)
    assert rk.dispatch("cross_terms_mul", 64)
    with mesh:
        assert not rk.dispatch("cross_terms_mul", 64)
    with spmd.make_mesh(1):
        # one device: nothing to split
        assert rk.dispatch("cross_terms_mul", 64)
    with rk.declined():
        assert not rk.dispatch("cross_terms_mul", 64)
    assert rk.dispatch("cross_terms_mul", 64)

    alice = pm.host_placement("alice")
    bob = pm.host_placement("bob")
    carole = pm.host_placement("carole")
    rep = pm.replicated_placement("rep", players=[alice, bob, carole])

    @pm.computation
    def comp(
        x: pm.Argument(placement=alice, dtype=pm.float64),
        y: pm.Argument(placement=bob, dtype=pm.float64),
    ):
        with alice:
            xf = pm.cast(x, dtype=pm.fixed(8, 17))
        with bob:
            yf = pm.cast(y, dtype=pm.fixed(8, 17))
        with rep:
            z = pm.mul(xf, yf)
        with carole:
            return pm.cast(z, dtype=pm.float64)

    x, y = RNG.normal(size=(2, 4, 3))

    def dispatched():
        return sum(
            metrics.REGISTRY.snapshot()
            ["moose_tpu_pallas_dispatch_total"]["values"].values()
        )

    before = dispatched()
    rt = LocalMooseRuntime(
        ["alice", "bob", "carole"], layout="stacked", mesh=mesh
    )
    (got,) = rt.evaluate_computation(
        comp, arguments={"x": x, "y": y}
    ).values()
    np.testing.assert_allclose(got, x * y, atol=1e-4)
    assert dispatched() == before
    rt = LocalMooseRuntime(["alice", "bob", "carole"], layout="stacked")
    rt.evaluate_computation(comp, arguments={"x": x, "y": y})
    assert dispatched() > before


def test_dispatch_metric_increments(pallas_on):
    before = metrics.REGISTRY.value(
        "moose_tpu_pallas_dispatch_total", kernel="cross_terms_mul"
    )
    assert rk.dispatch("cross_terms_mul", 64)
    after = metrics.REGISTRY.value(
        "moose_tpu_pallas_dispatch_total", kernel="cross_terms_mul"
    )
    assert after == before + 1


def test_kernel_switched_off_by_name_never_dispatches(pallas_on):
    """``ring_mul`` is off by name since the chip showed the jitted
    plan around it wrong for some keys (the reason rides in
    ``report()``); the kernel itself stays, tested against its twin
    above, for the day a chip run clears it."""
    assert "ring_mul" in rk.report()["switched_off"]
    before = metrics.REGISTRY.value(
        "moose_tpu_pallas_dispatch_total", kernel="ring_mul"
    )
    for width in WIDTHS:
        assert not rk.dispatch("ring_mul", width)
    assert ("ring_mul", 64) not in rk._STATE  # no check was spent on it
    assert metrics.REGISTRY.value(
        "moose_tpu_pallas_dispatch_total", kernel="ring_mul"
    ) == before


# ---------------------------------------------------------------------------
# The fixed(24,40) sigmoid regression pin + stacked-by-default routing
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_sigmoid_fixed24_40_jit_vs_eager_bitexact_pallas(pallas_on):
    """The exact reproducer of repro_miscompile.py --sigmoid-probe,
    with the Pallas kernels forced on: jitted fx_sigmoid at
    fixed(24,40) must be bit-identical to its own eager execution (on
    TPU this is the miscompile sidestep; on CPU it pins the harness)."""
    x = RNG.normal(size=(2, 3)) * 2.0

    def forward(master_key, x_f):
        sess = spmd.SpmdSession(master_key)
        xs = spmd.fx_encode_share(sess, x_f, 24, 40, 128)
        return spmd.fx_reveal_decode(sm.fx_sigmoid(sess, xs))

    eager = np.asarray(forward(MK, x))
    jitted = np.asarray(jax.jit(forward)(MK, x))
    assert np.array_equal(eager, jitted)
    want = 1.0 / (1.0 + np.exp(-x))
    assert np.abs(eager - want).max() < 5e-3


def _traced_logreg(fx):
    alice = pm.host_placement("alice")
    bob = pm.host_placement("bob")
    carole = pm.host_placement("carole")
    rep = pm.replicated_placement("rep", players=[alice, bob, carole])

    @pm.computation
    def logreg(
        xa: pm.Argument(placement=alice, dtype=pm.float64),
        wa: pm.Argument(placement=bob, dtype=pm.float64),
    ):
        with alice:
            xf = pm.cast(xa, dtype=fx)
        with bob:
            wf = pm.cast(wa, dtype=fx)
        with rep:
            y = pm.sigmoid(pm.dot(xf, wf))
        with carole:
            out = pm.cast(y, dtype=pm.float64)
        return out

    return logreg


@pytest.mark.slow
def test_auto_layout_whole_graph_zero_pins(pallas_on):
    """ISSUE 9 acceptance shape (CPU leg): the traced logreg through
    the DEFAULT runtime (layout auto) lands on the stacked backend as
    ONE whole-graph jit with zero pinned ops, at the miscompile
    precision fixed(24,40)."""
    x = RNG.normal(size=(4, 3)) * 0.5
    w = RNG.normal(size=(3, 1)) * 0.5
    rt = LocalMooseRuntime(
        ["alice", "bob", "carole"], use_jit=True
    )
    assert rt.layout == "auto"
    out = next(iter(rt.evaluate_computation(
        _traced_logreg(pm.fixed(24, 40)),
        arguments={"xa": x, "wa": w},
    ).values()))
    assert rt.last_plan["layout"] == "stacked"
    assert rt.last_plan["plan_mode"] == "whole-graph"
    assert rt.last_plan["pinned_ops"] == []
    want = 1.0 / (1.0 + np.exp(-(x @ w)))
    assert np.abs(np.asarray(out) - want).max() < 5e-3


def test_auto_layout_host_only_stays_per_host():
    alice = pm.host_placement("alice")

    @pm.computation
    def comp(x: pm.Argument(placement=alice, dtype=pm.float64)):
        with alice:
            y = pm.add(x, x)
        return y

    rt = LocalMooseRuntime(["alice"], use_jit=False)
    rt.evaluate_computation(comp, arguments={"x": np.ones((4,))})
    assert rt.last_plan["layout"] == "per-host"


def test_auto_layout_demotes_unsupported_graph():
    """supports() rejection under the auto DEFAULT still runs the
    per-host path — demotion is the safety net of stacked-by-default
    (same graph shape as the explicit-stacked fallback test)."""
    alice = pm.host_placement("alice")
    bob = pm.host_placement("bob")
    carole = pm.host_placement("carole")
    rep = pm.replicated_placement("rep", players=[alice, bob, carole])

    @pm.computation
    def comp(x: pm.Argument(placement=alice, dtype=pm.float64)):
        with alice:
            x_f = pm.cast(x, dtype=pm.fixed(8, 27))
            mask = pm.constant(
                np.array([True, False, True]), dtype=pm.bool_
            )
        with rep:
            y = pm.mul(x_f, x_f)
        with carole:
            y_h = pm.cast(y, dtype=pm.float64)
            out = pm.select(y_h, 0, mask)  # dynamic shape: unsupported
        return out

    rt = LocalMooseRuntime(["alice", "bob", "carole"], use_jit=False)
    assert rt.layout == "auto"
    (got,) = rt.evaluate_computation(
        comp, arguments={"x": np.array([1.0, 2.0, 3.0])}
    ).values()
    assert rt.last_plan["layout"] == "per-host"
    np.testing.assert_allclose(np.asarray(got), [1.0, 9.0], atol=1e-3)


def test_pack_banks_outside_a_trace_goes_a_bank_at_a_time():
    """Called eagerly (the validating ladder's twin) the bank stack is
    packed one bank at a time by a jitted program: widened whole, the
    forest cell's 3.3 GB stack is 12 GiB op by op and 24.5 GB of
    temporaries as one program, more than a v5e holds (PERF.md, PR 32)."""
    rng = np.random.default_rng(3)
    banks = rng.integers(0, 2, size=(16, 3, 128, 8, 5)).astype(np.uint8)
    want = (
        banks.reshape(16, 3, 4, 32, 40).astype(np.uint64)
        << np.arange(32, dtype=np.uint64).reshape(1, 1, 1, 32, 1)
    ).sum(axis=3).astype(np.uint32)
    before = rk._pack_banks_jit._cache_size()
    eager = rk._pack_banks(jnp.asarray(banks), 128)
    assert rk._pack_banks_jit._cache_size() == before + 1  # one bank's shape
    traced = jax.jit(lambda b: rk._pack_banks(b, 128))(banks)
    assert rk._pack_banks_jit._cache_size() == before + 1  # inlined there
    np.testing.assert_array_equal(np.asarray(eager), want)
    np.testing.assert_array_equal(np.asarray(traced), want)
