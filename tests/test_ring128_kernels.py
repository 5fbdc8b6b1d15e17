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
from moose_tpu import metrics, telemetry
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
@pytest.mark.parametrize(
    "x_shape, y_shape",
    [((3, 6, 10), (3, 6, 1)), ((3, 6, 1), (3, 6, 10)), ((3, 1, 5), (3, 4, 1))],
)
def test_cross_terms_mul_broadcasts_as_the_lax_path(
    pallas_on, width, x_shape, y_shape
):
    """A softmax divides rows x classes by a rows x 1 sum, so the secure
    multiplication meets operands that broadcast; the kernel walks flat
    lanes and used to pair lane i of one with lane i of the other
    (PR 35: `mlp-score-batch`'s rehearsal was off by 2^47)."""
    x0, x1 = (_rand_ring(x_shape, width) for _ in range(2))
    y0, y1 = (_rand_ring(y_shape, width) for _ in range(2))
    ys = ring.add(*y0, *y1)
    want = ring.add(*ring.mul(*x0, *ys), *ring.mul(*x1, *y0))
    got = rk.cross_terms_mul(x0, x1, y0, y1, width)
    assert got[0].shape == np.broadcast_shapes(x_shape, y_shape)
    _assert_ring_equal(got, want, f"cross{x_shape}x{y_shape}/ring{width}")


def test_secure_mul_of_broadcast_operands_is_bit_identical_on_and_off():
    """The same master key through ``spmd.mul`` with the kernels on and
    off: rows x 10 times rows x 1, the softmax's division."""
    x, y = _rand_ring((5, 10), 128), _rand_ring((5, 1), 128)

    def product(on):
        rk.set_enabled(on)
        sess = spmd.SpmdSession(jnp.asarray(MK))
        xs = spmd.share(sess, *x, 128)
        ys = spmd.share(sess, *y, 128)
        return spmd.reveal(spmd.mul(sess, xs, ys))

    try:
        off, on = product(False), product(True)
    finally:
        rk.set_enabled(None)
    _assert_ring_equal(on, off, "spmd.mul broadcast")
    _assert_ring_equal(on, ring.mul(*x, *y), "spmd.mul broadcast vs plain")


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


def _rand_adder_case(shape, width):
    """Random held shares and a random AND-bank stack in the words the
    kernel reads (``spmd_math._draw_adder_banks``' layout)."""
    lo = jnp.asarray(RNG.integers(
        0, 1 << 64, size=(3, 2) + shape, dtype=np.uint64
    ))
    hi = (
        jnp.asarray(RNG.integers(
            0, 1 << 64, size=(3, 2) + shape, dtype=np.uint64
        )) if width == 128 else None
    )
    banks = jnp.asarray(RNG.integers(
        0, 1 << 32, dtype=np.uint32,
        size=(rk.adder_bank_count(width), 3)
        + rk.bank_words_shape(width, int(np.prod(shape))),
    ))
    return lo, hi, banks


# a shape with pad lanes, one with none (8 x 128 lanes: one tile), and a
# strip of the forest cell's comparison (rows x 4150 inner nodes)
@pytest.mark.parametrize(
    "width,shape",
    [
        (64, (2, 5)), (64, (8, 128)), (64, (8, 4150)),
        (128, (2, 5)),
        pytest.param(128, (8, 128), marks=pytest.mark.slow),
        (128, (8, 4150)),
    ],
)
def test_bit_decompose_and_msb_match_lax(pallas_on, width, shape):
    lo, hi, banks = _rand_adder_case(shape, width)
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
        lo, hi, banks = _rand_adder_case((3,), width)
        sm._bit_decompose_with_banks(lo, hi, width, banks)  # exact fit
        short = banks[: n - 1]
        with pytest.raises(Exception):
            sm._bit_decompose_with_banks(lo, hi, width, short)


def test_unpack_bank_reads_bit_j_of_word_j_over_32():
    """The one contract between ``spmd_math`` and the bit kernels: bit
    ``j % 32`` of word ``j // 32`` is the mask of bit plane ``j``, the
    lanes tiled (R, 128) row-major and the pad lanes dropped."""
    width, shape = 128, (3, 50)
    bank = RNG.integers(
        0, 1 << 32, size=(3,) + rk.bank_words_shape(width, 150),
        dtype=np.uint32,
    )
    assert bank.shape == (3, 4, 8, 128)
    got = np.asarray(rk.unpack_bank(jnp.asarray(bank), width, shape))
    assert got.shape == (3, width) + shape and got.dtype == np.uint8
    flat = bank.reshape(3, 4, -1)[:, :, :150]
    for j in (0, 1, 31, 32, 77, 127):
        want = (flat[:, j // 32] >> np.uint32(j % 32)) & np.uint32(1)
        assert np.array_equal(got[:, j].reshape(3, 150), want)


@pytest.mark.parametrize("width", WIDTHS)
def test_draw_adder_banks_are_words_in_the_kernels_layout(width):
    """``_draw_adder_banks`` hands over uint32 words shaped as the
    kernel's bank operand; every bit is a PRF bit of its own (balanced,
    and no party's, bank's or word plane's slice repeats another's)."""
    prf = ring.get_prf_impl()
    ring.set_prf_impl("threefry")
    try:
        sess = _fresh_session()
        x = spmd.SpmdRep(
            jnp.zeros((3, 2, 9, 130), jnp.uint64),
            jnp.zeros((3, 2, 9, 130), jnp.uint64) if width == 128 else None,
            width,
        )
        banks = np.asarray(sm._draw_adder_banks(sess, x))
    finally:
        ring.set_prf_impl(prf)
    rows = 16  # 9 * 130 = 1170 lanes: two tiles of 8 x 128
    n_ands = rk.adder_bank_count(width)
    assert banks.dtype == np.uint32
    assert banks.shape == (n_ands, 3, width // 32, rows, 128)
    bits = np.unpackbits(banks.view(np.uint8))
    assert abs(bits.mean() - 0.5) < 0.005  # 3 sigma is 0.0007 at ring64
    flat = banks.reshape(n_ands * 3 * (width // 32), -1)
    assert len({row.tobytes() for row in flat}) == len(flat)
    for j in (0, 31):  # a single bit position is balanced too
        assert abs(((banks >> np.uint32(j)) & 1).mean() - 0.5) < 0.01
    # the session moved on by one seed a bank
    assert sess._counter == n_ands


@pytest.mark.parametrize("kernels", (True, False), ids=("on", "off"))
def test_msb_traces_no_byte_bank_stack(kernels):
    """The forest cell's comparison, (128, 4150) at ring128, traced:
    the PRF is asked for the packed words and nothing else, and no
    uint8 value of the bank stack's size exists (the parent drew 3.26 GB
    of them and packed them 32 to a word)."""
    shape, width = (128, 4150), 128
    n = 128 * 4150
    L, R, C = rk.bank_words_shape(width, n)
    assert (L, R, C) == (4, 4152, 128)
    n_ands = rk.adder_bank_count(width)
    x = jax.ShapeDtypeStruct((3, 2) + shape, jnp.uint64)

    def go(mk, lo, hi):
        sess = spmd.SpmdSession(mk)
        return sm.msb(sess, spmd.SpmdRep(lo, hi, width)).arr

    def drawn(form):
        return metrics.REGISTRY.value(
            "moose_tpu_bit_bank_draw_bytes_total", form=form
        )

    before = drawn("words"), drawn("bytes")
    rk.set_enabled(kernels)
    try:
        jaxpr = jax.make_jaxpr(go)(
            jax.ShapeDtypeStruct((4,), jnp.uint32), x, x
        )
    finally:
        rk.set_enabled(None)
    assert drawn("words") - before[0] == n_ands * 3 * L * R * 128 * 4
    assert drawn("bytes") == before[1]

    sizes = {}

    def walk(jp):
        for eqn in jp.eqns:
            for v in eqn.outvars:
                dt = getattr(v.aval, "dtype", None)
                if dt in (jnp.uint8, jnp.uint32):  # (PRNG keys are neither)
                    sizes.setdefault(np.dtype(dt).name, []).append(
                        int(np.prod(v.aval.shape))
                    )
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jaxpr.jaxpr)
    # the words: one value of the whole stack, drawn as such; nothing
    # wider but, with the kernels off, one bank's planes on their way
    # from a word's bits to bytes (a fused elementwise chain under jit)
    stack_words = n_ands * 3 * L * R * 128
    assert stack_words in sizes["uint32"]
    assert max(sizes["uint32"]) == (
        stack_words if kernels else max(stack_words, 3 * width * n)
    )
    # bytes: with the kernel, the result's top plane at most; without,
    # the twin's own bit shares (3, 2, k, n), an eighth of the parent's
    # stack, and one bank at a time as bytes (3 x k x padded lanes)
    largest_u8 = max(sizes["uint8"])
    assert largest_u8 <= (3 * 2 * R * 128 if kernels else 3 * 2 * width * n)
    assert largest_u8 <= n_ands * 3 * width * n // 8  # the parent's stack


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


_ADDER_FNS = {
    "msb": lambda sess, t: sm.msb(sess, t).arr,
    "bit_decompose": lambda sess, t: sm.bit_decompose(sess, t).arr,
}


@pytest.mark.parametrize("fn", sorted(_ADDER_FNS))
@pytest.mark.parametrize(
    "width", [64, pytest.param(128, marks=pytest.mark.slow)]
)
def test_msb_bit_identical_on_off(width, fn):
    x = RNG.normal(size=(2, 5))

    def go(sess):
        xs = spmd.fx_encode_share(sess, x, 8, 12, width)
        return _ADDER_FNS[fn](sess, xs.tensor)

    on, off = _run_both(go)
    assert np.array_equal(np.asarray(on), np.asarray(off))
    # and the bits are the value's: the masks cancel
    bits = np.asarray(on)
    plain = bits[0, 0] ^ bits[1, 0] ^ bits[2, 0]
    sign = plain if fn == "msb" else plain[width - 1]
    assert np.array_equal(sign, (x < 0).astype(np.uint8))


@pytest.mark.parametrize("fn", sorted(_ADDER_FNS))
def test_adder_error_fallback_replays_same_banks(monkeypatch, fn):
    """A bit kernel that dies AFTER its banks were drawn must not skew
    the stream: the fallback runs the lax twin on the SAME words, so the
    result equals the kernels-off run bit for bit."""
    x = RNG.normal(size=(3, 4))

    def go(sess):
        xs = spmd.fx_encode_share(sess, x, 8, 12, 64)
        out = _ADDER_FNS[fn](sess, xs.tensor)
        # what is drawn after the banks sits where it sat
        return out, sess.sample_bit_bank((2,))

    rk.reset_state()
    rk.set_enabled(False)
    try:
        want = go(_fresh_session())
    finally:
        rk.set_enabled(None)
    rk.reset_state()
    rk.set_enabled(True)
    before = metrics.REGISTRY.value(
        "moose_tpu_pallas_fallback_total", kernel=fn, reason="error"
    )

    def boom(*a, **k):
        raise RuntimeError("synthetic kernel failure")

    monkeypatch.setattr(rk, fn, boom)
    try:
        got = go(_fresh_session())
    finally:
        rk.set_enabled(None)
        rk.reset_state()
    for g, w in zip(got, want):
        assert np.array_equal(np.asarray(g), np.asarray(w))
    after = metrics.REGISTRY.value(
        "moose_tpu_pallas_fallback_total", kernel=fn, reason="error"
    )
    assert after == before + 1


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
    with telemetry.span("root") as root:
        assert not rk.dispatch("trunc_combine", 64)
    (check,) = root.children
    assert (check.name, check.attrs["verdict"]) == ("pallas_selfcheck", reason)
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


def test_first_use_check_is_one_span_a_kernel_and_width(pallas_on, monkeypatch):
    """ISSUE 37: the check is a ``pallas_selfcheck`` span of the tree
    of whatever met the kernel first (it was a ``profiling.phase``,
    which records nothing outside a capture), its worker thread's
    compiles land on it, an active capture still gets it on its
    timeline through the span hook, and a second dispatch opens none."""
    from moose_tpu import profiling

    compile_event = "/jax/core/compile/backend_compile_duration"
    saved = dict(rk._STATE)
    rk.reset_state()
    monkeypatch.setitem(  # a check that only "compiles", on its thread
        rk._CHECKS, "trunc_combine",
        lambda width: jax.monitoring.record_event_duration_secs(
            compile_event, 0.25
        ),
    )
    profiling.start()
    try:
        with telemetry.span("root") as root:
            for width in WIDTHS:
                assert rk.dispatch("trunc_combine", width)
            assert rk.dispatch("trunc_combine", 64)
    finally:
        doc = profiling.stop()
        rk.reset_state()
        rk._STATE.update(saved)
    assert [(c.name, c.attrs) for c in root.children] == [
        ("pallas_selfcheck", {
            "kernel": "trunc_combine", "width": width, "verdict": "ok",
            "backend_compile_s": 0.25, "compiles": 1,
        })
        for width in WIDTHS
    ]
    timeline = [
        e["args"]["width"] for e in doc["traceEvents"]
        if e.get("ph") == "X" and e["name"] == "pallas_selfcheck"
    ]
    assert timeline == list(WIDTHS)


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
