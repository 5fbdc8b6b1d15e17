"""Ring arithmetic unit tests, mirroring the reference's host-dialect tests
(moose/src/host tests): wrapping semantics, 128-bit limbs, shifts, matmul,
fixed-point encode/decode."""

import numpy as np
import pytest

import moose_tpu  # noqa: F401  (enables x64)
from moose_tpu.dialects import ring

M64 = 1 << 64
M128 = 1 << 128


def as_int128(lo, hi):
    lo = np.asarray(lo).astype(object)
    hi = np.asarray(hi).astype(object)
    return (hi << 64) + lo


rng = np.random.default_rng(0)


def rand_u128(shape):
    return [
        int(rng.integers(0, M64, dtype=np.uint64))
        + (int(rng.integers(0, M64, dtype=np.uint64)) << 64)
        for _ in range(int(np.prod(shape)))
    ]


class TestRing64:
    def test_wrapping_add_mul(self):
        a = np.array([2**63, 2**64 - 1, 5], dtype=np.uint64)
        b = np.array([2**63, 2, 7], dtype=np.uint64)
        lo, hi = ring.add(a, None, b, None)
        assert hi is None
        np.testing.assert_array_equal(
            np.asarray(lo), (a.astype(object) + b.astype(object)) % M64
        )
        lo, _ = ring.mul(a, None, b, None)
        np.testing.assert_array_equal(
            np.asarray(lo), (a.astype(object) * b.astype(object)) % M64
        )

    def test_neg_sub(self):
        a = np.array([0, 1, 2**63], dtype=np.uint64)
        lo, _ = ring.neg(a, None)
        np.testing.assert_array_equal(np.asarray(lo), (-a.astype(object)) % M64)

    def test_shifts(self):
        a = np.array([0xDEADBEEFCAFEBABE], dtype=np.uint64)
        lo, _ = ring.shl(a, None, 13)
        assert int(lo[0]) == (0xDEADBEEFCAFEBABE << 13) % M64
        lo, _ = ring.shr(a, None, 13)
        assert int(lo[0]) == 0xDEADBEEFCAFEBABE >> 13

    def test_matmul_native(self):
        a = rng.integers(0, M64, size=(4, 5), dtype=np.uint64)
        b = rng.integers(0, M64, size=(5, 3), dtype=np.uint64)
        lo, hi = ring.matmul(a, None, b, None)
        expected = (a.astype(object) @ b.astype(object)) % M64
        np.testing.assert_array_equal(np.asarray(lo).astype(object), expected)

    @pytest.mark.parametrize("strategy", ["limb_f32", "limb_int8"])
    def test_matmul_limb(self, strategy):
        a = rng.integers(0, M64, size=(4, 300), dtype=np.uint64)
        b = rng.integers(0, M64, size=(300, 3), dtype=np.uint64)
        ring.set_matmul_strategy(strategy)
        try:
            lo, hi = ring.matmul(a, None, b, None)
        finally:
            ring.set_matmul_strategy("native")
        expected = (a.astype(object) @ b.astype(object)) % M64
        np.testing.assert_array_equal(np.asarray(lo).astype(object), expected)


class TestRing128:
    def to_limbs(self, ints, shape):
        lo = np.array([v % M64 for v in ints], dtype=np.uint64).reshape(shape)
        hi = np.array([v >> 64 for v in ints], dtype=np.uint64).reshape(shape)
        return lo, hi

    def test_add_mul_sub(self):
        xs = rand_u128((6,))
        ys = rand_u128((6,))
        xlo, xhi = self.to_limbs(xs, (6,))
        ylo, yhi = self.to_limbs(ys, (6,))
        lo, hi = ring.add(xlo, xhi, ylo, yhi)
        np.testing.assert_array_equal(
            as_int128(lo, hi),
            np.array([(x + y) % M128 for x, y in zip(xs, ys)], dtype=object),
        )
        lo, hi = ring.mul(xlo, xhi, ylo, yhi)
        np.testing.assert_array_equal(
            as_int128(lo, hi),
            np.array([(x * y) % M128 for x, y in zip(xs, ys)], dtype=object),
        )
        lo, hi = ring.sub(xlo, xhi, ylo, yhi)
        np.testing.assert_array_equal(
            as_int128(lo, hi),
            np.array([(x - y) % M128 for x, y in zip(xs, ys)], dtype=object),
        )

    def test_shifts_cross_limb(self):
        v = 0xDEADBEEFCAFEBABE0123456789ABCDEF
        lo, hi = self.to_limbs([v], (1,))
        for amt in (0, 1, 40, 64, 70, 127):
            slo, shi = ring.shl(lo, hi, amt)
            assert as_int128(slo, shi)[0] == (v << amt) % M128, amt
            slo, shi = ring.shr(lo, hi, amt)
            assert as_int128(slo, shi)[0] == v >> amt, amt

    def test_matmul128(self):
        xs = rand_u128((3, 4))
        ys = rand_u128((4, 2))
        xlo, xhi = self.to_limbs(xs, (3, 4))
        ylo, yhi = self.to_limbs(ys, (4, 2))
        a = np.array(xs, dtype=object).reshape(3, 4)
        b = np.array(ys, dtype=object).reshape(4, 2)
        lo, hi = ring.matmul(xlo, xhi, ylo, yhi)
        np.testing.assert_array_equal(as_int128(lo, hi), (a @ b) % M128)

    @pytest.mark.parametrize("strategy", ["limb_f32", "limb_int8"])
    def test_matmul128_limb_strategies(self, strategy):
        """Every limb lowering is bit-exact against python-int ground
        truth (full-range u128 entries, k spanning odd/one/larger)."""
        for m, k, n in [(3, 33, 2), (2, 1, 2), (4, 300, 3)]:
            xs = rand_u128((m, k))
            ys = rand_u128((k, n))
            xlo, xhi = self.to_limbs(xs, (m, k))
            ylo, yhi = self.to_limbs(ys, (k, n))
            a = np.array(xs, dtype=object).reshape(m, k)
            b = np.array(ys, dtype=object).reshape(k, n)
            ring.set_matmul_strategy(strategy)
            try:
                lo, hi = ring.matmul(xlo, xhi, ylo, yhi)
            finally:
                ring.set_matmul_strategy(None)
            np.testing.assert_array_equal(
                as_int128(lo, hi), (a @ b) % M128
            )

    def test_sum(self):
        xs = rand_u128((7,))
        lo, hi = self.to_limbs(xs, (7,))
        slo, shi = ring.sum_(lo, hi, 0)
        assert as_int128(slo, shi) == sum(xs) % M128

    def test_bit_extract(self):
        v = (1 << 100) | (1 << 3)
        lo, hi = self.to_limbs([v], (1,))
        assert int(ring.bit_extract(lo, hi, 100)[0]) == 1
        assert int(ring.bit_extract(lo, hi, 3)[0]) == 1
        assert int(ring.bit_extract(lo, hi, 99)[0]) == 0


class TestFixedpoint:
    @pytest.mark.parametrize("width", [64, 128])
    def test_roundtrip(self, width):
        x = np.array([1.5, -2.25, 0.0, 1000.125, -0.0009765625])
        lo, hi = ring.fixedpoint_encode(x, 40 if width == 128 else 20, width)
        frac = 40 if width == 128 else 20
        out = np.asarray(ring.fixedpoint_decode(lo, hi, frac))
        np.testing.assert_allclose(out, x, atol=2.0 ** -frac)

    def test_negative_two_complement(self):
        x = np.array([-1.0])
        lo, hi = ring.fixedpoint_encode(x, 40, 128)
        v = as_int128(lo, hi)[0]
        assert v == M128 - (1 << 40)


class TestSampling:
    def test_deterministic(self):
        import jax.numpy as jnp

        seed = jnp.array([1, 2, 3, 4], dtype=jnp.uint32)
        a1, _ = ring.sample_uniform_seeded((4,), seed, 64)
        a2, _ = ring.sample_uniform_seeded((4,), seed, 64)
        np.testing.assert_array_equal(np.asarray(a1), np.asarray(a2))
        seed2 = jnp.array([1, 2, 3, 5], dtype=jnp.uint32)
        b, _ = ring.sample_uniform_seeded((4,), seed2, 64)
        assert not np.array_equal(np.asarray(a1), np.asarray(b))

    def test_128_limbs_differ(self):
        import jax.numpy as jnp

        seed = jnp.array([9, 9, 9, 9], dtype=jnp.uint32)
        lo, hi = ring.sample_uniform_seeded((8,), seed, 128)
        assert hi is not None
        assert not np.array_equal(np.asarray(lo), np.asarray(hi))


def _extreme_operands(kind, m, k, n, width):
    """(a, b, expected) for the extremes of the centred limbs: 0xFF limbs
    centre to 127 and de-centre to the largest diagonal, zero limbs
    centre to -128 (the largest centred product), and the mixed pair is
    the largest cancellation between product and correction."""
    full = (1 << width) - 1
    if kind == "random":
        r = np.random.default_rng(k)
        a = np.array(
            [[int.from_bytes(r.bytes(width // 8), "little")
              for _ in range(k)] for _ in range(m)], dtype=object)
        b = np.array(
            [[int.from_bytes(r.bytes(width // 8), "little")
              for _ in range(n)] for _ in range(k)], dtype=object)
    else:
        va, vb = {"ff_ff": (full, full), "00_00": (0, 0),
                  "ff_00": (full, 0)}[kind]
        a = np.full((m, k), va, dtype=object)
        b = np.full((k, n), vb, dtype=object)
    return a, b, a.dot(b) % (1 << width)


def _halves(x, width):
    lo = (x % M64).astype(np.uint64)
    hi = (x >> 64).astype(np.uint64) if width == 128 else None
    return lo, hi


_EXTREMES = ["ff_ff", "00_00", "ff_00", "random"]


def _int8_matmul_exact(k, operands, width):
    a, b, expected = _extreme_operands(operands, 2, k, 2, width)
    ring.set_matmul_strategy("limb_int8")
    try:
        lo, hi = ring.matmul(*_halves(a, width), *_halves(b, width))
    finally:
        ring.set_matmul_strategy(None)
    assert (hi is None) == (width == 64)
    got = as_int128(lo, hi) if width == 128 else np.asarray(lo).astype(object)
    np.testing.assert_array_equal(got, expected)


@pytest.mark.parametrize("operands", _EXTREMES)
@pytest.mark.parametrize("k", [2047, 2048, 2064, 2065, 4129])
def test_matmul128_int8_i32_diag_boundary(k, operands):
    """The int32 bound of a whole limb diagonal, 16 * k * 255^2 < 2^31,
    holds to k = 2064 on ring128: 2064 is the last contraction taken in
    one piece, 2065 the first in two and 4129 goes in three uneven ones.
    The extremes of the centred limbs and a random pair stay bit-exact
    against Python integers on both sides of it."""
    assert ring._int8_i32_diag_max_k(16, 16) == 2064
    _int8_matmul_exact(k, operands, 128)


@pytest.mark.parametrize("operands", _EXTREMES)
@pytest.mark.parametrize("k", [4128, 4129])
def test_matmul64_int8_i32_diag_boundary(k, operands):
    """ring64 has 8 limb pairs on its longest diagonal: one piece to
    k = 4128, two from 4129."""
    assert ring._int8_i32_diag_max_k(8, 8) == 4128
    _int8_matmul_exact(k, operands, 64)


def _count_eqns(jaxpr, pred):
    n = 0
    for eqn in jaxpr.eqns:
        n += bool(pred(eqn))
        for v in eqn.params.values():
            for sub in v if isinstance(v, (list, tuple)) else (v,):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    n += _count_eqns(inner, pred)
    return n


@pytest.mark.parametrize(
    "width,k,widenings,dots",
    [
        (128, 101, 16, 136),
        (128, 2047, 16, 136),
        (128, 2048, 16, 136),
        (128, 2064, 16, 136),
        (128, 2065, 32, 272),
        (64, 2048, 8, 36),
        (64, 4128, 8, 36),
        (64, 4129, 16, 72),
    ],
)
def test_int8_matmul_widens_once_per_diagonal_and_piece(
    width, k, widenings, dots
):
    """Where the mechanism engages is static, so it is counted in the
    traced program (nothing runs): the TPU emulates 64-bit integers, and
    a ring matmul under limb_int8 widens s32 -> s64 once per diagonal of
    each piece of the contraction, never once per limb pair."""
    import jax

    m, n = 3, 5
    a = jax.ShapeDtypeStruct((m, k), np.uint64)
    b = jax.ShapeDtypeStruct((k, n), np.uint64)
    ring.set_matmul_strategy("limb_int8")
    try:
        if width == 128:
            jaxpr = jax.make_jaxpr(ring.matmul)(a, a, b, b)
        else:
            jaxpr = jax.make_jaxpr(
                lambda x, y: ring.matmul(x, None, y, None)[0]
            )(a, b)
    finally:
        ring.set_matmul_strategy(None)

    def widens(eqn):
        return (
            eqn.primitive.name == "convert_element_type"
            and eqn.params["new_dtype"] == np.int64
            and eqn.outvars[0].aval.shape == (m, n)
        )

    assert _count_eqns(jaxpr.jaxpr, widens) == widenings
    assert _count_eqns(
        jaxpr.jaxpr, lambda e: e.primitive.name == "dot_general"
    ) == dots


def test_integer_encode_is_exact_beyond_float_mantissa():
    """Scale-0 encode of integer inputs must NOT take the float64 detour:
    secret-uint64 sharing relies on lossless lifts for values >= 2^53."""
    import numpy as np

    from moose_tpu.dialects import ring

    x = np.array([2**53 + 1, 2**63 + 5, 0, 2**64 - 1], dtype=np.uint64)
    lo, hi = ring.fixedpoint_encode(x, 0, 64)
    np.testing.assert_array_equal(np.asarray(lo), x)
    assert hi is None
    lo, hi = ring.fixedpoint_encode(x, 0, 128)
    np.testing.assert_array_equal(np.asarray(lo), x)
    np.testing.assert_array_equal(np.asarray(hi), np.zeros_like(x))
    # signed inputs sign-extend into the high limb
    s = np.array([-1, -(2**40)], dtype=np.int64)
    lo, hi = ring.fixedpoint_encode(s, 0, 128)
    np.testing.assert_array_equal(
        np.asarray(lo), s.astype(np.uint64)
    )
    np.testing.assert_array_equal(
        np.asarray(hi), np.full(2, 2**64 - 1, dtype=np.uint64)
    )


# ---------------------------------------------------------------------------
# Bit-draw domain separation (ADVICE r5 low #1): sample_bits_seeded and
# sample_uniform_seeded must never share a PRF counter stream, on EVERY
# backend — a reused seed across a uniform mask draw and a bit draw would
# otherwise yield correlated shares.
# ---------------------------------------------------------------------------


_SEP_SEED = np.array([11, 22, 33, 44], dtype=np.uint32)


@pytest.mark.parametrize("impl", ["rbg", "threefry", "aes-ctr"])
def test_bit_draw_domain_separated_from_uniform_draw(impl):
    """The bit stream must come from the TAGGED key, not the raw seed's
    stream: compare against what the UNTAGGED key would produce (the
    pre-fix behavior) and require a different draw."""
    import jax

    ring.set_prf_impl(impl)
    try:
        lo, hi = ring.sample_bits_seeded((257,), _SEP_SEED, 64)
        bits = np.asarray(lo)
        assert set(np.unique(bits)) <= {0, 1}
        if impl == "aes-ctr":
            from moose_tpu.crypto.aes_prng import AesCtrRng

            untagged = AesCtrRng(
                np.asarray(_SEP_SEED, np.uint32).tobytes()
            ).bits(257).astype(np.uint64)
        else:
            key = ring._key_from_seed(_SEP_SEED)
            untagged = np.asarray(
                jax.random.bits(key, (257,), dtype=np.uint8)
                & np.uint8(1)
            ).astype(np.uint64)
        assert not np.array_equal(bits, untagged), (
            f"{impl}: bit draw still uses the untagged uniform-stream key"
        )
    finally:
        ring.set_prf_impl("rbg")


@pytest.mark.parametrize("impl", ["rbg", "threefry", "aes-ctr"])
def test_bit_and_uniform_draws_differ_under_one_seed(impl):
    """Fixed seed, both samplers: the two outputs must be distinct
    streams (regression for the shared-counter correlation)."""
    ring.set_prf_impl(impl)
    try:
        bits, _ = ring.sample_bits_seeded((256,), _SEP_SEED, 64)
        uniform, _ = ring.sample_uniform_seeded((256,), _SEP_SEED, 64)
        assert not np.array_equal(
            np.asarray(bits), np.asarray(uniform) & np.uint64(1)
        )
        # determinism within a backend still holds
        bits2, _ = ring.sample_bits_seeded((256,), _SEP_SEED, 64)
        np.testing.assert_array_equal(np.asarray(bits), np.asarray(bits2))
    finally:
        ring.set_prf_impl("rbg")


# ---------------------------------------------------------------------------
# The PRF seam under ``threefry``: the PRF both benchmark cells and every
# deployment across trust domains run.
# ---------------------------------------------------------------------------


def test_ring_prf_impl_secure_dot_roundtrip():
    """The full secure dot is correct under threefry masks, and the
    zero-share still telescopes to zero."""
    import jax

    from moose_tpu.parallel import spmd

    ring.set_prf_impl("threefry")
    try:
        mk = np.arange(4, dtype=np.uint32) + 11
        draws = np.random.default_rng(3)
        a = draws.normal(size=(24, 24))
        b = draws.normal(size=(24, 24))

        @jax.jit
        def secure_dot(master_key, x_f, y_f):
            sess = spmd.SpmdSession(master_key)
            xs = spmd.fx_encode_share(sess, x_f, 14, 23, 128)
            ys = spmd.fx_encode_share(sess, y_f, 14, 23, 128)
            z = spmd.fx_dot(sess, xs, ys)
            return spmd.fx_reveal_decode(z)

        out = np.asarray(secure_dot(mk, a, b))
        assert np.abs(out - a @ b).max() < 1e-4

        sess = spmd.SpmdSession(mk)
        alpha_lo, alpha_hi = spmd.zero_share(sess, (5, 5), 128)
        total = np.zeros((5, 5), np.uint64)
        for i in range(3):  # wrapping u64 accumulation
            total = total + np.asarray(alpha_lo)[i]
        assert (total == 0).all()
    finally:
        ring.set_prf_impl("rbg")


def test_distributed_accepts_threefry(monkeypatch):
    # test_distributed sets the weak-PRF escape hatch process-wide;
    # clear it so the rbg rejection below is exercised for real
    monkeypatch.delenv("MOOSE_TPU_ALLOW_WEAK_PRF", raising=False)
    ring.set_prf_impl("threefry")
    try:
        ring.require_strong_prf("test")  # must not raise
    finally:
        ring.set_prf_impl("rbg")
    with pytest.raises(Exception):
        ring.require_strong_prf("test")


def test_bits_sampling_is_binary():
    ring.set_prf_impl("threefry")
    try:
        lo, hi = ring.sample_bits_seeded(
            (50, 50), np.array([1, 2, 3, 4], np.uint32), 128
        )
        a = np.asarray(lo)
        assert set(np.unique(a)) <= {0, 1}
        assert 0.4 < a.mean() < 0.6
        assert not np.asarray(hi).any()
    finally:
        ring.set_prf_impl("rbg")


def test_set_prf_impl_rejects_a_retired_name():
    """A PRF name arrives from outside the program (a deployment's
    configuration): one that is not an impl is refused by name, and the
    impl in force stays."""
    from moose_tpu.errors import ConfigurationError

    before = ring.get_prf_impl()
    with pytest.raises(ConfigurationError, match="threefry-pallas"):
        ring.set_prf_impl("threefry-pallas")
    assert ring.get_prf_impl() == before


def test_env_prf_rejects_a_retired_name_at_import():
    """``MOOSE_TPU_PRF`` is read when ``ring`` is imported: a child
    process given a name that is not an impl fails there, and the
    message names the values it may have."""
    import os
    import subprocess
    import sys

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = ""
    env["MOOSE_TPU_PRF"] = "threefry-pallas"
    out = subprocess.run(
        [sys.executable, "-c", "import moose_tpu.dialects.ring"],
        capture_output=True, text=True, env=env, timeout=300,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert out.returncode != 0
    assert "MOOSE_TPU_PRF must be one of" in out.stderr
    for name in ("rbg", "threefry", "aes-ctr"):
        assert repr(name) in out.stderr
    assert "'threefry-pallas'" in out.stderr.splitlines()[-1]
