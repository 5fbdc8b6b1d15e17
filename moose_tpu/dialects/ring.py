"""Ring arithmetic over Z_{2^64} and Z_{2^128} on JAX arrays.

TPU-native re-design of the reference's ``HostRingTensor<u64/u128>`` kernels
(``moose/src/host/ops.rs``): the reference uses ndarray ``Wrapping<u64/u128>``
on CPU.  TPUs have no native u128, so ring128 values are two-limb ``(hi, lo)``
uint64 arrays; all carries are explicit.  XLA's unsigned integer arithmetic
wraps, which is exactly ring semantics, so ring64 ops map 1:1 onto uint64
lanes.

Matmul strategies: the MXU only natively multiplies small floats/ints, so
large ring matmuls can either use XLA's emulated u64 dot (``native``) or a
limb-decomposition onto exact f32 matmuls (``limb_f32``) that ride the MXU:
u64 is split into 8-bit limbs, limb products are exact in f32 for contraction
chunks <= 256, partial sums recombine with shifts mod 2^64/2^128.
"""

from __future__ import annotations

import functools
import os as _os
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

U64 = jnp.uint64
MASK32 = np.uint64(0xFFFFFFFF)

# Matmul strategy; "native" (XLA integer dot; CPU only — TPU XLA cannot
# rewrite u64 dot_general), "limb_f32" (MXU bf16 limb decomposition),
# "limb_int8" (centered s8 MXU path; the s8*s8->s32 MXU rate is twice
# the bf16 one on a v5e, and a contraction is cut only past the k whose
# whole limb diagonals still fit s32: 2064 for ring128) or
# "limb_f64" (16-bit limbs over f64 dgemms, the CPU path).  None =
# auto-select by backend; a programmatic set_matmul_strategy() wins, and
# set_matmul_strategy(None) restores the auto default.
_MATMUL_STRATEGY: Optional[str] = None

_STRATEGIES = (None, "native", "limb_f32", "limb_int8", "limb_f64")


def set_matmul_strategy(name: Optional[str]) -> None:
    """Select the ring matmul lowering: None (auto), "native" (XLA u64
    dot), "limb_f32" (8-bit limbs on bf16/f32 MXU matmuls, chunked), or
    "limb_int8" (8-bit limbs centered into s8 feeding the native
    s8*s8->s32 MXU path — 2x bf16 throughput on v5e; a diagonal's limb
    pairs are summed in s32 and widened once, exact while
    pairs * k * 255^2 < 2^31, and a longer contraction, up to 2^17
    terms, goes by near-equal pieces within that bound)."""
    global _MATMUL_STRATEGY
    if name not in _STRATEGIES:
        from ..errors import ConfigurationError

        raise ConfigurationError(
            "matmul strategy must be None, 'native', 'limb_f32', "
            f"'limb_int8' or 'limb_f64', got {name!r}"
        )
    _MATMUL_STRATEGY = name


def get_matmul_strategy() -> str:
    if _MATMUL_STRATEGY is not None:
        return _MATMUL_STRATEGY
    # Auto.  TPU: the centered-int8 MXU path (u64 dot_general does not
    # lower there, and s8 limbs take k <= 2064 in one piece).
    # CPU: 16-bit limbs over f64 dgemms (Eigen/BLAS) — XLA's integer
    # dot has no BLAS path there and is ~12x slower at 1000^3 (measured
    # 35 s vs 2.9 s for the u128 matmul on one host).  The measurement
    # is CPU-specific: consumer GPUs throttle f64, so any other backend
    # keeps the native integer dot.
    backend = jax.default_backend()
    if backend == "tpu":
        return "limb_int8"
    return "limb_f64" if backend == "cpu" else "native"


# ---------------------------------------------------------------------------
# u64 helpers
# ---------------------------------------------------------------------------


def mulhi_u64(a, b):
    """High 64 bits of the 128-bit product of two uint64 arrays, via 32-bit
    halves (4 multiplies, schoolbook)."""
    a = a.astype(U64)
    b = b.astype(U64)
    al = a & MASK32
    ah = a >> np.uint64(32)
    bl = b & MASK32
    bh = b >> np.uint64(32)
    t = al * bl
    u = ah * bl + (t >> np.uint64(32))
    v = al * bh + (u & MASK32)
    return ah * bh + (u >> np.uint64(32)) + (v >> np.uint64(32))


def mulwide_u64(a, b):
    """(hi, lo) 128-bit product of uint64 arrays."""
    return mulhi_u64(a, b), (a.astype(U64) * b.astype(U64))


# ---------------------------------------------------------------------------
# Ring element ops.  A ring value is (lo, hi) with hi=None for width 64.
# ---------------------------------------------------------------------------


def add(lo1, hi1, lo2, hi2):
    lo = lo1 + lo2
    if hi1 is None:
        return lo, None
    carry = (lo < lo1).astype(U64)
    return lo, hi1 + hi2 + carry


def sub(lo1, hi1, lo2, hi2):
    lo = lo1 - lo2
    if hi1 is None:
        return lo, None
    borrow = (lo1 < lo2).astype(U64)
    return lo, hi1 - hi2 - borrow


def neg(lo, hi):
    if hi is None:
        return (jnp.zeros_like(lo) - lo), None
    nlo = jnp.zeros_like(lo) - lo
    borrow = (lo != 0).astype(U64)
    return nlo, jnp.zeros_like(hi) - hi - borrow


def mul(lo1, hi1, lo2, hi2):
    if hi1 is None:
        return lo1 * lo2, None
    p_hi, p_lo = mulwide_u64(lo1, lo2)
    hi = p_hi + lo1 * hi2 + hi1 * lo2
    return p_lo, hi


def shl(lo, hi, amount: int):
    """Logical left shift by a static amount."""
    amount = int(amount)
    if hi is None:
        if amount >= 64:
            return jnp.zeros_like(lo), None
        return lo << np.uint64(amount), None
    if amount == 0:
        return lo, hi
    if amount >= 128:
        return jnp.zeros_like(lo), jnp.zeros_like(hi)
    if amount >= 64:
        return jnp.zeros_like(lo), lo << np.uint64(amount - 64)
    a = np.uint64(amount)
    return lo << a, (hi << a) | (lo >> np.uint64(64 - amount))


def shr(lo, hi, amount: int):
    """Logical right shift by a static amount."""
    amount = int(amount)
    if hi is None:
        if amount >= 64:
            return jnp.zeros_like(lo), None
        return lo >> np.uint64(amount), None
    if amount == 0:
        return lo, hi
    if amount >= 128:
        return jnp.zeros_like(lo), jnp.zeros_like(hi)
    if amount >= 64:
        return hi >> np.uint64(amount - 64), jnp.zeros_like(hi)
    a = np.uint64(amount)
    return (lo >> a) | (hi << np.uint64(64 - amount)), hi >> a


def shr_arith(lo, hi, amount: int):
    """Arithmetic (sign-extending) right shift by a static amount.

    Used for plaintext host fixed-point truncation (the reference truncates
    host fixed tensors with a signed shift, fixedpoint/ops.rs host kernels);
    the secure replicated path uses TruncPr instead.
    """
    amount = int(amount)
    if hi is None:
        if amount == 0:
            return lo, None
        amount = min(amount, 63)
        return (lo.astype(jnp.int64) >> np.int64(amount)).astype(U64), None
    if amount == 0:
        return lo, hi
    sign_fill = (hi.astype(jnp.int64) >> np.int64(63)).astype(U64)
    if amount >= 128:
        return sign_fill, sign_fill
    if amount >= 64:
        a = min(amount - 64, 63)
        new_lo = (hi.astype(jnp.int64) >> np.int64(a)).astype(U64)
        if amount == 64:
            new_lo = hi
        return new_lo, sign_fill
    a = np.uint64(amount)
    new_lo = (lo >> a) | (hi << np.uint64(64 - amount))
    new_hi = (hi.astype(jnp.int64) >> np.int64(amount)).astype(U64)
    return new_lo, new_hi


def bit_extract(lo, hi, bit_idx: int):
    """Extract bit ``bit_idx`` as a uint8 0/1 array."""
    bit_idx = int(bit_idx)
    if bit_idx < 64:
        return ((lo >> np.uint64(bit_idx)) & np.uint64(1)).astype(jnp.uint8)
    return ((hi >> np.uint64(bit_idx - 64)) & np.uint64(1)).astype(jnp.uint8)


def from_bit(bit, width: int):
    """Inject a 0/1 uint8 array into the ring (RingInject with bit_idx=0)."""
    lo = bit.astype(U64)
    hi = jnp.zeros_like(lo) if width == 128 else None
    return lo, hi


def fill_like_shape(shape, width: int, value: int):
    value = int(value) % (1 << width)
    lo = jnp.full(shape, np.uint64(value & 0xFFFFFFFFFFFFFFFF), dtype=U64)
    if width == 64:
        return lo, None
    hi = jnp.full(shape, np.uint64(value >> 64), dtype=U64)
    return lo, hi


def equal_bits(lo1, hi1, lo2, hi2):
    """Plaintext ring equality -> uint8 0/1."""
    eq = lo1 == lo2
    if hi1 is not None:
        eq = jnp.logical_and(eq, hi1 == hi2)
    return eq.astype(jnp.uint8)


# ---------------------------------------------------------------------------
# Sampling (counter-based PRF on device).
#
# The reference derives seeds with blake3 and expands them with AES-128-CTR
# (``host/prim.rs:113-133``).  On TPU we expand seeds with XLA's native
# ``RngBitGenerator`` (Philox counter PRF, ONE fused HLO op) via JAX's
# ``rbg`` PRNG implementation.  The protocol only needs the *same seed* to
# yield the *same stream on every party holding it*; Philox provides that
# deterministically within a backend.  The threefry path (a stronger,
# reduced-Threefish PRF, ~100 HLO ops per draw) is available via
# ``set_prf_impl("threefry")`` for strict deployments — a documented
# deviation either way, since neither is the reference's AES-CTR.
#
# IMPORTANT: rbg streams are only guaranteed identical within one backend
# and jaxlib version.  Heterogeneous distributed deployments (parties on
# different backends) MUST use ``set_prf_impl("threefry")`` (backend-
# deterministic); the distributed runtime enforces backend homogeneity
# otherwise.
# ---------------------------------------------------------------------------

# Default: fast Philox ("rbg") for single-trust-domain local simulation;
# "threefry" (a real reduced-Threefish PRF) for anything deployed across
# trust domains.  Distributed runtimes call ``require_strong_prf()`` and
# refuse to run on rbg unless MOOSE_TPU_ALLOW_WEAK_PRF=1 is set explicitly.
_PRF_IMPLS = ("rbg", "threefry", "aes-ctr")
_PRF_IMPL = _os.environ.get("MOOSE_TPU_PRF", "rbg")
if _PRF_IMPL not in _PRF_IMPLS:
    raise ValueError(
        f"MOOSE_TPU_PRF must be one of {_PRF_IMPLS}, got {_PRF_IMPL!r}"
    )


def set_prf_impl(name: str) -> None:
    """Select the PRF: "rbg" (fast Philox; local simulation), "threefry"
    (cryptographic, jittable), or "aes-ctr"
    (the REFERENCE's construction — blake3 seed derivation +
    AES-128-CTR expansion on the host, for bit-compatibility checks
    against pymoose; eager-only)."""
    global _PRF_IMPL
    if name not in _PRF_IMPLS:
        from ..errors import ConfigurationError

        raise ConfigurationError(
            f"PRF impl must be one of {_PRF_IMPLS}, got {name!r}"
        )
    _PRF_IMPL = name


def get_prf_impl() -> str:
    return _PRF_IMPL


def require_strong_prf(context: str) -> None:
    """Refuse the non-cryptographic default PRF outside local simulation.

    The reference uses blake3 + AES-128-CTR everywhere (host/prim.rs:113);
    our rbg default (Philox with a linear key/nonce mix) is fine when all
    three parties live in one trust domain (one XLA program) but is an
    unsafe source of share masks across genuinely distrusting parties.
    """
    # threefry and aes-ctr are both real PRFs; only rbg is gated
    if _PRF_IMPL == "rbg" and _os.environ.get(
        "MOOSE_TPU_ALLOW_WEAK_PRF"
    ) != "1":
        from ..errors import ConfigurationError

        raise ConfigurationError(
            f"{context} requires a cryptographic PRF: call "
            "moose_tpu.dialects.ring.set_prf_impl('threefry') (or set "
            "MOOSE_TPU_PRF=threefry); set MOOSE_TPU_ALLOW_WEAK_PRF=1 only "
            "for testing"
        )


def _key_from_seed(seed_u32x4):
    """Wrap a uint32[4] seed as a PRNG key of the active implementation."""
    k = jnp.asarray(seed_u32x4, dtype=jnp.uint32)
    if _PRF_IMPL == "rbg":
        return jax.random.wrap_key_data(k, impl="rbg")
    data = (k[0].astype(U64) << np.uint64(32)) | k[1].astype(U64)
    data2 = (k[2].astype(U64) << np.uint64(32)) | k[3].astype(U64)
    return jax.random.key(data ^ (data2 * np.uint64(0x9E3779B97F4A7C15)))


def mix_seed(seed_u32x4, nonce_u32x4):
    """Derive a fresh 128-bit seed from (key, public nonce) on device.

    Replaces the reference's blake3 keyed hash (host/prim.rs:123).  One
    Philox draw keyed by key^nonce-mix: distinct nonces index distinct
    Philox counters, so derived seeds are computationally independent under
    the PRF assumption on Philox/Threefry.
    """
    k = jnp.asarray(seed_u32x4, dtype=jnp.uint32)
    n = jnp.asarray(nonce_u32x4, dtype=jnp.uint32)
    mixed = k ^ (n * np.uint32(0x9E3779B9) + np.uint32(0x85EBCA6B))
    key = _key_from_seed(mixed)
    return jax.random.bits(key, (4,), dtype=jnp.uint32)


def _concrete_seed_bytes(seed_u32x4) -> bytes:
    """Seed words -> 16 bytes; rejects tracers (the aes-ctr PRF runs on
    the host and cannot live inside a jitted program)."""
    import jax.core as _core

    if isinstance(seed_u32x4, _core.Tracer):
        from ..errors import ConfigurationError

        raise ConfigurationError(
            "the aes-ctr PRF is host-side (numpy blake3 + AES) and "
            "cannot run under jit; evaluate eagerly (MOOSE_TPU_JIT=0 / "
            "use_jit=False) when using set_prf_impl('aes-ctr')"
        )
    return np.asarray(seed_u32x4, dtype=np.uint32).tobytes()


def sample_uniform_seeded(shape, seed_u32x4, width: int):
    shape = tuple(int(s) for s in shape)
    if _PRF_IMPL == "aes-ctr":
        from ..crypto.aes_prng import AesCtrRng

        rng = AesCtrRng(_concrete_seed_bytes(seed_u32x4))
        n = int(np.prod(shape)) if shape else 1
        if width == 64:
            return jnp.asarray(rng.uniform_u64(n).reshape(shape)), None
        lo, hi = rng.uniform_u128(n)
        return (
            jnp.asarray(lo.reshape(shape)),
            jnp.asarray(hi.reshape(shape)),
        )
    key = _key_from_seed(seed_u32x4)
    if width == 64:
        return jax.random.bits(key, shape, dtype=U64), None
    # one draw for both limbs (avoids key splits, which are expensive for
    # non-rbg impls and needless here)
    both = jax.random.bits(key, (2,) + shape, dtype=U64)
    return both[1], both[0]


def _bit_domain_seed(seed_u32x4):
    """Domain-separation tag for BIT draws: flip a high key bit so a
    seed reused across a uniform draw (:func:`sample_uniform_seeded`)
    and a bit draw can never index the same PRF counter stream.
    Applied uniformly in EVERY backend branch: an impl left untagged
    would share one stream between its two kinds of draw."""
    return jnp.asarray(seed_u32x4, dtype=jnp.uint32) ^ jnp.asarray(
        [0, 0, 0, 0x80000000], dtype=jnp.uint32
    )


def sample_bits_seeded(shape, seed_u32x4, width: int):
    shape = tuple(int(s) for s in shape)
    if _PRF_IMPL == "aes-ctr":
        from ..crypto.aes_prng import AesCtrRng

        rng = AesCtrRng(
            _concrete_seed_bytes(_bit_domain_seed(seed_u32x4))
        )
        n = int(np.prod(shape)) if shape else 1
        lo = jnp.asarray(rng.bits(n).reshape(shape).astype(np.uint64))
        hi = jnp.zeros_like(lo) if width == 128 else None
        return lo, hi
    key = _key_from_seed(_bit_domain_seed(seed_u32x4))
    bits = jax.random.bits(key, shape, dtype=jnp.uint8) & jnp.uint8(1)
    lo = bits.astype(U64)
    hi = jnp.zeros_like(lo) if width == 128 else None
    return lo, hi


def sample_bit_words_seeded(shape, seeds):
    """Mask bits 32 to a uint32 word: one draw of ``shape`` for each of
    ``seeds``, (count, *shape), joined by the draw itself and not by a
    stack of finished draws.  Every bit of every word is a PRF output
    bit of its own, under :func:`_bit_domain_seed`'s tag as the byte
    form is; for the same bits the PRF is asked for an eighth of the
    words :func:`sample_bits_seeded` asks for."""
    shape = tuple(int(s) for s in shape)
    if _PRF_IMPL == "aes-ctr":
        from ..crypto.aes_prng import AesCtrRng

        n = int(np.prod(shape)) if shape else 1
        # the reference's bit stream: bit i at bit i % 32 of word i // 32
        return jnp.asarray(np.stack([
            np.packbits(
                AesCtrRng(
                    _concrete_seed_bytes(_bit_domain_seed(seed))
                ).bits(32 * n),
                bitorder="little",
            ).view("<u4").reshape(shape)
            for seed in seeds
        ]))
    return jax.vmap(
        lambda seed: jax.random.bits(
            _key_from_seed(_bit_domain_seed(seed)), shape, dtype=jnp.uint32
        )
    )(jnp.stack(seeds))


# ---------------------------------------------------------------------------
# Contractions (Dot / matmul / sum)
# ---------------------------------------------------------------------------


def sum_(lo, hi, axis):
    """Sum-reduce; wrapping accumulation is exact ring semantics for u64;
    for u128 we accumulate limbs with carry counting."""
    if hi is None:
        return jnp.sum(lo, axis=axis, dtype=U64), None
    # Accumulate lo with carry tracking: process via cumulative trick —
    # sum of N uint64 values needs carry counts.  We chunk: add one by one is
    # O(N); instead reduce pairwise with lax.reduce?  Simpler: use 32-bit
    # split so partial sums are exact in u64, then recombine.
    lo_lo = lo & MASK32
    lo_hi = lo >> np.uint64(32)
    s_ll = jnp.sum(lo_lo, axis=axis, dtype=U64)
    s_lh = jnp.sum(lo_hi, axis=axis, dtype=U64)
    s_hi = jnp.sum(hi, axis=axis, dtype=U64)
    # result_lo128 = s_ll + (s_lh << 32), exact carries:
    lo_out = s_ll + (s_lh << np.uint64(32))
    carry = (s_lh >> np.uint64(32)) + (
        ((s_ll + ((s_lh & MASK32) << np.uint64(32))) < s_ll).astype(U64)
    )
    return lo_out, s_hi + carry


def _matmul_u64_native(a, b):
    return jax.lax.dot_general(
        a, b, (((a.ndim - 1,), (0,)), ((), ())), preferred_element_type=U64
    )


def _limbs8_bf16(x, n_limbs: int):
    """Split a uint64 array holding values < 2^(8*n_limbs) into 8-bit limbs
    cast to bfloat16 (integers 0..255 are exactly representable in bf16)."""
    return [
        ((x >> np.uint64(8 * i)) & np.uint64(0xFF)).astype(jnp.bfloat16)
        for i in range(n_limbs)
    ]


_CHUNK = 256  # limb products < 2^16; 256-term f32 accumulation stays < 2^24


def _limb_matmul_pairs(a, b, in_limbs: int, out_limbs: int):
    """Exact limb-decomposed matmul on the MXU.

    ``a`` (m, k) and ``b`` (k, n) hold uint64 values < 2^(8*in_limbs).
    Returns the list of per-diagonal partial sums [S_0 .. S_{out_limbs-1}]
    as uint64 arrays, where S_s = sum_{i+j=s} A_i @ B_j and only s <
    out_limbs is produced (higher limbs are truncated by the ring modulus).

    Path: bf16 limbs -> MXU matmul with f32 accumulation (exact: products
    < 2^16, chunked contraction of 256 terms < 2^24) -> u64 accumulation
    across chunks (exact for any contraction length).
    """
    k = a.shape[-1]
    pad = (-k) % _CHUNK
    if pad:
        a = jnp.pad(a, [(0, 0)] * (a.ndim - 1) + [(0, pad)])
        b = jnp.pad(b, [(0, pad)] + [(0, 0)] * (b.ndim - 1))
    nchunks = (k + pad) // _CHUNK
    m, n = a.shape[0], b.shape[-1]
    la = [
        x.reshape(m, nchunks, _CHUNK).transpose(1, 0, 2)
        for x in _limbs8_bf16(a, in_limbs)
    ]
    lb = [
        x.reshape(nchunks, _CHUNK, n) for x in _limbs8_bf16(b, in_limbs)
    ]
    diags = []
    for s in range(out_limbs):
        ps = None
        for i in range(min(s + 1, in_limbs)):
            j = s - i
            if j >= in_limbs:
                continue
            # batched over chunks: (c,m,256) @ (c,256,n) -> (c,m,n) in f32
            p = jax.lax.dot_general(
                la[i], lb[j], (((2,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32,
            )
            # exact: convert to integer before cross-chunk/pair accumulation
            pi = jnp.sum(p.astype(U64), axis=0)
            ps = pi if ps is None else ps + pi
        diags.append(ps if ps is not None else jnp.zeros((m, n), dtype=U64))
    return diags


_INT8_MAX_K = (1 << 17) - 1  # s32 accumulation exact: k * 128^2 < 2^31


@jax.named_scope("moose/limb_split")
def _limbs8_s8_centered(x, n_limbs: int):
    """Split u64 values < 2^(8*n_limbs) into 8-bit limbs centered into
    int8: limb' = limb - 128 in [-128, 127]."""
    return [
        (
            ((x >> np.uint64(8 * i)) & np.uint64(0xFF)).astype(jnp.int32)
            - 128
        ).astype(jnp.int8)
        for i in range(n_limbs)
    ]


def _int8_i32_diag_max_k(in_limbs: int, out_limbs: int) -> int:
    """Largest contraction whose de-centered diagonals fit int32.

    A de-centered limb product is at most 255^2 per term and the longest
    diagonal of an (in_limbs, out_limbs) call sums min(in_limbs,
    out_limbs) pairs, so a diagonal stays below 2^31 while
    pairs * k * 255^2 <= 2^31 - 1: k <= 2064 for the 16 limbs of
    ring128, 4128 for ring64's 8, 16512 for 2."""
    return ((1 << 31) - 1) // (min(in_limbs, out_limbs) * 255 * 255)


@jax.named_scope("moose/limb_matmul")
def _int8_pair_diags(la, lb, out_limbs: int, k: int):
    """Per-diagonal sums S_s = sum_{i+j=s} A_i . B_j over centered s8 limb
    lists, as u64 arrays.

    Unsigned 8-bit limbs don't fit int8, so limbs are centered
    (limb - 128) and products de-centered with rank-1 corrections:
      A_i . B_j = A'_i . B'_j + 128*(rowsum(A'_i) + colsum(B'_j)) + 128^2*k
    Centered products accumulate exactly in s32 for k <= 2^17.  On v5e
    int8 matmul runs at 2x bf16 throughput.

    Whole diagonals are summed in s32 and widened to 64 bits once each
    (:func:`_int8_pair_diags_pairs_i32`), which is exact while k is
    within :func:`_int8_i32_diag_max_k`.  A longer contraction is cut
    along k into ceil(k / limit) near-equal pieces, each de-centered
    from its own k, and the pieces' u64 diagonals are added: the TPU
    emulates every 64-bit operation, so the widenings are kept to
    out_limbs a piece.  A single piece (every k within the limit) keeps
    k an ordinary per-array contraction dim, so a sharded k partitions
    as local partial dots + all-reduce.
    """
    limit = _int8_i32_diag_max_k(len(la), out_limbs)
    pieces = max(1, -(-k // limit))
    if pieces == 1:
        return _int8_pair_diags_pairs_i32(la, lb, out_limbs, k)
    bounds = [k * p // pieces for p in range(pieces + 1)]
    diags = None
    for lo, hi in zip(bounds, bounds[1:]):
        d = _int8_pair_diags_pairs_i32(
            [x[:, lo:hi] for x in la], [x[lo:hi, :] for x in lb],
            out_limbs, hi - lo,
        )
        diags = d if diags is None else [x + y for x, y in zip(diags, d)]
    return diags


def _int8_pair_diags_pairs_i32(la, lb, out_limbs: int, k: int):
    """Per-pair dot_generals with s32 diagonal accumulation, for k within
    :func:`_int8_i32_diag_max_k`: one widening per diagonal."""
    # de-centering correction vectors, exact in s32 (k*128 < 2^31).
    # dtype pinned: under x64 mode jnp.sum would silently promote to
    # int64, dragging every correction into emulated 64-bit arithmetic
    # on TPU
    ra = [
        jnp.sum(x.astype(jnp.int32), axis=-1, dtype=jnp.int32) for x in la
    ]  # (m,)
    cb = [
        jnp.sum(x.astype(jnp.int32), axis=0, dtype=jnp.int32) for x in lb
    ]  # (n,)
    in_limbs = len(la)
    bias = jnp.int32(128 * 128 * k)
    m, n = la[0].shape[0], lb[0].shape[-1]
    diags = []
    for s in range(out_limbs):
        ps = None
        for i in range(min(s + 1, in_limbs)):
            j = s - i
            if j >= in_limbs:
                continue
            p = jax.lax.dot_general(
                la[i], lb[j], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32,
            )
            p = p + (
                jnp.int32(128) * (ra[i][:, None] + cb[j][None, :]) + bias
            )
            ps = p if ps is None else ps + p
        if ps is None:
            diags.append(jnp.zeros((m, n), dtype=U64))
        else:
            diags.append(ps.astype(jnp.int64).astype(U64))
    return diags


def _limb_matmul_pairs_int8(a, b, in_limbs: int, out_limbs: int):
    """Int8-MXU variant of :func:`_limb_matmul_pairs` (same contract)."""
    k = a.shape[-1]
    if k > _INT8_MAX_K:
        # rare: a single centered product would leave s32; the chunked
        # f32 path takes it
        return _limb_matmul_pairs(a, b, in_limbs, out_limbs)
    return _int8_pair_diags(
        _limbs8_s8_centered(a, in_limbs),
        _limbs8_s8_centered(b, in_limbs),
        out_limbs,
        k,
    )


def _limb_pairs(a, b, in_limbs: int, out_limbs: int):
    if get_matmul_strategy() == "limb_int8":
        return _limb_matmul_pairs_int8(a, b, in_limbs, out_limbs)
    return _limb_matmul_pairs(a, b, in_limbs, out_limbs)


# f64 dgemm of 16-bit limbs: products < 2^32, so a 2^20-term contraction
# stays < 2^52 — inside the f64 mantissa, hence exact
_F64_CHUNK = 1 << 20

# below this m*k*n the 36-dgemm decomposition costs more in dispatch than
# the native integer dot costs in math (the native path only falls off a
# cliff on big contractions where Eigen/BLAS would vectorize)
_F64_MIN_WORK = 1 << 21

# Exactness ceiling of the f64 path's u64 diagonal accumulation: each
# 16-bit-limb product is < 2^32 and a diagonal sums up to 8 limb pairs
# over k terms in uint64, so 8 * k * 2^32 must stay < 2^64 -> k <= 2^28.
# Beyond it the lost carries would silently corrupt the high limb; the
# strategy selectors below fall back to the generic limb path instead.
_F64_MAX_K = 1 << 28


def _limbs16_f64(x, n_limbs: int):
    """Split a uint64 array into 16-bit limbs cast to float64 (integers
    below 2^16 are exactly representable)."""
    return [
        ((x >> np.uint64(16 * i)) & np.uint64(0xFFFF)).astype(jnp.float64)
        for i in range(n_limbs)
    ]


def _f64_pair_diags(la, lb, out_limbs: int, k: int, m: int, n: int):
    """Per-diagonal pair sums S_s = sum_{i+j=s} A_i @ B_j over pre-split
    f64 limb lists (values < 2^16), chunked so every contraction stays
    exact in the f64 mantissa; returns u64 arrays for s < out_limbs.
    Single owner of the chunk/pad layout — both the u64 and u128 f64
    paths go through here so the exactness bound lives in one place."""
    in_limbs = len(la)
    chunked = k > _F64_CHUNK
    if chunked:
        pad = (-k) % _F64_CHUNK
        nchunks = (k + pad) // _F64_CHUNK
        la = [
            jnp.pad(x, [(0, 0), (0, pad)])
            .reshape(m, nchunks, _F64_CHUNK).transpose(1, 0, 2)
            for x in la
        ]
        lb = [
            jnp.pad(x, [(0, pad), (0, 0)])
            .reshape(nchunks, _F64_CHUNK, n)
            for x in lb
        ]
    diags = []
    for s in range(out_limbs):
        ps = None
        for i in range(min(s + 1, in_limbs)):
            j = s - i
            if j >= in_limbs:
                continue
            if chunked:
                p = jax.lax.dot_general(
                    la[i], lb[j], (((2,), (1,)), ((0,), (0,))),
                    preferred_element_type=jnp.float64,
                )
                pi = jnp.sum(p.astype(U64), axis=0)
            else:
                p = jax.lax.dot_general(
                    la[i], lb[j], (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float64,
                )
                pi = p.astype(U64)
            ps = pi if ps is None else ps + pi
        diags.append(ps if ps is not None else jnp.zeros((m, n), dtype=U64))
    return diags


def _limb_matmul_pairs_f64(a, b, in_limbs: int, out_limbs: int):
    """Exact 16-bit-limb matmul over f64 dgemms (the CPU fast path: XLA
    lowers f64 dot_general to Eigen/BLAS, which its integer dots never
    get).  ``a`` (m, k) and ``b`` (k, n) are uint64."""
    return _f64_pair_diags(
        _limbs16_f64(a, in_limbs), _limbs16_f64(b, in_limbs),
        out_limbs, a.shape[-1], a.shape[0], b.shape[-1],
    )


def _f64_worth_it(a, b) -> bool:
    work = a.shape[0] * a.shape[-1] * b.shape[-1]
    return work >= _F64_MIN_WORK and a.shape[-1] <= _F64_MAX_K


def _matmul_u64_limb_f64(a, b):
    """Exact u64 matmul (mod 2^64) over f64 dgemms: 4 limbs, 10 dgemms."""
    diags = _limb_matmul_pairs_f64(a, b, in_limbs=4, out_limbs=4)
    acc = jnp.zeros(a.shape[:-1] + b.shape[1:], dtype=U64)
    for s, d in enumerate(diags):
        acc = acc + (d << np.uint64(16 * s))
    return acc


def _matmul_u128_f64(lo1, hi1, lo2, hi2):
    """Exact u128 matmul over f64 dgemms: 8 limbs of 16 bits, 36 dgemms,
    one shifted two-limb recombination."""
    la = _limbs16_f64(lo1, 4) + _limbs16_f64(hi1, 4)
    lb = _limbs16_f64(lo2, 4) + _limbs16_f64(hi2, 4)
    k = lo1.shape[-1]
    m, n = lo1.shape[0], lo2.shape[-1]
    diags = _f64_pair_diags(la, lb, 8, k, m, n)
    rlo = jnp.zeros((m, n), dtype=U64)
    rhi = jnp.zeros((m, n), dtype=U64)
    for s, ps in enumerate(diags):
        add_lo, add_hi = shl(ps, jnp.zeros_like(ps), 16 * s)
        rlo, rhi = add(rlo, rhi, add_lo, add_hi)
    return rlo, rhi


def _matmul_u64_limb_f32(a, b):
    """Exact u64 matmul (mod 2^64) on the MXU: 8 limbs, 36 MXU matmuls
    (bf16/f32 chunked, or native int8 under the limb_int8 strategy)."""
    diags = _limb_pairs(a, b, in_limbs=8, out_limbs=8)
    with jax.named_scope("moose/limb_recombine"):
        acc = jnp.zeros(a.shape[:-1] + b.shape[1:], dtype=U64)
        for s, d in enumerate(diags):
            acc = acc + (d << np.uint64(8 * s))
        return acc


def matmul(lo1, hi1, lo2, hi2):
    """Ring matmul (Dot).  For u64 the wrapping u64 dot is exact ring math.
    For u128 we decompose to 16-bit limbs, take exact u64 partial matmuls,
    and recombine with 128-bit shifted adds.

    Vector operands are promoted to matrices for the limb path (which needs
    (m, k) @ (k, n)) and the unit axes squeezed from the result.
    """
    a_vec = lo1.ndim == 1
    b_vec = lo2.ndim == 1
    if a_vec:
        lo1 = lo1[None, :]
        hi1 = hi1[None, :] if hi1 is not None else None
    if b_vec:
        lo2 = lo2[:, None]
        hi2 = hi2[:, None] if hi2 is not None else None

    if hi1 is None:
        strat = get_matmul_strategy()
        if strat in ("limb_f32", "limb_int8"):
            lo, hi = _matmul_u64_limb_f32(lo1, lo2), None
        elif strat == "limb_f64" and _f64_worth_it(lo1, lo2):
            lo, hi = _matmul_u64_limb_f64(lo1, lo2), None
        else:
            lo, hi = _matmul_u64_native(lo1, lo2), None
    else:
        lo, hi = _matmul_u128(lo1, hi1, lo2, hi2)

    if a_vec and b_vec:
        lo = lo[0, 0]
        hi = hi[0, 0] if hi is not None else None
    elif a_vec:
        lo = lo[0]
        hi = hi[0] if hi is not None else None
    elif b_vec:
        lo = lo[..., 0]
        hi = hi[..., 0] if hi is not None else None
    return lo, hi


def _limbs16_128(lo, hi):
    """Split a (hi, lo) u128 array into 8 limbs of 16 bits (u64 dtype)."""
    limbs = []
    for i in range(4):
        limbs.append((lo >> np.uint64(16 * i)) & np.uint64(0xFFFF))
    for i in range(4):
        limbs.append((hi >> np.uint64(16 * i)) & np.uint64(0xFFFF))
    return limbs


def _matmul_u64_exact_small(a, b):
    """Exact (non-wrapping) u64 matmul where inputs are < 2^16, so the full
    result fits u64 for contraction dims < 2^31."""
    if get_matmul_strategy() in ("limb_f32", "limb_int8"):
        diags = _limb_pairs(a, b, in_limbs=2, out_limbs=3)
        acc = jnp.zeros(a.shape[:-1] + b.shape[1:], dtype=U64)
        for s, d in enumerate(diags):
            acc = acc + (d << np.uint64(8 * s))
        return acc
    return _matmul_u64_native(a, b)


def _matmul_u128(lo1, hi1, lo2, hi2):
    if (
        get_matmul_strategy() == "limb_int8"
        and lo1.shape[-1] <= _INT8_MAX_K
    ):
        return _matmul_u128_int8(lo1, hi1, lo2, hi2)
    if get_matmul_strategy() == "limb_f64" and _f64_worth_it(lo1, lo2):
        return _matmul_u128_f64(lo1, hi1, lo2, hi2)
    la = _limbs16_128(lo1, hi1)
    lb = _limbs16_128(lo2, hi2)
    out_shape = lo1.shape[:-1] + lo2.shape[1:]
    rlo = jnp.zeros(out_shape, dtype=U64)
    rhi = jnp.zeros(out_shape, dtype=U64)
    for s in range(8):
        ps = None
        for i in range(s + 1):
            j = s - i
            p = _matmul_u64_exact_small(la[i], lb[j])
            ps = p if ps is None else ps + p
        add_lo, add_hi = shl(ps, jnp.zeros_like(ps), 16 * s)
        rlo, rhi = add(rlo, rhi, add_lo, add_hi)
    return rlo, rhi


def _matmul_u128_int8(lo1, hi1, lo2, hi2):
    """Direct u128 matmul on the int8 MXU: 16 centered 8-bit limbs per
    operand, 136 s8*s8->s32 matmuls (pairs with i+j < 16) a piece of the
    contraction (one piece for k <= 2064), 16 widenings a piece, one
    shifted recombination — no nested 16-bit detour."""
    k = lo1.shape[-1]
    la = _limbs8_s8_centered(lo1, 8) + _limbs8_s8_centered(hi1, 8)
    lb = _limbs8_s8_centered(lo2, 8) + _limbs8_s8_centered(hi2, 8)
    diags = _int8_pair_diags(la, lb, 16, k)
    out_shape = lo1.shape[:-1] + lo2.shape[1:]
    with jax.named_scope("moose/limb_recombine"):
        rlo = jnp.zeros(out_shape, dtype=U64)
        rhi = jnp.zeros(out_shape, dtype=U64)
        for s, ps in enumerate(diags):
            add_lo, add_hi = shl(ps, jnp.zeros_like(ps), 8 * s)
            rlo, rhi = add(rlo, rhi, add_lo, add_hi)
        return rlo, rhi


# ---------------------------------------------------------------------------
# Convolution (north-star extension, BASELINE.json configs: encrypted
# ResNet-style inference; no reference counterpart — the reference's model
# zoo is Gemm-only).  Conv over the ring = dtype-agnostic im2col (pure
# data movement, exact for any dtype incl. u64 limbs) + the exact limb
# matmul above, so every matmul strategy applies unchanged.
# ---------------------------------------------------------------------------


def conv_out_size(size: int, k: int, stride: int, pad0: int, pad1: int) -> int:
    return (size + pad0 + pad1 - k) // stride + 1


def resolve_padding(padding, h, w, kh, kw, sh, sw):
    """Normalize padding to ((ph0, ph1), (pw0, pw1)).

    Accepts "VALID", "SAME" (TF convention: output = ceil(in/stride)),
    or explicit ((ph0, ph1), (pw0, pw1))."""
    if padding == "VALID":
        return (0, 0), (0, 0)
    if padding == "SAME":
        def same(size, k, s):
            out = -(-size // s)
            total = max(0, (out - 1) * s + k - size)
            return total // 2, total - total // 2

        return same(h, kh, sh), same(w, kw, sw)
    (p0, p1), (q0, q1) = padding
    return (int(p0), int(p1)), (int(q0), int(q1))


def check_maxpool_padding(padding, h, w, kh, kw, sh, sw):
    """Shared padding policy for secret max pooling (per-host replicated
    and stacked backends): implicit padding would pad with the ring
    encoding of 0, while the host kernel pads with -inf — negative
    inputs would silently produce different results per placement.
    Rejected unless MOOSE_TPU_MAXPOOL_ZERO_PAD=1 explicitly accepts
    zero-padding semantics."""
    (p0, p1), (q0, q1) = resolve_padding(padding, h, w, kh, kw, sh, sw)
    if (p0, p1, q0, q1) == (0, 0, 0, 0):
        return
    import os

    if os.environ.get("MOOSE_TPU_MAXPOOL_ZERO_PAD") == "1":
        return
    from ..errors import KernelError

    raise KernelError(
        "padded max_pool2d on a secret-shared placement pads with the "
        "ring encoding of 0, while the host kernel pads with -inf — "
        "negative inputs would silently produce different results per "
        "placement.  Use VALID padding, pad on the host side, or set "
        "MOOSE_TPU_MAXPOOL_ZERO_PAD=1 to accept zero-padding semantics."
    )


def im2col(x, kh: int, kw: int, strides, padding):
    """Extract conv patches from an NHWC array of ANY dtype.

    Returns (patches, out_h, out_w) where patches has shape
    (N, out_h, out_w, kh*kw*C): static slices only, so it works on ring
    limb arrays where XLA has no integer convolution."""
    sh, sw = strides
    n, h, w, c = x.shape
    (ph0, ph1), (pw0, pw1) = resolve_padding(padding, h, w, kh, kw, sh, sw)
    if ph0 or ph1 or pw0 or pw1:
        # zero padding is exact for secret shares too: sharing is linear,
        # so zero-padded shares reconstruct to a zero-padded secret
        x = jnp.pad(x, ((0, 0), (ph0, ph1), (pw0, pw1), (0, 0)))
    hp, wp = x.shape[1], x.shape[2]
    out_h = conv_out_size(h, kh, sh, ph0, ph1)
    out_w = conv_out_size(w, kw, sw, pw0, pw1)
    cols = []
    for i in range(kh):
        for j in range(kw):
            cols.append(
                x[:, i:i + (out_h - 1) * sh + 1:sh,
                  j:j + (out_w - 1) * sw + 1:sw, :]
            )
    patches = jnp.concatenate(cols, axis=-1)
    return patches, out_h, out_w


def conv2d(x_lo, x_hi, k_lo, k_hi, strides=(1, 1), padding="VALID"):
    """Ring conv: x (N, H, W, C) * kernel (KH, KW, C, O) -> (N, OH, OW, O),
    exact mod 2^64 / 2^128 via im2col + the limb matmul."""
    kh, kw, c, o = k_lo.shape
    p_lo, out_h, out_w = im2col(x_lo, kh, kw, strides, padding)
    n = x_lo.shape[0]
    cols = p_lo.reshape(n * out_h * out_w, kh * kw * c)
    kmat_lo = k_lo.reshape(kh * kw * c, o)
    if x_hi is None:
        lo, hi = matmul(cols, None, kmat_lo, None)
    else:
        p_hi, _, _ = im2col(x_hi, kh, kw, strides, padding)
        cols_hi = p_hi.reshape(n * out_h * out_w, kh * kw * c)
        kmat_hi = k_hi.reshape(kh * kw * c, o)
        lo, hi = matmul(cols, cols_hi, kmat_lo, kmat_hi)
    lo = lo.reshape(n, out_h, out_w, o)
    hi = hi.reshape(n, out_h, out_w, o) if hi is not None else None
    return lo, hi


# ---------------------------------------------------------------------------
# Fixed-point encode/decode (reference host/fixedpoint.rs)
# ---------------------------------------------------------------------------


def fixedpoint_encode(x, frac_precision: int, width: int):
    """Encode floats into the ring: round(x * 2^f) two's complement.

    Exactness caveat shared with the reference: the scaled value must fit in
    float64's 53-bit mantissa to be exact.  Integer inputs at scale 0
    (the secret-uint64 integer dialect) skip the float detour entirely —
    full 64-bit values lift losslessly.
    """
    if frac_precision == 0 and jnp.issubdtype(
        jnp.asarray(x).dtype, jnp.integer
    ):
        lo = jnp.asarray(x).astype(U64)
        if width == 64:
            return lo, None
        # sign-extend signed inputs into the high limb
        if jnp.issubdtype(jnp.asarray(x).dtype, jnp.signedinteger):
            hi = (jnp.asarray(x).astype(jnp.int64) >> np.int64(63)).astype(U64)
        else:
            hi = jnp.zeros_like(lo)
        return lo, hi
    scaled = jnp.round(x.astype(jnp.float64) * (2.0 ** frac_precision))
    si = scaled.astype(jnp.int64)
    lo = si.astype(U64)
    if width == 64:
        return lo, None
    hi = (si >> np.int64(63)).astype(U64)  # sign extension
    return lo, hi


def fixedpoint_decode(lo, hi, frac_precision: int):
    """Decode ring values to float64, interpreting as signed two's
    complement.  Negatives are negated to magnitude *before* the float
    conversion — float64(2^64 - small) would round the low bits away."""
    if hi is None:
        signed = lo.astype(jnp.int64)
        return signed.astype(jnp.float64) / (2.0 ** frac_precision)
    negative = (hi >> np.uint64(63)) != 0
    mlo, mhi = neg(lo, hi)
    mag_lo = jnp.where(negative, mlo, lo)
    mag_hi = jnp.where(negative, mhi, hi)
    mag = mag_hi.astype(jnp.float64) * (2.0 ** 64) + mag_lo.astype(jnp.float64)
    v = jnp.where(negative, -mag, mag)
    return v / (2.0 ** frac_precision)


# ---------------------------------------------------------------------------
# numpy boundary helpers
# ---------------------------------------------------------------------------


def from_numpy_u64(arr: np.ndarray):
    return jnp.asarray(arr.astype(np.uint64)), None


def from_python_ints(arr, width: int):
    """Build (lo, hi) from an array of Python ints (possibly >= 2^64)."""
    a = np.asarray(arr, dtype=object)
    lo = np.vectorize(lambda v: int(v) & 0xFFFFFFFFFFFFFFFF, otypes=[np.uint64])(a)
    if width == 64:
        return jnp.asarray(lo), None
    hi = np.vectorize(
        lambda v: (int(v) >> 64) & 0xFFFFFFFFFFFFFFFF, otypes=[np.uint64]
    )(a)
    return jnp.asarray(lo), jnp.asarray(hi)
