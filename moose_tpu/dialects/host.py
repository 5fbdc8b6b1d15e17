"""Host dialect: plaintext kernels owned by a single host placement.

TPU-native re-design of the reference's host dialect (``moose/src/host/``):
every kernel is a pure function on JAX arrays so the whole dataflow graph can
be fused by XLA.  The reference's ndarray/OpenBLAS kernels (``host/ops.rs``)
map to jnp; ring tensors map to the limb representation in ``ring.py``;
PRF-key/seed handling maps to JAX's counter-based threefry PRF
(``host/prim.rs:113-133`` equivalents).
"""

from __future__ import annotations

import secrets
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .. import dtypes as dt
from ..values import (
    HostBitTensor,
    HostFixedTensor,
    HostPrfKey,
    HostRingTensor,
    HostSeed,
    HostShape,
    HostString,
    HostTensor,
    HostUnit,
)
from . import ring

# ---------------------------------------------------------------------------
# Shapes, constants, identities
# ---------------------------------------------------------------------------


def shape(x, plc: str) -> HostShape:
    if isinstance(x, HostRingTensor):
        return HostShape(tuple(x.lo.shape), plc)
    return HostShape(tuple(x.value.shape), plc)


def constant(value, plc: str, dtype: Optional[dt.DType] = None):
    """Materialize a constant. ``value`` may be a numpy array, scalar,
    tuple (shape), or string."""
    if isinstance(value, (HostTensor, HostRingTensor, HostBitTensor,
                          HostShape, HostString)):
        return place(value, plc)
    if isinstance(value, str):
        return HostString(value, plc)
    if isinstance(value, (tuple, list)) and all(
        isinstance(v, (int, np.integer)) for v in value
    ):
        if dtype is None:
            return HostShape(tuple(int(v) for v in value), plc)
    arr = np.asarray(value)
    if dtype is not None and not dtype.is_fixedpoint:
        arr = arr.astype(np.dtype(dtype.numpy_name))
    if arr.dtype == np.bool_:
        return HostBitTensor(jnp.asarray(arr.astype(np.uint8)), plc)
    return HostTensor(jnp.asarray(arr), plc, dt.from_numpy(arr.dtype))


def place(x, plc: str):
    """Move/claim a value onto a host placement (Identity / Send+Receive
    collapse to a placement relabel in single-program execution)."""
    import dataclasses as _dc

    return _dc.replace(x, plc=plc) if hasattr(x, "plc") else x


def fill(shp: HostShape, value, plc: str, ty_name: str):
    if ty_name.startswith("HostRing"):
        width = 128 if "128" in ty_name else 64
        lo, hi = ring.fill_like_shape(shp.value, width, int(value))
        return HostRingTensor(lo, hi, width, plc)
    if ty_name == "HostBitTensor":
        return HostBitTensor(
            jnp.full(shp.value, np.uint8(int(value) & 1), dtype=jnp.uint8), plc
        )
    raise NotImplementedError(f"fill for {ty_name}")


def ones(shp: HostShape, dtype: dt.DType, plc: str) -> HostTensor:
    return HostTensor(
        jnp.ones(shp.value, dtype=np.dtype(dtype.numpy_name)), plc, dtype
    )


def zeros(shp: HostShape, dtype: dt.DType, plc: str) -> HostTensor:
    return HostTensor(
        jnp.zeros(shp.value, dtype=np.dtype(dtype.numpy_name)), plc, dtype
    )


def ring_zeros(shp: HostShape, width: int, plc: str) -> HostRingTensor:
    lo, hi = ring.fill_like_shape(shp.value, width, 0)
    return HostRingTensor(lo, hi, width, plc)


def ring_constant(ints, width: int, plc: str) -> HostRingTensor:
    """Public ring tensor from an array of Python ints (mod 2^width)."""
    lo, hi = ring.from_python_ints(ints, width)
    return HostRingTensor(lo, hi, width, plc)


# ---------------------------------------------------------------------------
# PRF keys & seeds (reference host/prim.rs)
# ---------------------------------------------------------------------------


# Deterministic sync-key streams: the jit self-check gate
# (execution/interpreter._SelfCheckRunner) must run the eager reference
# and the jit candidate over IDENTICAL nonce sequences so their results
# compare bit-for-bit (nonces are public; seed security rests on the
# master key, which stays fresh per evaluation).
import contextlib as _contextlib
import contextvars as _contextvars

_SYNC_KEY_STREAM: "_contextvars.ContextVar" = _contextvars.ContextVar(
    "moose_tpu_sync_key_stream", default=None
)


@_contextlib.contextmanager
def deterministic_sync_keys(seed: int):
    """Within the context, :func:`random_sync_key` draws from a Philox
    stream seeded by ``seed`` instead of OS entropy, so two executions
    of the same op walk see the same nonce sequence."""
    rng = np.random.Generator(np.random.Philox(int(seed)))
    token = _SYNC_KEY_STREAM.set(rng)
    try:
        yield
    finally:
        _SYNC_KEY_STREAM.reset(token)


def random_sync_key() -> bytes:
    """Trace-time random nonce identifying one seed derivation
    (reference SyncKey::random())."""
    stream = _SYNC_KEY_STREAM.get()
    if stream is not None:
        return stream.bytes(16)
    return secrets.token_bytes(16)


def key_gen(plc: str, key_words) -> HostPrfKey:
    """Create a PRF key from session-provided entropy words (uint32[4])."""
    return HostPrfKey(jnp.asarray(key_words, dtype=jnp.uint32), plc)


def derive_seed(key: HostPrfKey, sync_key: bytes, plc: str,
                session_id: str = "") -> HostSeed:
    """Derive a 128-bit seed from a PRF key and a static nonce.

    Default impls use one PRF draw keyed by a key/nonce mix (see
    ring.mix_seed); under ``set_prf_impl("aes-ctr")`` this is the
    reference's exact construction — blake3 derive_key("Derive Seed",
    key) then a keyed hash of session_id || sync_key
    (host/prim.rs:123-147) — so seeds match pymoose bit for bit given
    the same key, session id, and sync key."""
    if ring.get_prf_impl() == "aes-ctr":
        from ..crypto.aes_prng import derive_seed as _reference_derive

        key_bytes = ring._concrete_seed_bytes(key.value)
        seed = _reference_derive(key_bytes, session_id, sync_key)
        import jax.numpy as jnp

        return HostSeed(
            jnp.asarray(np.frombuffer(seed, dtype=np.uint32)), plc
        )
    words = np.frombuffer(sync_key[:16].ljust(16, b"\0"), dtype=np.uint32)
    return HostSeed(ring.mix_seed(key.value, words), plc)


def sample_uniform_seeded(
    shp: HostShape, seed: HostSeed, width: int, plc: str
) -> HostRingTensor:
    lo, hi = ring.sample_uniform_seeded(shp.value, seed.value, width)
    return HostRingTensor(lo, hi, width, plc)


def sample_bits_seeded(
    shp: HostShape, seed: HostSeed, width: int, plc: str
) -> HostRingTensor:
    lo, hi = ring.sample_bits_seeded(shp.value, seed.value, width)
    return HostRingTensor(lo, hi, width, plc)


def sample_bit_tensor_seeded(shp: HostShape, seed: HostSeed, plc: str) -> HostBitTensor:
    shape = tuple(shp.value)
    if ring.get_prf_impl() == "aes-ctr":
        from ..crypto.aes_prng import AesCtrRng

        rng = AesCtrRng(ring._concrete_seed_bytes(seed.value))
        n = int(np.prod(shape)) if shape else 1
        return HostBitTensor(
            jnp.asarray(rng.bits(n).reshape(shape)), plc
        )
    key = ring._key_from_seed(seed.value)
    bits = jax.random.bits(key, shape, dtype=jnp.uint8) & jnp.uint8(1)
    return HostBitTensor(bits, plc)


# ---------------------------------------------------------------------------
# Ring tensor kernels
# ---------------------------------------------------------------------------


def _ring2(op):
    def kernel(x: HostRingTensor, y: HostRingTensor, plc: str) -> HostRingTensor:
        lo, hi = op(x.lo, x.hi, y.lo, y.hi)
        return HostRingTensor(lo, hi, x.width, plc)

    return kernel


ring_add = _ring2(ring.add)
ring_sub = _ring2(ring.sub)
ring_mul = _ring2(ring.mul)


def ring_neg(x: HostRingTensor, plc: str) -> HostRingTensor:
    lo, hi = ring.neg(x.lo, x.hi)
    return HostRingTensor(lo, hi, x.width, plc)


def ring_dot(x: HostRingTensor, y: HostRingTensor, plc: str) -> HostRingTensor:
    lo, hi = ring.matmul(x.lo, x.hi, y.lo, y.hi)
    return HostRingTensor(lo, hi, x.width, plc)


def ring_sum(x: HostRingTensor, axis, plc: str) -> HostRingTensor:
    lo, hi = ring.sum_(x.lo, x.hi, axis)
    return HostRingTensor(lo, hi, x.width, plc)


def ring_conv2d(x: HostRingTensor, k: HostRingTensor, strides, padding,
                plc: str) -> HostRingTensor:
    """Exact ring convolution: NHWC input * HWIO kernel (im2col + limb
    matmul; see ring.conv2d)."""
    lo, hi = ring.conv2d(x.lo, x.hi, k.lo, k.hi, strides, padding)
    return HostRingTensor(lo, hi, x.width, plc)


def ring_im2col(x: HostRingTensor, kh: int, kw: int, strides, padding,
                plc: str) -> HostRingTensor:
    """Patch extraction on ring tensors (share-local data movement):
    (N,H,W,C) -> (N,OH,OW,KH*KW*C)."""
    lo, out_h, out_w = ring.im2col(x.lo, kh, kw, strides, padding)
    hi = None
    if x.hi is not None:
        hi, _, _ = ring.im2col(x.hi, kh, kw, strides, padding)
    return HostRingTensor(lo, hi, x.width, plc)


def ring_shl(x: HostRingTensor, amount: int, plc: str) -> HostRingTensor:
    lo, hi = ring.shl(x.lo, x.hi, amount)
    return HostRingTensor(lo, hi, x.width, plc)


def ring_shr(x: HostRingTensor, amount: int, plc: str) -> HostRingTensor:
    lo, hi = ring.shr(x.lo, x.hi, amount)
    return HostRingTensor(lo, hi, x.width, plc)


def ring_shr_arith(x: HostRingTensor, amount: int, plc: str) -> HostRingTensor:
    lo, hi = ring.shr_arith(x.lo, x.hi, amount)
    return HostRingTensor(lo, hi, x.width, plc)


def ring_bit_extract(x: HostRingTensor, bit_idx: int, plc: str) -> HostBitTensor:
    return HostBitTensor(ring.bit_extract(x.lo, x.hi, bit_idx), plc)


def ring_inject(b: HostBitTensor, bit_idx: int, width: int, plc: str) -> HostRingTensor:
    lo, hi = ring.from_bit(b.value, width)
    lo, hi = ring.shl(lo, hi, bit_idx)
    return HostRingTensor(lo, hi, width, plc)


def ring_decompose_bits(x: HostRingTensor, plc: str) -> HostBitTensor:
    """All bits of a ring tensor, stacked on a new leading axis
    (BitDecompose host kernel) — one broadcast shift per limb, not a
    per-bit Python loop."""
    shifts = jnp.arange(64, dtype=ring.U64).reshape((64,) + (1,) * x.lo.ndim)
    bits_lo = ((x.lo[None, ...] >> shifts) & jnp.uint64(1)).astype(jnp.uint8)
    if x.width == 64:
        return HostBitTensor(bits_lo, plc)
    bits_hi = ((x.hi[None, ...] >> shifts) & jnp.uint64(1)).astype(jnp.uint8)
    return HostBitTensor(
        jnp.concatenate([bits_lo, bits_hi], axis=0), plc
    )


def ring_compose_bits(b: HostBitTensor, width: int, plc: str) -> HostRingTensor:
    """Inverse of ring_decompose_bits (BitCompose host kernel): weighted sum
    with power-of-two weights, vectorized over the bit axis."""
    bits = b.value.astype(ring.U64)
    weights = (
        jnp.uint64(1) << jnp.arange(64, dtype=ring.U64)
    ).reshape((64,) + (1,) * (b.value.ndim - 1))
    lo = jnp.sum(bits[:64] * weights[: min(width, 64)], axis=0, dtype=ring.U64)
    if width == 64:
        return HostRingTensor(lo, None, width, plc)
    hi = jnp.sum(bits[64:128] * weights, axis=0, dtype=ring.U64)
    return HostRingTensor(lo, hi, width, plc)


# Structural ops shared by ring and plaintext tensors -----------------------


def _map_ring_arrays(x: HostRingTensor, fn, plc: str) -> HostRingTensor:
    lo = fn(x.lo)
    hi = fn(x.hi) if x.hi is not None else None
    return HostRingTensor(lo, hi, x.width, plc)


def _structural(fn_name):
    """Build a kernel applying a jnp structural transform to any host
    tensor kind."""

    def kernel(x, plc: str, **kwargs):
        fn = lambda a: getattr(jnp, fn_name)(a, **kwargs)
        if isinstance(x, HostRingTensor):
            return _map_ring_arrays(x, fn, plc)
        if isinstance(x, HostBitTensor):
            return HostBitTensor(fn(x.value), plc)
        return HostTensor(fn(x.value), plc, x.dtype)

    return kernel


expand_dims = _structural("expand_dims")
squeeze = _structural("squeeze")


def transpose(x, plc: str, axes=None):
    fn = lambda a: jnp.transpose(a, axes)
    if isinstance(x, HostRingTensor):
        return _map_ring_arrays(x, fn, plc)
    if isinstance(x, HostBitTensor):
        return HostBitTensor(fn(x.value), plc)
    return HostTensor(fn(x.value), plc, x.dtype)


def reshape(x, shp: HostShape, plc: str):
    fn = lambda a: jnp.reshape(a, shp.value)
    if isinstance(x, HostRingTensor):
        return _map_ring_arrays(x, fn, plc)
    if isinstance(x, HostBitTensor):
        return HostBitTensor(fn(x.value), plc)
    return HostTensor(fn(x.value), plc, x.dtype)


def index_axis(x, axis: int, index, plc: str):
    """``index`` an int (the axis goes) or a sequence of ints (a static
    gather: the axis stays, ``len(index)`` long)."""
    if not isinstance(index, (int, np.integer)):
        index = np.asarray(index, dtype=np.int32)
    fn = lambda a: jnp.take(a, index, axis=axis)
    if isinstance(x, HostRingTensor):
        return _map_ring_arrays(x, fn, plc)
    if isinstance(x, HostBitTensor):
        return HostBitTensor(fn(x.value), plc)
    return HostTensor(fn(x.value), plc, x.dtype)


def slice_(x, begin, end, plc: str):
    fn = lambda a: a[tuple(slice(b, e) for b, e in zip(begin, end))]
    if isinstance(x, HostShape):
        return HostShape(x.value[begin[0]:end[0]], plc)
    if isinstance(x, HostRingTensor):
        return _map_ring_arrays(x, fn, plc)
    if isinstance(x, HostBitTensor):
        return HostBitTensor(fn(x.value), plc)
    return HostTensor(fn(x.value), plc, x.dtype)


def strided_slice(x, slices, plc: str):
    fn = lambda a: a[tuple(slices)]
    if isinstance(x, HostRingTensor):
        return _map_ring_arrays(x, fn, plc)
    if isinstance(x, HostBitTensor):
        return HostBitTensor(fn(x.value), plc)
    return HostTensor(fn(x.value), plc, x.dtype)


def concat(xs: Sequence, axis: int, plc: str):
    x0 = xs[0]
    if isinstance(x0, HostRingTensor):
        lo = jnp.concatenate([x.lo for x in xs], axis=axis)
        hi = (
            jnp.concatenate([x.hi for x in xs], axis=axis)
            if x0.hi is not None
            else None
        )
        return HostRingTensor(lo, hi, x0.width, plc)
    if isinstance(x0, HostBitTensor):
        return HostBitTensor(
            jnp.concatenate([x.value for x in xs], axis=axis), plc
        )
    return HostTensor(
        jnp.concatenate([x.value for x in xs], axis=axis), plc, x0.dtype
    )


def broadcast(x, shp: HostShape, plc: str):
    fn = lambda a: jnp.broadcast_to(a, shp.value)
    if isinstance(x, HostRingTensor):
        return _map_ring_arrays(x, fn, plc)
    if isinstance(x, HostBitTensor):
        return HostBitTensor(fn(x.value), plc)
    return HostTensor(fn(x.value), plc, x.dtype)


def diag(x, plc: str):
    fn = jnp.diag
    if isinstance(x, HostRingTensor):
        return _map_ring_arrays(x, fn, plc)
    return HostTensor(fn(x.value), plc, x.dtype)


def shl_dim(x: HostRingTensor, amount: int, bit_length: int, plc: str):
    """Rotate the leading (bit) axis by ``amount`` positions, filling with
    zeros (used by bit-compose paths; reference ShlDim)."""
    fn = lambda a: jnp.concatenate(
        [jnp.zeros_like(a[:amount]), a[: bit_length - amount]], axis=0
    )
    if isinstance(x, HostBitTensor):
        return HostBitTensor(fn(x.value), plc)
    return _map_ring_arrays(x, fn, plc)


# ---------------------------------------------------------------------------
# Bit tensor kernels
# ---------------------------------------------------------------------------


def bit_xor(x: HostBitTensor, y: HostBitTensor, plc: str) -> HostBitTensor:
    return HostBitTensor(x.value ^ y.value, plc)


def bit_and(x: HostBitTensor, y: HostBitTensor, plc: str) -> HostBitTensor:
    return HostBitTensor(x.value & y.value, plc)


def bit_or(x: HostBitTensor, y: HostBitTensor, plc: str) -> HostBitTensor:
    return HostBitTensor(x.value | y.value, plc)


def bit_neg(x: HostBitTensor, plc: str) -> HostBitTensor:
    return HostBitTensor(x.value ^ jnp.uint8(1), plc)


# ---------------------------------------------------------------------------
# Plaintext float/int kernels
# ---------------------------------------------------------------------------


def _f2(fn):
    def kernel(x: HostTensor, y: HostTensor, plc: str) -> HostTensor:
        return HostTensor(fn(x.value, y.value), plc, x.dtype)

    return kernel


add = _f2(jnp.add)
sub = _f2(jnp.subtract)
mul = _f2(jnp.multiply)
div = _f2(jnp.divide)


def dot(x: HostTensor, y: HostTensor, plc: str) -> HostTensor:
    return HostTensor(jnp.matmul(x.value, y.value), plc, x.dtype)


def conv2d(x: HostTensor, k: HostTensor, strides, padding,
           plc: str) -> HostTensor:
    """Plaintext conv: NHWC input * HWIO kernel (XLA native conv)."""
    pad = padding
    if not isinstance(pad, str):
        pad = [tuple(p) for p in pad]
    out = jax.lax.conv_general_dilated(
        x.value, k.value, window_strides=tuple(strides), padding=pad,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )
    return HostTensor(out, plc, x.dtype)


def _pool2d(x: HostTensor, pool, strides, padding, plc: str,
            init, reduce_fn, finish):
    ph, pw = pool
    sh, sw = strides
    n, h, w, c = x.value.shape
    (p0, p1), (q0, q1) = ring.resolve_padding(padding, h, w, ph, pw, sh, sw)
    out = jax.lax.reduce_window(
        x.value, init, reduce_fn,
        window_dimensions=(1, ph, pw, 1),
        window_strides=(1, sh, sw, 1),
        padding=((0, 0), (p0, p1), (q0, q1), (0, 0)),
    )
    return HostTensor(finish(out), plc, x.dtype)


def avg_pool2d(x: HostTensor, pool, strides, padding,
               plc: str) -> HostTensor:
    strides = tuple(strides) if strides is not None else tuple(pool)
    taps = pool[0] * pool[1]
    return _pool2d(
        x, pool, strides, padding, plc, 0.0, jax.lax.add,
        lambda v: v / taps,
    )


def max_pool2d(x: HostTensor, pool, strides, padding,
               plc: str) -> HostTensor:
    strides = tuple(strides) if strides is not None else tuple(pool)
    return _pool2d(
        x, pool, strides, padding, plc, -jnp.inf, jax.lax.max,
        lambda v: v,
    )


def neg_(x: HostTensor, plc: str) -> HostTensor:
    return HostTensor(-x.value, plc, x.dtype)


def sum_(x: HostTensor, axis, plc: str) -> HostTensor:
    return HostTensor(jnp.sum(x.value, axis=axis), plc, x.dtype)


def mean(x: HostTensor, axis, plc: str) -> HostTensor:
    return HostTensor(jnp.mean(x.value, axis=axis), plc, x.dtype)


def exp(x: HostTensor, plc: str) -> HostTensor:
    return HostTensor(jnp.exp(x.value), plc, x.dtype)


def log(x: HostTensor, plc: str) -> HostTensor:
    return HostTensor(jnp.log(x.value), plc, x.dtype)


def log2(x: HostTensor, plc: str) -> HostTensor:
    return HostTensor(jnp.log2(x.value), plc, x.dtype)


def sqrt(x: HostTensor, plc: str) -> HostTensor:
    return HostTensor(jnp.sqrt(x.value), plc, x.dtype)


def sigmoid(x: HostTensor, plc: str) -> HostTensor:
    return HostTensor(jax.nn.sigmoid(x.value), plc, x.dtype)


def relu(x: HostTensor, plc: str) -> HostTensor:
    return HostTensor(jnp.maximum(x.value, 0), plc, x.dtype)


def abs_(x: HostTensor, plc: str) -> HostTensor:
    return HostTensor(jnp.abs(x.value), plc, x.dtype)


def sign(x: HostTensor, plc: str) -> HostTensor:
    return HostTensor(jnp.sign(x.value), plc, x.dtype)


def pow2(x: HostTensor, plc: str) -> HostTensor:
    return HostTensor(jnp.exp2(x.value), plc, x.dtype)


def softmax(x: HostTensor, axis: int, plc: str) -> HostTensor:
    return HostTensor(jax.nn.softmax(x.value, axis=axis), plc, x.dtype)


def argmax(x: HostTensor, axis: int, plc: str) -> HostTensor:
    return HostTensor(
        jnp.argmax(x.value, axis=axis).astype(jnp.uint64), plc, dt.uint64
    )


def maximum(xs: Sequence[HostTensor], plc: str) -> HostTensor:
    out = xs[0].value
    for x in xs[1:]:
        out = jnp.maximum(out, x.value)
    return HostTensor(out, plc, xs[0].dtype)


def inverse(x: HostTensor, plc: str) -> HostTensor:
    return HostTensor(jnp.linalg.inv(x.value), plc, x.dtype)


def at_least_2d(x: HostTensor, to_column_vector: bool, plc: str) -> HostTensor:
    v = x.value
    if v.ndim == 0:
        v = v.reshape(1, 1)
    elif v.ndim == 1:
        v = v.reshape(1, -1)
        if to_column_vector:
            v = v.T
    return HostTensor(v, plc, x.dtype)


def less(x: HostTensor, y: HostTensor, plc: str) -> HostBitTensor:
    return HostBitTensor((x.value < y.value).astype(jnp.uint8), plc)


def greater(x: HostTensor, y: HostTensor, plc: str) -> HostBitTensor:
    return HostBitTensor((x.value > y.value).astype(jnp.uint8), plc)


def equal(x, y, plc: str) -> HostBitTensor:
    if isinstance(x, HostRingTensor):
        return HostBitTensor(
            ring.equal_bits(x.lo, x.hi, y.lo, y.hi), plc
        )
    return HostBitTensor((x.value == y.value).astype(jnp.uint8), plc)


def mux(s: HostBitTensor, x: HostTensor, y: HostTensor, plc: str) -> HostTensor:
    return HostTensor(
        jnp.where(s.value.astype(bool), x.value, y.value), plc, x.dtype
    )


def select(x, axis: int, index: HostBitTensor, plc: str):
    """Filter entries along ``axis`` by a boolean mask (reference SelectOp,
    host/ops.rs:605).  Output shape is data-dependent, so computations using
    Select are executed eagerly (outside jit) by the interpreter."""
    mask = np.asarray(index.value).astype(bool)
    if isinstance(x, HostRingTensor):
        lo = np.compress(mask, np.asarray(x.lo), axis=axis)
        hi = (
            np.compress(mask, np.asarray(x.hi), axis=axis)
            if x.hi is not None
            else None
        )
        return HostRingTensor(jnp.asarray(lo), None if hi is None else jnp.asarray(hi), x.width, plc)
    if isinstance(x, HostFixedTensor):
        return HostFixedTensor(
            select(x.tensor, axis, index, plc),
            x.integral_precision,
            x.fractional_precision,
        )
    if isinstance(x, HostBitTensor):
        return HostBitTensor(
            jnp.asarray(np.compress(mask, np.asarray(x.value), axis=axis)), plc
        )
    return HostTensor(
        jnp.asarray(np.compress(mask, np.asarray(x.value), axis=axis)),
        plc,
        x.dtype,
    )


def cast(x, target: dt.DType, plc: str):
    if isinstance(x, HostBitTensor):
        if target.is_boolean:
            return x
        return HostTensor(
            x.value.astype(np.dtype(target.numpy_name)), plc, target
        )
    if target.is_boolean:
        return HostBitTensor((x.value != 0).astype(jnp.uint8), plc)
    return HostTensor(x.value.astype(np.dtype(target.numpy_name)), plc, target)


# ---------------------------------------------------------------------------
# Fixed-point encode/decode on host (reference host/fixedpoint.rs)
# ---------------------------------------------------------------------------


@jax.named_scope("moose/encode")
def ring_fixedpoint_encode(
    x: HostTensor, frac_precision: int, width: int, plc: str
) -> HostRingTensor:
    lo, hi = ring.fixedpoint_encode(x.value, frac_precision, width)
    return HostRingTensor(lo, hi, width, plc)


@jax.named_scope("moose/decode")
def ring_fixedpoint_decode(
    x: HostRingTensor, frac_precision: int, plc: str, dtype: dt.DType = dt.float64
) -> HostTensor:
    v = ring.fixedpoint_decode(x.lo, x.hi, frac_precision)
    return HostTensor(v.astype(np.dtype(dtype.numpy_name)), plc, dtype)


def fixedpoint_encode(
    x: HostTensor, integ: int, frac: int, width: int, plc: str
) -> HostFixedTensor:
    return HostFixedTensor(
        ring_fixedpoint_encode(x, frac, width, plc), integ, frac
    )


def fixedpoint_decode(
    x: HostFixedTensor, plc: str, dtype: dt.DType = dt.float64
) -> HostTensor:
    return ring_fixedpoint_decode(
        x.tensor, x.fractional_precision, plc, dtype
    )


def ring_fixedpoint_mean(
    x: HostRingTensor, axis, frac_precision: int, plc: str
) -> HostRingTensor:
    """Fixed-point mean (reference RingFixedpointMean, host/ops.rs).

    Sums over ``axis`` then multiplies by ``round(2^frac / n)``, folding the
    division by n into one ring multiply.  CONTRACT: the result is scaled by
    2^(2*frac) — i.e. one fixed-point scale too high — and every caller MUST
    follow with a truncation by ``frac_precision`` (host shift-based trunc
    on plaintext, TruncPr on shares) to restore the 2^frac scale.  This
    matches the reference, whose RingFixedpointMean is likewise always
    paired with a trunc in the fixedpoint dialect (fixedpoint/ops.rs)."""
    s = ring_sum(x, axis, plc)
    n = x.lo.shape[axis] if axis is not None else int(np.prod(x.lo.shape))
    factor = int(round((2.0 ** frac_precision) / n))
    flo, fhi = ring.fill_like_shape((), x.width, factor)
    lo, hi = ring.mul(s.lo, s.hi, flo, fhi)
    return HostRingTensor(lo, hi, x.width, plc)
