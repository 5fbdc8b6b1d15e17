"""Party-stacked SPMD execution of the 3-party replicated protocol.

This is the TPU-native execution layout for single-controller deployments
(one XLA program spanning the pod): instead of six separately-labelled
per-party arrays (the lowering-friendly layout in ``dialects/replicated.py``),
a replicated sharing is ONE array with leading axes ``(party=3, slot=2)``.

- Share-local kernels are single vectorized ops over the party axis.
- Cross-party share movement (resharing after multiplication) is
  ``jnp.roll`` over the party axis — XLA lowers it to ``collective-permute``
  over ICI when the axis is sharded on a device mesh.
- The party axis rides a named mesh axis (``parties``), with additional
  mesh axes sharding the data dimensions (batch) — the analogue of the
  reference's 3 workers exchanging shares over gRPC
  (``replicated/arith.rs:317-367``; networking backends, SURVEY §5), with
  ICI collectives instead of the network.

Sharing convention matches ``dialects/replicated.py``: x = x0+x1+x2, party i
holds the pair (x_i, x_{i+1}); ``lo[i, 0]`` is x_i, ``lo[i, 1]`` is
x_{i+1}.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..dialects import ring
from ..execution import drawledger as _ledger
from ..native import ring128_kernels as _rk

U64 = jnp.uint64


@dataclasses.dataclass
class SpmdRep:
    """Party-stacked replicated ring tensor: arrays (3, 2, *shape)."""

    lo: jax.Array
    hi: Optional[jax.Array]
    width: int

    @property
    def shape(self):
        return self.lo.shape[2:]


jax.tree_util.register_pytree_node(
    SpmdRep,
    lambda v: ((v.lo, v.hi), (v.width,)),
    lambda aux, ch: SpmdRep(ch[0], ch[1], aux[0]),
)


@dataclasses.dataclass
class SpmdFixed:
    tensor: SpmdRep
    integral_precision: int
    fractional_precision: int


jax.tree_util.register_pytree_node(
    SpmdFixed,
    lambda v: ((v.tensor,), (v.integral_precision, v.fractional_precision)),
    lambda aux, ch: SpmdFixed(ch[0], aux[0], aux[1]),
)


# ---------------------------------------------------------------------------
# Session: seed bank + counter for on-device PRF draws
# ---------------------------------------------------------------------------


def derive_step_keys(master_key, n: int, salt: int = 0x9E3779B9):
    """Per-iteration session keys for protocol steps under ``lax.scan``:
    mask freshness per step is a protocol concern, so the derivation lives
    here rather than in each caller.  Returns uint32[n, 4]."""
    steps = jnp.arange(n, dtype=jnp.uint32)
    mk = jnp.asarray(master_key, dtype=jnp.uint32)
    return mk[None, :] ^ jnp.stack(
        [
            steps,
            steps * jnp.uint32(salt),
            steps ^ jnp.uint32(0xC2B2AE35),
            steps | jnp.uint32(1),
        ],
        axis=1,
    )


def _ambient_mesh():
    """The mesh installed by ``with mesh:`` at trace time, or None."""
    from jax.interpreters import pxla

    mesh = pxla.thread_resources.env.physical_mesh
    if mesh is None or mesh.empty:
        return None
    return mesh


def _pin_contract_rhs() -> bool:
    """Whether to pin the second operand of a secure dot/conv replicated.

    XLA's CPU SPMD partitioner miscompiles programs that feed one
    partially-sharded and one unconstrained u64 operand into a batched
    ``dot_general`` and also combine the unconstrained operand elsewhere
    (the pair-sum): the contraction reads corrupted values.  Repro in
    ``tests/test_spmd.py::test_sharded_dot_mixed_consumer_repro`` (jax
    0.4.37, 12 virtual CPU devices) — the PRF-drawn share banks are part
    of the trigger; a constants-only reduction compiles correctly, so
    the repro drives the real fx_dot path.  Pinning the rhs share slices to the
    replicated sharding gives the partitioner one explicit layout and
    restores exactness, while the lhs keeps its batch sharding so the
    contraction still partitions over the data axis.  Applied on the CPU
    backend (where the miscompile reproduces); MOOSE_TPU_SPMD_PIN=
    always|never overrides for A/B on other backends."""
    import os as _os_

    knob = _os_.environ.get("MOOSE_TPU_SPMD_PIN", "auto")
    if knob == "always":
        return True
    if knob == "never":
        return False
    return jax.default_backend() == "cpu"


def _pin_replicated(*arrays):
    """Pin PRF outputs to a fully-replicated sharding under an ambient mesh.

    Inside a jitted program whose values carry sharding constraints, GSPMD
    is free to materialize a cheap producer once per consumer sharding
    instead of resharding one copy.  For ordinary pure ops that is sound,
    but the PRF expansion ops (``RngBitGenerator``, and the threefry
    custom-call on CPU) are only deterministic per materialization — two
    differently-partitioned copies of the same logical draw yield
    DIFFERENT bits, so a mask drawn once and consumed twice (every secret
    share: x2 = x - x0 - x1 with x0/x1 re-emitted as share slices) silently
    stops cancelling and reconstruction returns uniform garbage.  Observed
    on (parties, data) meshes with data > 1 (tests/test_spmd.py mesh
    sweep).  Pinning the draw itself to the replicated sharding gives the
    partitioner exactly one layout for every copy, which restores
    bit-identical masks on every consumer path; downstream resharding is
    then plain data movement, which GSPMD handles soundly."""
    from jax.interpreters import pxla

    mesh = pxla.thread_resources.env.physical_mesh
    if mesh is None or mesh.empty:
        return arrays if len(arrays) > 1 else arrays[0]
    sharding = jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec()
    )
    pinned = tuple(
        None if a is None else jax.lax.with_sharding_constraint(a, sharding)
        for a in arrays
    )
    return pinned if len(pinned) > 1 else pinned[0]


def _count_bit_bank_draw(form: str, nbytes: int) -> None:
    """Bytes of PRF output a traced bit-mask draw asks for, by the form
    the masks leave the PRF in, on the counter and on the span open
    while the program is traced (``bank_draw_mb``)."""
    from .. import metrics, telemetry

    metrics.counter(
        "moose_tpu_bit_bank_draw_bytes_total",
        "PRF output asked for by traced bit-mask draws: words (32 mask "
        "bits to a uint32, the fused adder's banks) or bytes (a mask "
        "bit to a uint8)",
        labels=("form",),
    ).inc(nbytes, form=form)
    telemetry.accumulate(bank_draw_mb=nbytes / 1e6)


class SpmdSession:
    """Derives all per-invocation randomness from one master key.

    In stacked mode each ``sample`` produces the whole (3, ...) party bank
    in one RngBitGenerator call.  Party i's slice is exactly the stream it
    would derive from pairwise PRF keys in the per-host layout; sharding the
    leading axis over the party mesh axis keeps each slice resident on its
    party's devices.  Under an ambient device mesh every draw is pinned
    replicated (:func:`_pin_replicated`) so the partitioner can never
    duplicate a PRF op into inconsistent per-sharding copies.
    """

    def __init__(self, master_key, domain: int = 0):
        self._master = jnp.asarray(master_key, dtype=jnp.uint32)
        self._counter = 0
        # distinct domains partition the nonce space so several sessions
        # sharing one master key (the segmented executor runs one per
        # graph segment) never reuse a mask; domain 0 reproduces the
        # historical stream exactly
        self._domain = int(domain)

    def _next_seed(self) -> jax.Array:
        idx = self._counter
        self._counter += 1
        nonce = np.array(
            [
                idx & 0xFFFFFFFF,
                0x5B3D9E21 ^ ((self._domain * 0x85EBCA6B) & 0xFFFFFFFF),
                idx ^ 0xA5A5A5A5,
                7,
            ],
            np.uint32,
        )
        return ring.mix_seed(self._master, nonce)

    @jax.named_scope("moose/prf_draw")
    def sample_bank(self, shape, width: int):
        """(3, *shape) uniform ring elements, one per party."""
        _ledger.record_stacked_draw("bank", shape, width)
        seed = self._next_seed()
        lo, hi = ring.sample_uniform_seeded((3,) + tuple(shape), seed, width)
        return _pin_replicated(lo, hi)

    @jax.named_scope("moose/prf_draw")
    def sample(self, shape, width: int):
        _ledger.record_stacked_draw("sample", shape, width)
        seed = self._next_seed()
        lo, hi = ring.sample_uniform_seeded(tuple(shape), seed, width)
        return _pin_replicated(lo, hi)

    @jax.named_scope("moose/prf_draw")
    def sample_bit_bank(self, shape):
        """(3, *shape) uniform bits as uint8 0/1, one slice per party."""
        _ledger.record_stacked_draw("bit_bank", shape, None)
        _count_bit_bank_draw("bytes", 3 * int(np.prod(shape, dtype=np.int64)))
        seed = self._next_seed()
        lo, _ = ring.sample_bits_seeded((3,) + tuple(shape), seed, 64)
        return _pin_replicated(lo.astype(jnp.uint8))

    @jax.named_scope("moose/prf_draw")
    def sample_bit_words(self, count: int, shape):
        """(count, 3, *shape) uint32 words of mask bits, 32 to a word:
        ``count`` banks, a seed each in session order, one slice per
        party.  The form the fused adder reads its AND banks in
        (``spmd_math._draw_adder_banks``): every bit of every word is
        one PRF output bit, where :meth:`sample_bit_bank` spends a byte
        of PRF output on each."""
        shape = tuple(shape)
        for _ in range(count):
            _ledger.record_stacked_draw("bit_words", shape, 32)
        _count_bit_bank_draw(
            "words", 4 * count * 3 * int(np.prod(shape, dtype=np.int64))
        )
        seeds = [self._next_seed() for _ in range(count)]
        return _pin_replicated(
            ring.sample_bit_words_seeded((3,) + shape, seeds)
        )


# ---------------------------------------------------------------------------
# Core protocol
# ---------------------------------------------------------------------------


def _pairs(z_lo, z_hi, width):
    """Stack per-party values z_i into the pair layout (z_i, z_{i+1})."""
    lo = jnp.stack([z_lo, jnp.roll(z_lo, -1, axis=0)], axis=1)
    hi = (
        jnp.stack([z_hi, jnp.roll(z_hi, -1, axis=0)], axis=1)
        if z_hi is not None
        else None
    )
    return SpmdRep(lo, hi, width)


@jax.named_scope("moose/share")
def share(sess: SpmdSession, x_lo, x_hi, width: int) -> SpmdRep:
    """Share a plaintext ring tensor: x0, x1 ~ PRF, x2 = x - x0 - x1."""
    r_lo, r_hi = sess.sample_bank(x_lo.shape, width)
    # stack [x0, x1, x2] with x2 = x - x0 - x1
    s_lo, s_hi = ring.sub(x_lo, x_hi, r_lo[0], None if r_hi is None else r_hi[0])
    s_lo, s_hi = ring.sub(s_lo, s_hi, r_lo[1], None if r_hi is None else r_hi[1])
    z_lo = jnp.stack([r_lo[0], r_lo[1], s_lo], axis=0)
    z_hi = (
        jnp.stack([r_hi[0], r_hi[1], s_hi], axis=0)
        if x_hi is not None
        else None
    )
    return _pairs(z_lo, z_hi, width)


@jax.named_scope("moose/reveal")
def reveal(x: SpmdRep):
    """Reconstruct the plaintext: sum over parties of first-slot shares."""
    lo, hi = x.lo[0, 0], None if x.hi is None else x.hi[0, 0]
    for i in (1, 2):
        lo, hi = ring.add(
            lo, hi, x.lo[i, 0], None if x.hi is None else x.hi[i, 0]
        )
    return lo, hi


def add(x: SpmdRep, y: SpmdRep) -> SpmdRep:
    lo, hi = ring.add(x.lo, x.hi, y.lo, y.hi)
    return SpmdRep(lo, hi, x.width)


def sub(x: SpmdRep, y: SpmdRep) -> SpmdRep:
    lo, hi = ring.sub(x.lo, x.hi, y.lo, y.hi)
    return SpmdRep(lo, hi, x.width)


def neg(x: SpmdRep) -> SpmdRep:
    lo, hi = ring.neg(x.lo, x.hi)
    return SpmdRep(lo, hi, x.width)


def shl(x: SpmdRep, amount: int) -> SpmdRep:
    lo, hi = ring.shl(x.lo, x.hi, amount)
    return SpmdRep(lo, hi, x.width)


@jax.named_scope("moose/zero_share")
def zero_share(sess: SpmdSession, shape, width: int):
    """alpha_i = PRF_i - PRF_{i+1}; one bank draw, sums to zero."""
    s_lo, s_hi = sess.sample_bank(shape, width)
    n_lo = jnp.roll(s_lo, -1, axis=0)
    n_hi = jnp.roll(s_hi, -1, axis=0) if s_hi is not None else None
    return ring.sub(s_lo, s_hi, n_lo, n_hi)


@jax.named_scope("moose/cross_terms")
def _cross_terms(x: SpmdRep, y: SpmdRep, contract):
    """v_i = x_i·(y_i + y_{i+1}) + x_{i+1}·y_i, per party.

    Regrouped form of the standard 3-term cross product
    x_i·y_i + x_i·y_{i+1} + x_{i+1}·y_i (replicated/arith.rs:317-367):
    the contraction distributes over ring addition mod 2^w, so the
    regrouping is bit-exact while doing TWO contractions instead of
    three — a 33% cut in MXU work for the dominant phase of secure
    mul/dot (the y-pair add is a cheap elementwise ring add).

    The hot contractions route through the Pallas kernels of
    ``native/ring128_kernels.py`` when selected (MOOSE_TPU_PALLAS):
    the elementwise cross terms as ONE fused Mosaic program, the
    party-batched dot cross terms behind the opt-in dot kernel — each
    validated bit-exactly against this lax path on first use, with
    per-primitive XLA fallback."""

    def take(t, slot):
        return (
            t.lo[:, slot],
            None if t.hi is None else t.hi[:, slot],
        )

    x0, y0 = take(x, 0), take(y, 0)
    x1, y1 = take(x, 1), take(y, 1)
    if (
        contract is not ring.mul
        and _ambient_mesh() is not None
        and _pin_contract_rhs()
    ):
        # See _pin_contract_rhs: replicate the second operand's share
        # slices so the partitioner never mixes an unconstrained u64
        # operand into the batched contraction (CPU miscompile guard).
        x0 = _pin_replicated(*x0)
        x1 = _pin_replicated(*x1)
        y0 = _pin_replicated(*y0)
        y1 = _pin_replicated(*y1)
    if contract is ring.mul and _rk.dispatch("cross_terms_mul", x.width):
        try:
            return _rk.cross_terms_mul(x0, x1, y0, y1, x.width)
        except Exception as e:  # noqa: BLE001 — the kernel is an
            # optimization; any failure keeps the exact XLA path
            _rk.record_fallback("cross_terms_mul", x.width, "error", e)
    ys_pair = None
    dot_shape = (
        (x0[0].shape[1], x0[0].shape[2], y0[0].shape[2])
        if x0[0].ndim == 3 and y0[0].ndim == 3 else None
    )
    if contract is _dot_contract and _rk.dispatch(
        "dot_cross_terms", x.width, shape=dot_shape,
    ):
        ys_pair = ring.add(*y0, *y1)
        try:
            return _rk.dot_cross_terms(x0, x1, y0, ys_pair, x.width)
        except _rk.ShapeUnsupported:
            pass  # this shape only; the (kernel, width) verdict stands
        except Exception as e:  # noqa: BLE001
            _rk.record_fallback("dot_cross_terms", x.width, "error", e)
    ys_lo, ys_hi = (
        ys_pair if ys_pair is not None else ring.add(*y0, *y1)
    )
    v_lo, v_hi = contract(*x0, ys_lo, ys_hi)
    t_lo, t_hi = contract(*x1, *y0)
    return ring.add(v_lo, v_hi, t_lo, t_hi)


@jax.named_scope("moose/reshare")
def _reshare(sess, v_lo, v_hi, width):
    a_lo, a_hi = zero_share(sess, v_lo.shape[1:], width)
    z_lo, z_hi = ring.add(v_lo, v_hi, a_lo, a_hi)
    return _pairs(z_lo, z_hi, width)


def mul(sess: SpmdSession, x: SpmdRep, y: SpmdRep) -> SpmdRep:
    v_lo, v_hi = _cross_terms(x, y, ring.mul)
    return _reshare(sess, v_lo, v_hi, x.width)


def _dot_contract(a_lo, a_hi, b_lo, b_hi):
    """Party-batched ring matmul: the limb-decomposed MXU path in
    ``ring.matmul`` vmaps cleanly over the party axis, so the parties'
    local contractions run as one batched MXU program."""
    if a_hi is None:
        f = jax.vmap(lambda p, q: ring.matmul(p, None, q, None)[0])
        return f(a_lo, b_lo), None
    f = jax.vmap(lambda p, ph, q, qh: ring.matmul(p, ph, q, qh))
    return f(a_lo, a_hi, b_lo, b_hi)


def dot(sess: SpmdSession, x: SpmdRep, y: SpmdRep) -> SpmdRep:
    """Secure matmul: two regrouped party-batched contractions + reshare."""
    v_lo, v_hi = _cross_terms(x, y, _dot_contract)
    return _reshare(sess, v_lo, v_hi, x.width)


def _conv_contract(strides, padding):
    """Party-batched ring convolution (NHWC x HWIO), the conv analogue
    of :func:`_dot_contract`: ``ring.conv2d`` (im2col + limb matmul)
    vmapped over the party axis."""

    def contract(a_lo, a_hi, b_lo, b_hi):
        if a_hi is None:
            f = jax.vmap(
                lambda p, q: ring.conv2d(p, None, q, None, strides,
                                         padding)[0]
            )
            return f(a_lo, b_lo), None
        f = jax.vmap(
            lambda p, ph, q, qh: ring.conv2d(p, ph, q, qh, strides,
                                             padding)
        )
        return f(a_lo, a_hi, b_lo, b_hi)

    return contract


def conv2d(sess: SpmdSession, x: SpmdRep, k: SpmdRep,
           strides=(1, 1), padding="VALID") -> SpmdRep:
    """Secure convolution, stacked form of ``replicated.conv2d``: the
    cross-product/zero-share-reshare structure of mul/dot with a ring
    conv as the local contraction."""
    v_lo, v_hi = _cross_terms(x, k, _conv_contract(strides, padding))
    return _reshare(sess, v_lo, v_hi, x.width)


def im2col(x: SpmdRep, kh: int, kw: int, strides=(1, 1),
           padding="VALID") -> SpmdRep:
    """Patch extraction applied share-locally (pure data movement;
    sharing is linear, so patched shares reconstruct to the patched
    secret).  The (party, slot) prefix folds into the batch axis for
    ``ring.im2col`` and unfolds after."""

    def go(a):
        three, two, n, h, w, c = a.shape
        flat = a.reshape(three * two * n, h, w, c)
        patches, out_h, out_w = ring.im2col(flat, kh, kw, strides, padding)
        return patches.reshape(
            three, two, n, out_h, out_w, patches.shape[-1]
        )

    lo = go(x.lo)
    hi = None if x.hi is None else go(x.hi)
    return SpmdRep(lo, hi, x.width)


def fx_conv2d(sess, x: "SpmdFixed", k: "SpmdFixed",
              strides=(1, 1), padding="VALID") -> "SpmdFixed":
    """Fixed-point secure conv: one multiplication depth, fused with the
    single TruncPr exactly like fx_mul/fx_dot."""
    z = _mul_like_trunc(
        sess, x.tensor, k.tensor, _conv_contract(strides, padding),
        x.fractional_precision,
    )
    return SpmdFixed(
        z,
        max(x.integral_precision, k.integral_precision),
        x.fractional_precision,
    )


def mul_public(x: SpmdRep, c_lo, c_hi) -> SpmdRep:
    """x * public constant (same value on every party)."""
    if _rk.dispatch("ring_mul", x.width):
        try:
            b_lo = jnp.broadcast_to(c_lo, x.lo.shape)
            b_hi = (
                None if x.hi is None
                else jnp.broadcast_to(c_hi, x.hi.shape)
            )
            lo, hi = _rk.ring_mul(x.lo, x.hi, b_lo, b_hi, x.width)
            return SpmdRep(lo, hi, x.width)
        except Exception as e:  # noqa: BLE001 — kernel optional
            _rk.record_fallback("ring_mul", x.width, "error", e)
    lo, hi = ring.mul(x.lo, x.hi, c_lo, c_hi)
    return SpmdRep(lo, hi, x.width)


def add_public(x: SpmdRep, c_lo, c_hi) -> SpmdRep:
    """x + public c: only share x_0 (held at [0,0] and [2,1]) is adjusted."""
    lo, hi = x.lo, x.hi
    s_lo, s_hi = ring.add(lo[0, 0], None if hi is None else hi[0, 0], c_lo, c_hi)
    lo = lo.at[0, 0].set(s_lo)
    t_lo, t_hi = ring.add(
        x.lo[2, 1], None if hi is None else x.hi[2, 1], c_lo, c_hi
    )
    lo = lo.at[2, 1].set(t_lo)
    if hi is not None:
        hi = hi.at[0, 0].set(s_hi).at[2, 1].set(t_hi)
    return SpmdRep(lo, hi, x.width)


def sub_public(x: SpmdRep, c_lo, c_hi) -> SpmdRep:
    n_lo, n_hi = ring.neg(c_lo, c_hi)
    return add_public(x, n_lo, n_hi)


def public_sub(c_lo, c_hi, x: SpmdRep) -> SpmdRep:
    return add_public(neg(x), c_lo, c_hi)


def public_to_rep(lo, hi, width: int) -> SpmdRep:
    """Trivial replicated sharing of a public plaintext ring tensor:
    x_0 = v, x_1 = x_2 = 0, so only pair slots (party 0, slot 0) and
    (party 2, slot 1) hold v."""
    z_lo = jnp.zeros_like(lo)
    out_lo = jnp.stack(
        [
            jnp.stack([lo, z_lo]),
            jnp.stack([z_lo, z_lo]),
            jnp.stack([z_lo, lo]),
        ]
    )
    out_hi = None
    if hi is not None:
        z_hi = jnp.zeros_like(hi)
        out_hi = jnp.stack(
            [
                jnp.stack([hi, z_hi]),
                jnp.stack([z_hi, z_hi]),
                jnp.stack([z_hi, hi]),
            ]
        )
    return SpmdRep(out_lo, out_hi, width)


def fill_public(shape, width: int, raw: int) -> SpmdRep:
    """Trivial replicated sharing of a public ring constant."""
    return public_to_rep(*ring.fill_like_shape(shape, width, raw), width)


# Structural ops: pure share-local data movement on the logical axes
# (sharing is linear, so restructured shares reconstruct to the
# restructured secret).  Logical axis a lives at array axis a + 2.


def _laxis(arr, axis: int, extra: int = 0) -> int:
    """Logical axis -> array axis.  Negative axes count from the end of
    the LOGICAL shape (a bare +2 would land them on the party/slot
    axes); ``extra`` admits one-past-the-end for expand_dims/stack."""
    nd = arr.ndim - 2 + extra
    if axis < 0:
        axis += nd
    if not 0 <= axis < nd:
        raise ValueError(f"axis {axis} out of range for {nd} logical dims")
    return axis + 2


def _structural(fn):
    def kernel(x, *args, **kwargs):
        arr = getattr(x, "arr", None)
        if arr is not None:
            # SpmdBits (one XOR-shared uint8 array, same (3, 2, *shape)
            # layout): sharing is linear over Z_2 too, so restructured
            # bit shares reconstruct to the restructured secret —
            # exercised by tree-ensemble predictors slicing/indexing
            # comparison results
            return type(x)(fn(arr, *args, **kwargs))
        lo = fn(x.lo, *args, **kwargs)
        hi = None if x.hi is None else fn(x.hi, *args, **kwargs)
        return SpmdRep(lo, hi, x.width)

    return kernel


def _index_axis_arr(a, axis, idx):
    if isinstance(idx, (int, np.integer)):
        return jax.lax.index_in_dim(a, int(idx), _laxis(a, axis), keepdims=False)
    # a tuple of public indices: a static gather, the same on every
    # share (sharing is linear), no draw and no truncation
    with jax.named_scope("moose/gather"):
        return jnp.take(
            a, np.asarray(idx, dtype=np.int32), axis=_laxis(a, axis)
        )


index_axis = _structural(_index_axis_arr)
expand_dims = _structural(
    lambda a, axis: jnp.expand_dims(a, _laxis(a, axis, extra=1))
)
reshape = _structural(lambda a, shape: a.reshape(a.shape[:2] + tuple(shape)))
transpose_2d = _structural(lambda a: jnp.swapaxes(a, -1, -2))


def concat(xs, axis: int) -> SpmdRep:
    ax = _laxis(xs[0].lo, axis)
    lo = jnp.concatenate([x.lo for x in xs], axis=ax)
    hi = (
        None
        if xs[0].hi is None
        else jnp.concatenate([x.hi for x in xs], axis=ax)
    )
    return SpmdRep(lo, hi, xs[0].width)


def stack(xs, axis: int = 0) -> SpmdRep:
    ax = _laxis(xs[0].lo, axis, extra=1)
    lo = jnp.stack([x.lo for x in xs], axis=ax)
    hi = (
        None
        if xs[0].hi is None
        else jnp.stack([x.hi for x in xs], axis=ax)
    )
    return SpmdRep(lo, hi, xs[0].width)


def sum_axis(x: SpmdRep, axis: int) -> SpmdRep:
    lo, hi = ring.sum_(x.lo, x.hi, axis=_laxis(x.lo, axis))
    return SpmdRep(lo, hi, x.width)


# ---------------------------------------------------------------------------
# Probabilistic truncation (stacked form of additive/trunc.rs:115-170 +
# the PRF-compressed AdtToRep)
# ---------------------------------------------------------------------------


def trunc_pr(sess: SpmdSession, x: SpmdRep, amount: int) -> SpmdRep:
    def h(t, i, j):
        return None if t is None else t[i, j]

    # rep -> 2-party additive: a0 = x0 + x1 (party 0 holds both), a1 = x2.
    a0 = ring.add(x.lo[0, 0], h(x.hi, 0, 0), x.lo[0, 1], h(x.hi, 0, 1))
    a1 = (x.lo[1, 1], h(x.hi, 1, 1))
    return _trunc_pr_adt(sess, a0, a1, x.width, amount, x.shape)


@jax.named_scope("moose/trunc_pr")
def _trunc_pr_adt(sess, a0, a1, width, amount, shape) -> SpmdRep:
    """Probabilistic truncation from a 2-party additive sharing
    (a0 + a1 = x): the shared core of :func:`trunc_pr` and the fused
    multiply-then-truncate paths, which feed the additive sharing
    straight from the cross products + zero-share without materializing
    the intermediate replicated pair layout.

    The five PRF draws (mask r, the three additive-share masks, the
    replicated-compression share z0) happen HERE, in the historical
    session order, so the pure elementwise tail can dispatch to the
    fused Pallas kernel or its lax twin interchangeably — both consume
    identical randomness and are bit-identical."""
    draws = tuple(sess.sample(shape, width) for _ in range(5))
    z_lo, z_hi = _trunc_combine(a0, a1, draws, width, amount)
    return _pairs(z_lo, z_hi, width)


def _trunc_combine(a0, a1, draws, width, amount):
    if _rk.dispatch("trunc_combine", width):
        try:
            return _rk.trunc_combine(
                a0, a1, draws, width, amount, a0[0].shape
            )
        except Exception as e:  # noqa: BLE001 — the kernel is an
            # optimization; any failure keeps the exact XLA path
            _rk.record_fallback("trunc_combine", width, "error", e)
    return _trunc_combine_lax(a0, a1, draws, width, amount)


def _trunc_combine_lax(a0, a1, draws, width, amount):
    """The elementwise tail of probabilistic truncation given its five
    PRF draws — the historical ``_trunc_pr_adt`` math with the draws
    hoisted out (the Pallas kernel's lax twin).  Returns the stacked
    (3, *shape) replicated values (z0, z1, y1) as (z_lo, z_hi)."""
    k = width - 1
    a0_lo, a0_hi = a0
    a1_lo, a1_hi = a1
    (r_lo, r_hi), r0, rt0, rm0, (z0_lo, z0_hi) = draws
    shape = r_lo.shape

    # provider (party 2)'s mask and its derived top/msb parts,
    # additively shared against the pre-drawn masks
    r_msb_lo, r_msb_hi = ring.shr(r_lo, r_hi, width - 1)
    t_lo, t_hi = ring.shl(r_lo, r_hi, 1)
    r_top_lo, r_top_hi = ring.shr(t_lo, t_hi, amount + 1)
    r1 = ring.sub(r_lo, r_hi, r0[0], r0[1])
    rt1 = ring.sub(r_top_lo, r_top_hi, rt0[0], rt0[1])
    rm1 = ring.sub(r_msb_lo, r_msb_hi, rm0[0], rm0[1])

    ones_lo, ones_hi = ring.fill_like_shape(shape, width, 1)
    up_lo, up_hi = ring.shl(ones_lo, ones_hi, k - 1)
    down_lo, down_hi = ring.shl(ones_lo, ones_hi, k - amount - 1)

    # x_positive = x + 2^(k-1); mask with r; reveal c
    a0_lo, a0_hi = ring.add(a0_lo, a0_hi, up_lo, up_hi)
    m0_lo, m0_hi = ring.add(a0_lo, a0_hi, r0[0], r0[1])
    m1_lo, m1_hi = ring.add(a1_lo, a1_hi, r1[0], r1[1])
    c_lo, c_hi = ring.add(m0_lo, m0_hi, m1_lo, m1_hi)

    cns_lo, cns_hi = ring.shl(c_lo, c_hi, 1)
    ctop_lo, ctop_hi = ring.shr(cns_lo, cns_hi, amount + 1)
    cmsb_lo, cmsb_hi = ring.shr(c_lo, c_hi, width - 1)

    # overflow = r_msb XOR c_msb, additively: rm + cmsb - 2*rm*cmsb.
    # c is the REVEALED masked value, so cmsb is a public 0/1: the
    # rm*cmsb ring multiplication is a select (cheaper than the
    # multi-pass emulated u128 multiply on TPU)
    cmsb_on = cmsb_lo.astype(bool)

    def adt_overflow(rm, first: bool):
        p_lo = jnp.where(cmsb_on, rm[0], jnp.zeros_like(rm[0]))
        p_hi = (
            jnp.where(cmsb_on, rm[1], jnp.zeros_like(rm[1]))
            if rm[1] is not None
            else None
        )
        tw_lo, tw_hi = ring.shl(p_lo, p_hi, 1)
        o_lo, o_hi = ring.sub(rm[0], rm[1], tw_lo, tw_hi)
        if first:
            o_lo, o_hi = ring.add(o_lo, o_hi, cmsb_lo, cmsb_hi)
        return ring.shl(o_lo, o_hi, k - amount)

    of0 = adt_overflow(rm0, True)
    of1 = adt_overflow(rm1, False)

    # y_positive = (c_top - r_top) + overflow ; y = y_positive - downshifter
    y0_lo, y0_hi = ring.sub(ctop_lo, ctop_hi, rt0[0], rt0[1])
    y0_lo, y0_hi = ring.add(y0_lo, y0_hi, of0[0], of0[1])
    y0_lo, y0_hi = ring.sub(y0_lo, y0_hi, down_lo, down_hi)
    y1_lo, y1_hi = ring.neg(rt1[0], rt1[1])
    y1_lo, y1_hi = ring.add(y1_lo, y1_hi, of1[0], of1[1])

    # additive -> replicated (PRF-compressed): z0 = PRF, z1 = y0 - z0, z2 = y1
    z1_lo, z1_hi = ring.sub(y0_lo, y0_hi, z0_lo, z0_hi)
    z_lo = jnp.stack([z0_lo, z1_lo, y1_lo], axis=0)
    z_hi = (
        jnp.stack([z0_hi, z1_hi, y1_hi], axis=0)
        if z0_hi is not None else None
    )
    return z_lo, z_hi


def _mul_like_trunc(sess, x, y, contract, amount: int) -> SpmdRep:
    """Fused multiply-and-truncate: cross products + zero-share, then
    feed the (3,)-stacked z directly into truncation's 2-party additive
    form (a0 = z_0 + z_1, a1 = z_2) instead of materializing the
    replicated pair layout that trunc_pr would immediately collapse.
    Bit-identical to _reshare followed by trunc_pr (same PRF draw
    order, pure data-movement skipped); saves two full passes over the
    (3, 2, *shape) pair arrays — significant because the elementwise
    phases are bound by bytes moved, not operations (PERF.md section 3)."""
    width = x.width
    v_lo, v_hi = _cross_terms(x, y, contract)
    with jax.named_scope("moose/reshare"):  # _reshare less its pair layout
        a_lo, a_hi = zero_share(sess, v_lo.shape[1:], width)
        z_lo, z_hi = ring.add(v_lo, v_hi, a_lo, a_hi)

    def h(t, i):
        return None if t is None else t[i]

    a0 = ring.add(z_lo[0], h(z_hi, 0), z_lo[1], h(z_hi, 1))
    a1 = (z_lo[2], h(z_hi, 2))
    return _trunc_pr_adt(sess, a0, a1, width, amount, z_lo.shape[1:])


# ---------------------------------------------------------------------------
# Fixed-point layer
# ---------------------------------------------------------------------------


def fx_encode_share(sess, x_float, integ: int, frac: int, width: int):
    lo, hi = ring.fixedpoint_encode(x_float, frac, width)
    return SpmdFixed(share(sess, lo, hi, width), integ, frac)


def fx_reveal_decode(x: SpmdFixed):
    lo, hi = reveal(x.tensor)
    return ring.fixedpoint_decode(lo, hi, x.fractional_precision)


def fx_add(x: SpmdFixed, y: SpmdFixed) -> SpmdFixed:
    return SpmdFixed(
        add(x.tensor, y.tensor),
        max(x.integral_precision, y.integral_precision),
        x.fractional_precision,
    )


def fx_sub(x: SpmdFixed, y: SpmdFixed) -> SpmdFixed:
    return SpmdFixed(
        sub(x.tensor, y.tensor),
        max(x.integral_precision, y.integral_precision),
        x.fractional_precision,
    )


def fx_mul(sess, x: SpmdFixed, y: SpmdFixed) -> SpmdFixed:
    z = _mul_like_trunc(
        sess, x.tensor, y.tensor, ring.mul, x.fractional_precision
    )
    return SpmdFixed(
        z,
        max(x.integral_precision, y.integral_precision),
        x.fractional_precision,
    )


def fx_dot(sess, x: SpmdFixed, y: SpmdFixed) -> SpmdFixed:
    z = _mul_like_trunc(
        sess, x.tensor, y.tensor, _dot_contract, x.fractional_precision
    )
    return SpmdFixed(
        z,
        max(x.integral_precision, y.integral_precision),
        x.fractional_precision,
    )


def fx_mul_public(sess, x: SpmdFixed, value: float) -> SpmdFixed:
    raw = _fx_raw(value, x.fractional_precision, x.tensor.width)
    c_lo, c_hi = ring.fill_like_shape((), x.tensor.width, raw)
    z = mul_public(x.tensor, c_lo, c_hi)
    z = trunc_pr(sess, z, x.fractional_precision)
    return SpmdFixed(z, x.integral_precision, x.fractional_precision)


def _fx_raw(value: float, frac: int, width: int) -> int:
    return int(round(value * (1 << frac))) % (1 << width)


def fx_add_public(x: SpmdFixed, value: float) -> SpmdFixed:
    raw = _fx_raw(value, x.fractional_precision, x.tensor.width)
    c_lo, c_hi = ring.fill_like_shape((), x.tensor.width, raw)
    return SpmdFixed(
        add_public(x.tensor, c_lo, c_hi),
        x.integral_precision,
        x.fractional_precision,
    )


def fx_transpose(x: SpmdFixed) -> SpmdFixed:
    lo = jnp.swapaxes(x.tensor.lo, -1, -2)
    hi = None if x.tensor.hi is None else jnp.swapaxes(x.tensor.hi, -1, -2)
    return SpmdFixed(
        SpmdRep(lo, hi, x.tensor.width),
        x.integral_precision,
        x.fractional_precision,
    )


def fx_mean_rows(sess, x: SpmdFixed) -> SpmdFixed:
    """Mean over the leading data axis (axis 0 of the logical shape)."""
    n = x.tensor.shape[0]
    lo, hi = ring.sum_(x.tensor.lo, x.tensor.hi, axis=2)
    summed = SpmdRep(lo, hi, x.tensor.width)
    factor = _fx_raw(1.0 / n, x.fractional_precision, x.tensor.width)
    c_lo, c_hi = ring.fill_like_shape((), x.tensor.width, factor)
    z = mul_public(summed, c_lo, c_hi)
    z = trunc_pr(sess, z, x.fractional_precision)
    return SpmdFixed(z, x.integral_precision, x.fractional_precision)


def fx_sigmoid_poly(sess, x: SpmdFixed) -> SpmdFixed:
    """Degree-3 polynomial sigmoid approximation
    sigma(t) ~ 0.5 + 0.198285*t - 0.004469*t^3 (least-squares on [-5, 5],
    max error ~0.06) — the standard secure-logreg approximation; the exact
    protocol sigmoid (exp + division) lives in ``dialects/fixedpoint.py``."""
    x2 = fx_mul(sess, x, x)
    x3 = fx_mul(sess, x2, x)
    t1 = fx_mul_public(sess, x, 0.19828547)
    t3 = fx_mul_public(sess, x3, -0.00446928)
    return fx_add_public(fx_add(t1, t3), 0.5)


# ---------------------------------------------------------------------------
# Mesh helpers: shard the party axis + the batch axis
# ---------------------------------------------------------------------------


def make_mesh(n_devices: Optional[int] = None, devices=None):
    """Mesh with axes (parties, data).

    Whenever >=3 devices are available the party axis is a genuine size-3
    mesh axis (so share resharing lowers to collective-permute over ICI),
    with ``data = n // 3`` and any remainder devices left unused — e.g. a
    v5e-8 slice becomes a (3, 2) mesh over 6 of its 8 chips, which beats
    co-locating all three parties on every chip (reference: 3 workers on
    separate hosts, ``execution/asynchronous.rs:590-605``).  With fewer
    than 3 devices the parties are co-located (parties=1) and remaining
    devices shard the batch.
    """
    if devices is None:
        devices = jax.devices()
    devices = list(devices)[: n_devices or len(devices)]
    n = len(devices)
    if n >= 3:
        p, d = 3, n // 3
    else:
        p, d = 1, n
    arr = np.array(devices[: p * d]).reshape(p, d)
    return jax.sharding.Mesh(arr, ("parties", "data"))


def fabric_party_mesh(devices):
    """1-D mesh over axis ``"parties"`` — one lead device per party, in
    the FabricDomain's declaration order (party index = mesh position =
    ring position for the MSA6xx hop count).  The fabric transport's
    permute programs (distributed/fabric.py) run ``lax.ppermute`` over
    this axis."""
    arr = np.array(list(devices))
    if arr.ndim != 1 or arr.size < 2:
        raise ValueError(
            "fabric_party_mesh needs a flat list of >= 2 lead devices, "
            f"got shape {arr.shape}"
        )
    return jax.sharding.Mesh(arr, ("parties",))


def rep_sharding(mesh, batch_axis: Optional[int] = 0, ndim: int = 2):
    """PartitionSpec for a stacked share array (3, 2, *shape): party axis
    over 'parties', one data axis over 'data'."""
    P = jax.sharding.PartitionSpec
    spec = ["parties", None] + [None] * ndim
    if batch_axis is not None:
        spec[2 + batch_axis] = "data"
    return jax.sharding.NamedSharding(mesh, P(*spec))


def constrain(x: SpmdRep, mesh, batch_axis=0) -> SpmdRep:
    sh = rep_sharding(mesh, batch_axis, x.lo.ndim - 2)
    lo = jax.lax.with_sharding_constraint(x.lo, sh)
    hi = (
        jax.lax.with_sharding_constraint(x.hi, sh)
        if x.hi is not None
        else None
    )
    return SpmdRep(lo, hi, x.width)


# ---------------------------------------------------------------------------
# Flagship computation: secure logistic-regression training step
# (the reference's benchmark workload, benchmarks/pymoose/logreg.py)
# ---------------------------------------------------------------------------


def logreg_train_step(
    sess: SpmdSession,
    x: SpmdFixed,  # (batch, features)
    y: SpmdFixed,  # (batch, 1)
    w: SpmdFixed,  # (features, 1)
    lr: float,
    mesh=None,
):
    """One secure SGD step: w -= lr * X^T (sigmoid(Xw) - y) / batch."""
    if mesh is not None:
        x = SpmdFixed(
            constrain(x.tensor, mesh, 0),
            x.integral_precision,
            x.fractional_precision,
        )
    logits = fx_dot(sess, x, w)  # (batch, 1)
    preds = fx_sigmoid_poly(sess, logits)
    err = fx_sub(preds, y)  # (batch, 1)
    xt = fx_transpose(x)  # (features, batch)
    grad = fx_dot(sess, xt, err)  # (features, 1)
    n = x.tensor.shape[0]
    step = fx_mul_public(sess, grad, lr / n)
    return fx_sub(w, step)
