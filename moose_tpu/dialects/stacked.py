"""Stacked dialect: executes logical computations in the party-stacked
SPMD layout.

This is the compiler path from placement-labelled ``Computation``s to the
fast multi-chip layout (VERDICT r4 #1): the SAME logical IR that
``dialects/logical.py`` executes per-host is dispatched here onto the
``parallel/spmd.py`` / ``parallel/spmd_math.py`` kernels — replicated
tensors become ``SpmdRep``/``SpmdFixed``/``SpmdBits`` (one array with a
leading party axis instead of six per-party arrays), share-local math is
party-vectorized, and resharing rolls lower to ``collective-permute``
when the party axis rides a device mesh.  User graphs (``from_onnx``
predictors, traced softmax/argmax programs) reach this layout through
``LocalMooseRuntime(layout="stacked")`` without touching the spmd API.

Reference parity: the reference routes every computation through one
pipeline (``compilation/lowering.rs:4-6`` →
``execution/asynchronous.rs:558-632``); here the stacked layout is a
second *backend* for the same logical IR with identical semantics —
cross-layout equivalence against the per-host dialect is pinned by
``tests/test_stacked_backend.py``.

Host-placement ops delegate verbatim to the logical host dialect (same
``EagerSession`` kernels), so plaintext pre/post-processing is identical
across backends; only replicated-placement execution differs.
"""

from __future__ import annotations

import contextlib
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import dtypes as dt
from ..computation import (
    Computation,
    HostPlacement,
    Mirrored3Placement,
    Operation,
    ReplicatedPlacement,
)
from ..errors import TypeMismatchError
from ..execution.session import EagerSession
from ..native import ring128_kernels as _rk
from ..parallel import spmd
from ..parallel import spmd_math as sm
from ..parallel.spmd import SpmdFixed, SpmdRep, SpmdSession
from ..parallel.spmd_math import SpmdBits
from ..values import (
    HostBitTensor,
    HostFixedTensor,
    HostRingTensor,
    HostShape,
    HostString,
    HostTensor,
    HostUnit,
    Mir3FixedTensor,
    Mir3Tensor,
)
from . import logical

_STACKED_VALUES = (SpmdRep, SpmdFixed, SpmdBits)


class StackedSession:
    """Pairs an :class:`EagerSession` (host-placement kernels, identical
    to the default backend) with an :class:`SpmdSession` (party-stacked
    randomness bank) under one master key.  ``mesh`` (optional) constrains
    freshly-shared tensors to the (parties, data) device mesh so XLA
    propagates the sharding through the whole protocol program."""

    def __init__(self, master_key, key_domain: int = 0,
                 mesh=None, batch_axis: Optional[int] = 0):
        self.host = EagerSession(master_key=master_key, key_domain=key_domain)
        self.spmd = SpmdSession(master_key, domain=key_domain)
        self.mesh = mesh
        self.batch_axis = batch_axis
        self._placements = None

    @property
    def session_id(self):
        return self.host.session_id


def bind_placements(sess: StackedSession, comp: Computation):
    sess._placements = comp.placements
    logical.bind_placements(sess.host, comp)


class StackedDialect:
    """Module-shaped dialect handle carrying backend config (mesh); the
    interpreter only needs ``execute_op`` / ``to_host`` /
    ``bind_placements`` / ``make_session``."""

    def __init__(self, mesh=None, batch_axis: Optional[int] = 0):
        self.mesh = mesh
        self.batch_axis = batch_axis

    def make_session(self, master_key, key_domain: int = 0):
        return StackedSession(
            master_key, key_domain=key_domain,
            mesh=self.mesh, batch_axis=self.batch_axis,
        )

    execute_op = staticmethod(lambda *a: execute_op(*a))
    to_host = staticmethod(lambda *a: to_host(*a))
    bind_placements = staticmethod(lambda *a: bind_placements(*a))
    lift_aes_input = staticmethod(lambda *a: lift_aes_input(*a))
    effective_ops = staticmethod(lambda *a: effective_ops(*a))


# ---------------------------------------------------------------------------
# Conversions
# ---------------------------------------------------------------------------


def _constrain_opt(sess: StackedSession, t: SpmdRep) -> SpmdRep:
    if sess.mesh is None:
        return t
    batch = sess.batch_axis if t.lo.ndim - 2 >= 1 else None
    return spmd.constrain(t, sess.mesh, batch)


def _share_ring(sess: StackedSession, t: HostRingTensor) -> SpmdRep:
    return _constrain_opt(
        sess, spmd.share(sess.spmd, t.lo, t.hi, t.width)
    )


def to_rep(sess: StackedSession, v, width: Optional[int] = None):
    """Materialize any logical value as a party-stacked sharing.

    ``width`` picks the ring for SECRET INTEGER lifts (the value itself
    carries no ring): callers derive it from the consuming op's
    signature via :func:`_op_ring_width` so an integer operand meeting
    ring128 neighbours lifts at 128 instead of the old hard-coded 64
    (ADVICE r5 low #1)."""
    if isinstance(v, _STACKED_VALUES):
        return v
    if isinstance(v, HostFixedTensor):
        return SpmdFixed(
            _share_ring(sess, v.tensor),
            v.integral_precision,
            v.fractional_precision,
        )
    if isinstance(v, HostRingTensor):
        return _share_ring(sess, v)
    if isinstance(v, HostBitTensor):
        return sm.share_bits(sess.spmd, v.value)
    if isinstance(v, Mir3FixedTensor):
        # mirrored values are public; a trivial sharing keeps them cheap
        values, frac = logical._mirrored_to_public_ring(v)
        c = values[0]
        return SpmdFixed(
            spmd.public_to_rep(c.lo, c.hi, c.width),
            v.integral_precision,
            frac,
        )
    if isinstance(v, HostTensor):
        if v.dtype is not None and v.dtype.is_integer:
            # integer dialect lift (reference integer/mod.rs:12-15)
            ring = sess.host.ring_fixedpoint_encode(
                v.plc, v, 0, width or 64
            )
            return _share_ring(sess, ring)
        raise TypeMismatchError(
            "cannot share a plaintext float tensor; cast to a fixed "
            "dtype first (reference requires FixedpointEncode before "
            "Share)"
        )
    raise TypeMismatchError(
        f"cannot share {type(v).__name__} in stacked layout"
    )


def to_host(sess: StackedSession, plc_name: str, v):
    """Materialize any logical value as a host value on ``plc_name``."""
    if isinstance(v, SpmdFixed):
        lo, hi = spmd.reveal(v.tensor)
        return HostFixedTensor(
            HostRingTensor(lo, hi, v.tensor.width, plc_name),
            v.integral_precision,
            v.fractional_precision,
        )
    if isinstance(v, SpmdRep):
        lo, hi = spmd.reveal(v)
        return HostRingTensor(lo, hi, v.width, plc_name)
    if isinstance(v, SpmdBits):
        return HostBitTensor(sm.reveal_bits(v), plc_name)
    return logical.to_host(sess.host, plc_name, v)


# ---------------------------------------------------------------------------
# Structural helpers on the trailing (logical) axes of (3, 2, *shape)
# ---------------------------------------------------------------------------


def _squeeze_arr(a, axis):
    if axis is None:
        shape = a.shape[:2] + tuple(d for d in a.shape[2:] if d != 1)
        return a.reshape(shape)
    axes = (axis,) if isinstance(axis, int) else tuple(axis)
    return jnp.squeeze(a, tuple(spmd._laxis(a, ax) for ax in axes))


def _transpose_arr(a, axes):
    nd = a.ndim - 2
    if axes is None:
        axes = tuple(range(nd - 1, -1, -1))
    return jnp.transpose(
        a, (0, 1) + tuple(spmd._laxis(a, ax) for ax in axes)
    )


def _slice_arr(a, spec):
    return a[(slice(None), slice(None)) + tuple(spec)]


_squeeze = spmd._structural(_squeeze_arr)
_transpose = spmd._structural(_transpose_arr)
_strided_slice = spmd._structural(_slice_arr)


def _fx(t: SpmdRep, like: SpmdFixed) -> SpmdFixed:
    return SpmdFixed(t, like.integral_precision, like.fractional_precision)


# ---------------------------------------------------------------------------
# Replicated-placement dispatch
# ---------------------------------------------------------------------------


def _fx_sum(sess, x: SpmdFixed, axis) -> SpmdFixed:
    t = x.tensor
    if axis is None:
        flat = spmd.reshape(t, (int(np.prod(t.shape)),))
        return _fx(spmd.sum_axis(flat, 0), x)
    return _fx(spmd.sum_axis(t, axis), x)


def _fx_mean(sess, x: SpmdFixed, axis) -> SpmdFixed:
    n = (
        int(np.prod(x.tensor.shape))
        if axis is None
        else x.tensor.shape[axis]
    )
    return spmd.fx_mul_public(sess.spmd, _fx_sum(sess, x, axis), 1.0 / n)


@jax.named_scope("moose/relu")
def _relu(sess, x: SpmdFixed) -> SpmdFixed:
    """max(x, 0), exact on the encoded operand: the sign bit (``msb``),
    its conversion to arithmetic and a mux against public zeros; no
    truncation, so every layout reveals the same ring element."""
    s = sm.msb(sess.spmd, x.tensor)  # 1 <=> negative
    zeros = spmd.fill_public(x.tensor.shape, x.tensor.width, 0)
    return _fx(sm.mux_bit(sess.spmd, s, zeros, x.tensor), x)


def _abs(sess, x: SpmdFixed) -> SpmdFixed:
    s = sm.msb(sess.spmd, x.tensor)
    negated = spmd.neg(x.tensor)
    return _fx(sm.mux_bit(sess.spmd, s, negated, x.tensor), x)


_FX_MATH = {
    "Exp": sm.fx_exp,
    "Log": sm.fx_log,
    "Log2": sm.fx_log2,
    "Sqrt": sm.fx_sqrt,
    "Sigmoid": sm.fx_sigmoid,
}


def _public_binop(sess, x: SpmdFixed, pub: Mir3FixedTensor, kind: str,
                  right: bool) -> SpmdFixed:
    """x (+|-|*) mirrored-public value without sharing rounds (stacked
    form of the fixedpoint Mir ops, logical._rep_public_binop)."""
    values, pub_f = logical._mirrored_to_public_ring(pub)
    if pub_f != x.fractional_precision:
        raise TypeMismatchError(
            f"{kind} operands disagree on fractional precision: "
            f"{x.fractional_precision} vs mirrored {pub_f}"
        )
    c = values[0]
    if kind == "Add":
        return _fx(spmd.add_public(x.tensor, c.lo, c.hi), x)
    if kind == "Sub":
        out = spmd.sub_public(x.tensor, c.lo, c.hi)
        if not right:  # pub - x = -(x - pub)
            out = spmd.neg(out)
        return _fx(out, x)
    if kind == "Mul":
        out = spmd.mul_public(x.tensor, c.lo, c.hi)
        out = spmd.trunc_pr(sess.spmd, out, x.fractional_precision)
        return _fx(out, x)
    raise ValueError(kind)


def _mux_public(sess, s: SpmdBits, x: Mir3FixedTensor,
                y: Mir3FixedTensor) -> SpmdFixed:
    """Mux between two mirrored (public) branches (stacked form of
    ``logical._rep_mux_public``): the selector's conversion is the only
    protocol work; ``y + s * (x - y)`` with public ``x - y`` is local."""
    xs, x_f = logical._mirrored_to_public_ring(x)
    ys, y_f = logical._mirrored_to_public_ring(y)
    if x_f != y_f:
        raise TypeMismatchError(
            "Mux branches disagree on fractional precision: "
            f"{x_f} vs {y_f}"
        )
    out = sm.mux_bit_public(sess.spmd, s, xs[0], ys[0])
    return SpmdFixed(
        out, max(x.integral_precision, y.integral_precision), x_f
    )


def _op_ring_width(op: Operation) -> Optional[int]:
    """Ring width for secret-integer lifts, read off the op signature:
    any fixed-point dtype among the return/input types decides (an
    integer operand of a ring128 op must lift at 128 — ADVICE r5 low
    #1); explicit Ring-typed signatures decide by name; ``None`` means
    no evidence (``to_rep`` then defaults to 64, the integer dialect's
    native ring)."""
    sig = op.signature
    for ty in (sig.return_type, *sig.input_types):
        d = getattr(ty, "dtype", None)
        if d is not None and d.is_fixedpoint:
            return 64 if d.name == "fixed64" else 128
    for ty in (sig.return_type, *sig.input_types):
        name = getattr(ty, "name", "") or ""
        if "Ring128" in name:
            return 128
        if "Ring64" in name:
            return 64
    return None


def _logical_rank(v) -> Optional[int]:
    if isinstance(v, SpmdFixed):
        return len(v.tensor.shape)
    if isinstance(v, (SpmdRep, SpmdBits)):
        return len(v.shape)
    return None


def _insert_logical_axes(v, n: int):
    """Prepend ``n`` singleton LOGICAL axes — right after the
    (party, slot) stacking prefix — to one stacked value."""
    if n <= 0:
        return v
    if isinstance(v, SpmdFixed):
        return SpmdFixed(
            _insert_logical_axes(v.tensor, n),
            v.integral_precision, v.fractional_precision,
        )

    def expand(a):
        if a is None:
            return None
        return jnp.reshape(a, a.shape[:2] + (1,) * n + a.shape[2:])

    if isinstance(v, SpmdRep):
        return SpmdRep(expand(v.lo), expand(v.hi), v.width)
    if isinstance(v, SpmdBits):
        return SpmdBits(expand(v.arr))
    return v


def _align_logical_ranks(*vals):
    """NumPy broadcasting right-aligns trailing dims, but stacked
    arrays carry a (party, slot) PREFIX: logical (6, 14) against (14,)
    stacks to (3, 2, 6, 14) against (3, 2, 14), which misaligns 6
    against 2 and fails.  Insert singleton logical axes on the
    lower-rank operands so elementwise kernels broadcast by LOGICAL
    shape, exactly like the per-host layout (exercised by e.g. the
    tree-ensemble predictor's thresholds-vector-vs-gathered-features
    comparison)."""
    ranks = [_logical_rank(v) for v in vals]
    known = [r for r in ranks if r is not None]
    if not known:
        return vals
    top = max(known)
    return tuple(
        _insert_logical_axes(v, top - r) if r is not None else v
        for v, r in zip(vals, ranks)
    )


def _execute_rep(sess: StackedSession, comp, op: Operation,
                 rep: ReplicatedPlacement, args):
    kind = op.kind
    ret_dtype = op.signature.return_type.dtype
    lift_width = _op_ring_width(op)

    def as_rep(v):
        return to_rep(sess, v, width=lift_width)

    if kind == "Identity":
        return as_rep(args[0])

    if kind == "Constant":
        host_op = Operation(
            name=op.name, kind="Constant", inputs=[],
            placement_name=rep.owners[0], signature=op.signature,
            attributes=op.attributes,
        )
        h = logical._constant_on_host(sess.host, rep.owners[0], host_op)
        if isinstance(h, (HostShape, HostString)):
            return h
        return as_rep(h)

    if kind in ("Add", "Sub", "Mul", "Dot", "Div"):
        x, y = args
        if isinstance(y, Mir3FixedTensor) and kind in ("Add", "Sub", "Mul"):
            return _public_binop(sess, as_rep(x), y, kind, right=True)
        if isinstance(x, Mir3FixedTensor) and kind in ("Add", "Sub", "Mul"):
            return _public_binop(sess, as_rep(y), x, kind, right=False)
        xr, yr = as_rep(x), as_rep(y)
        if kind != "Dot":  # contraction has its own shape rules
            xr, yr = _align_logical_ranks(xr, yr)
        bare_x, bare_y = isinstance(xr, SpmdRep), isinstance(yr, SpmdRep)
        if bare_x != bare_y:
            raise TypeMismatchError(
                f"{kind} mixes a secret integer with a secret fixed-point "
                f"tensor (got {type(xr).__name__} and {type(yr).__name__})"
            )
        if bare_x and bare_y:
            fn = {
                "Add": lambda: spmd.add(xr, yr),
                "Sub": lambda: spmd.sub(xr, yr),
                "Mul": lambda: spmd.mul(sess.spmd, xr, yr),
                "Dot": lambda: spmd.dot(sess.spmd, xr, yr),
            }.get(kind)
            if fn is None:
                raise NotImplementedError(
                    "Div on secret uint64 is undefined (ring division)"
                )
            return fn()
        fn = {
            "Add": lambda: spmd.fx_add(xr, yr),
            "Sub": lambda: spmd.fx_sub(xr, yr),
            "Mul": lambda: spmd.fx_mul(sess.spmd, xr, yr),
            "Dot": lambda: spmd.fx_dot(sess.spmd, xr, yr),
            "Div": lambda: sm.fx_div(sess.spmd, xr, yr),
        }[kind]
        return fn()

    if kind == "Conv2D":
        x = as_rep(args[0])
        k = as_rep(args[1])
        if x.fractional_precision != k.fractional_precision:
            raise TypeMismatchError(
                "conv operands disagree on fractional precision: "
                f"{x.fractional_precision} vs {k.fractional_precision}"
            )
        return spmd.fx_conv2d(
            sess.spmd, x, k,
            strides=tuple(op.attributes.get("strides", (1, 1))),
            padding=op.attributes.get("padding", "VALID"),
        )

    if kind in ("AvgPool2D", "MaxPool2D"):
        x = as_rep(args[0])
        pool = tuple(op.attributes["pool_size"])
        strides = op.attributes.get("strides")
        strides = tuple(strides) if strides is not None else None
        padding = op.attributes.get("padding", "VALID")
        fn = (
            sm.fx_avg_pool2d if kind == "AvgPool2D" else sm.fx_max_pool2d
        )
        return fn(sess.spmd, x, pool, strides, padding)

    if kind == "AddN":
        vals = [as_rep(a) for a in args]
        out = vals[0]
        for v in vals[1:]:
            out = (
                spmd.add(out, v)
                if isinstance(out, SpmdRep)
                else spmd.fx_add(out, v)
            )
        return out

    if kind == "Neg":
        x = as_rep(args[0])
        if isinstance(x, SpmdFixed):
            return _fx(spmd.neg(x.tensor), x)
        return spmd.neg(x)

    if kind in ("Less", "Greater", "Equal"):
        x, y = _align_logical_ranks(as_rep(args[0]), as_rep(args[1]))
        xt = x.tensor if isinstance(x, SpmdFixed) else x
        yt = y.tensor if isinstance(y, SpmdFixed) else y
        if kind == "Less":
            return sm.less(sess.spmd, xt, yt)
        if kind == "Greater":
            return sm.greater(sess.spmd, xt, yt)
        return sm.equal_bit(sess.spmd, xt, yt)

    if kind in ("And", "Or", "Xor"):
        x = as_rep(args[0])
        y = as_rep(args[1])
        if kind == "Xor":
            return sm.bits_xor(x, y)
        fn = sm.bits_and if kind == "And" else sm.bits_or
        return fn(sess.spmd, x, y)

    if kind == "Mux":
        s = as_rep(args[0])
        if not isinstance(s, SpmdBits):
            raise TypeMismatchError(
                f"stacked Mux selector must be shared bits, got "
                f"{type(s).__name__}"
            )
        if isinstance(args[1], Mir3FixedTensor) and isinstance(
            args[2], Mir3FixedTensor
        ):
            return _mux_public(sess, s, args[1], args[2])
        s, x, y = _align_logical_ranks(s, as_rep(args[1]), as_rep(args[2]))
        if isinstance(x, SpmdRep):
            return sm.mux_bit(sess.spmd, s, x, y)
        if not isinstance(x, SpmdFixed) or not isinstance(y, SpmdFixed):
            raise TypeMismatchError(
                f"stacked Mux branches must both be secret fixed or "
                f"both secret ring tensors, got {type(x).__name__} and "
                f"{type(y).__name__}"
            )
        out = sm.mux_bit(sess.spmd, s, x.tensor, y.tensor)
        return _fx(out, x)

    if kind in ("Sum", "Mean"):
        x = as_rep(args[0])
        axis = op.attributes.get("axis")
        if isinstance(x, SpmdRep):
            # secret integer tensor (bare ring shares)
            if kind == "Mean":
                raise TypeMismatchError(
                    "Mean on secret uint64 is undefined (ring division); "
                    "cast to a fixed dtype first"
                )
            if axis is None:
                return spmd.sum_axis(
                    spmd.reshape(x, (int(np.prod(x.shape)),)), 0
                )
            return spmd.sum_axis(x, axis)
        fn = _fx_sum if kind == "Sum" else _fx_mean
        return fn(sess, x, axis)

    if kind in _FX_MATH:
        return _FX_MATH[kind](sess.spmd, as_rep(args[0]))

    if kind == "Relu":
        return _relu(sess, as_rep(args[0]))

    if kind == "Abs":
        return _abs(sess, as_rep(args[0]))

    if kind == "Softmax":
        x = as_rep(args[0])
        return sm.fx_softmax(
            sess.spmd, x, op.attributes["axis"],
            upmost_index=op.attributes.get("upmost_index"),
        )

    if kind == "Argmax":
        x = as_rep(args[0])
        return sm.fx_argmax(
            sess.spmd, x, op.attributes["axis"],
            upmost_index=op.attributes.get("upmost_index"),
        )

    if kind == "Maximum":
        vals = list(_align_logical_ranks(*[as_rep(a) for a in args]))
        if isinstance(vals[0], SpmdRep):
            raise TypeMismatchError(
                "Maximum on secret uint64 needs a signed comparison "
                "convention; cast to a fixed dtype first"
            )
        return sm.fx_maximum(sess.spmd, vals)

    if kind == "Concat":
        vals = [as_rep(a) for a in args]
        axis = op.attributes.get("axis", 0)
        if isinstance(vals[0], SpmdRep):
            return spmd.concat(vals, axis)
        out = spmd.concat([v.tensor for v in vals], axis)
        return _fx(out, vals[0])

    if kind == "Reshape":
        x = as_rep(args[0])
        shp = to_host(sess, rep.owners[0], args[1])
        inner = x.tensor if isinstance(x, SpmdFixed) else x
        out = spmd.reshape(inner, tuple(shp.value))
        return _fx(out, x) if isinstance(x, SpmdFixed) else out

    if kind == "ExpandDims":
        x = as_rep(args[0])
        inner = x.tensor if isinstance(x, SpmdFixed) else x
        out = inner
        for a in sorted(op.attributes["axis"]):
            out = spmd.expand_dims(out, a)
        return _fx(out, x) if isinstance(x, SpmdFixed) else out

    if kind == "Squeeze":
        x = as_rep(args[0])
        inner = x.tensor if isinstance(x, SpmdFixed) else x
        out = _squeeze(inner, op.attributes.get("axis"))
        return _fx(out, x) if isinstance(x, SpmdFixed) else out

    if kind == "Transpose":
        x = as_rep(args[0])
        inner = x.tensor if isinstance(x, SpmdFixed) else x
        out = _transpose(inner, op.attributes.get("axes"))
        return _fx(out, x) if isinstance(x, SpmdFixed) else out

    if kind == "IndexAxis":
        x = as_rep(args[0])
        inner = x.tensor if isinstance(x, SpmdFixed) else x
        out = spmd.index_axis(
            inner, op.attributes["axis"], op.attributes["index"]
        )
        return _fx(out, x) if isinstance(x, SpmdFixed) else out

    if kind == "Slice":
        x = as_rep(args[0])
        inner = x.tensor if isinstance(x, SpmdFixed) else x
        spec = logical.decode_slice_spec(op.attributes)
        out = _strided_slice(inner, spec)
        return _fx(out, x) if isinstance(x, SpmdFixed) else out

    if kind == "Shape":
        x = as_rep(args[0])
        inner = x.tensor if isinstance(x, SpmdFixed) else x
        return HostShape(tuple(inner.shape), rep.owners[0])

    if kind == "Cast":
        if ret_dtype is None or not ret_dtype.is_fixedpoint:
            raise TypeMismatchError(
                "stacked Cast on a replicated placement must target a "
                f"fixed-point dtype, got {ret_dtype}"
            )
        x = as_rep(args[0])
        new_f = ret_dtype.fractional_precision
        if isinstance(x, SpmdRep):
            # secret integer -> fixed: the scale-0 shares scaled up by
            # 2^f (integer lift + precision move; the lift width above
            # already follows the target fixed dtype's ring).  A sharing
            # produced at another width (e.g. by an upstream all-integer
            # op that lifted at 64) cannot just be relabelled — reject
            # so the runtime falls back to the per-host path
            target_w = 64 if ret_dtype.name == "fixed64" else 128
            if x.width != target_w:
                raise TypeMismatchError(
                    f"stacked Cast to {ret_dtype} needs a ring{target_w} "
                    f"sharing, got ring{x.width}"
                )
            t = spmd.shl(x, new_f) if new_f else x
            return SpmdFixed(t, ret_dtype.integral_precision, new_f)
        if not isinstance(x, SpmdFixed):
            raise TypeMismatchError(
                f"stacked Cast cannot convert {type(x).__name__} to "
                f"{ret_dtype}"
            )
        cur_f = x.fractional_precision
        t = x.tensor
        if new_f > cur_f:
            t = spmd.shl(t, new_f - cur_f)
        elif new_f < cur_f:
            t = spmd.trunc_pr(sess.spmd, t, cur_f - new_f)
        return SpmdFixed(t, ret_dtype.integral_precision, new_f)

    if kind == "Decrypt":
        from . import aes

        return aes.decrypt_stacked(sess.spmd, op, args[0], args[1])

    raise NotImplementedError(f"stacked replicated op {kind} ({op.name})")


# replicated-placement kinds the stacked backend executes; used by
# supports() so the runtime can fall back to the per-host path for
# anything else (e.g. a future op kind before its stacked kernel lands)
_REP_KINDS = frozenset({
    "Identity", "Constant", "Add", "Sub", "Mul", "Dot", "Div", "AddN",
    "Neg", "Less", "Greater", "Equal", "And", "Or", "Xor", "Mux", "Sum",
    "Mean", "Exp", "Log", "Log2", "Sqrt", "Sigmoid", "Relu", "Abs",
    "Softmax", "Argmax", "Maximum", "Concat", "Reshape", "ExpandDims",
    "Squeeze", "Transpose", "IndexAxis", "Slice", "Shape", "Cast",
    "Decrypt", "Conv2D", "AvgPool2D", "MaxPool2D",
})


def effective_ops(comp: Computation) -> int:
    """Expanded-program-size estimate for the TPU heavy-jit gate
    (interpreter.heavy_jit_gate): stacked graphs are short at the
    logical level, but a replicated nonlinear op expands to thousands
    of XLA ops inside one jit program — exactly the size class where
    the experimental TPU backend's known miscompile lives (DEVELOP.md
    "Known issue"; a fused fixed(24,40) protocol sigmoid measurably
    diverges while the same math runs exactly under eager dispatch).
    Weighing by ``logical.EXPANSION_WEIGHTS`` routes such graphs into
    the validated-jit self-check instead of blind whole-graph jit."""
    total = 0
    for op in comp.operations.values():
        plc = comp.placements.get(op.placement_name)
        if isinstance(plc, ReplicatedPlacement):
            total += logical.EXPANSION_WEIGHTS.get(op.kind, 20)
        else:
            total += 3
    return total


# replicated kinds whose operands must agree on the value family:
# mixing a secret integer (bare ring shares) with a secret fixed-point
# tensor has no stacked kernel — _execute_rep raises TypeMismatchError
# — so supports() keeps such graphs on the per-host path up front
_MIXED_SENSITIVE_KINDS = frozenset({
    "Add", "Sub", "Mul", "Dot", "Div", "AddN", "Less", "Greater",
    "Equal", "Maximum", "Mux", "Concat",
})


def supports(comp: Computation) -> bool:
    """Whether every op of ``comp`` has a stacked execution path.

    Host/mirrored placements delegate to the logical dialect (full
    coverage); replicated placements are checked against
    :data:`_REP_KINDS` plus signature-level screens for the value
    shapes ``_execute_rep``/``to_rep`` reject at dispatch time (ADVICE
    r5 low #2: a graph that passes supports() should execute, not error
    mid-run — the runtime additionally catches ``TypeMismatchError``
    and retries per-host as a belt-and-braces fallback).  Dynamic-shape
    ops (Select) stay on the default backend.  AES decryption IS
    covered — on the replicated placement only (a host-placement
    Decrypt of a stacked-shared key would need a reveal; the default
    backend handles that rare shape).
    """
    from ..computation import AES_TY_NAMES

    # boundary kinds are handled by the interpreter walk itself, before
    # placement dispatch — legal on any placement
    boundary = frozenset({"Input", "Output", "Save", "Load"})
    for op in comp.operations.values():
        plc = comp.placements.get(op.placement_name)
        if op.kind == "Select":
            return False
        if op.kind == "Decrypt" and not isinstance(plc, ReplicatedPlacement):
            return False
        if isinstance(plc, ReplicatedPlacement):
            if op.kind not in _REP_KINDS and op.kind not in boundary:
                return False
            sig = op.signature
            ret_dtype = sig.return_type.dtype if sig.return_type else None
            if op.kind == "Constant" and ret_dtype is not None \
                    and ret_dtype.is_float:
                # a plaintext float cannot be shared (to_rep requires a
                # fixed encode first)
                return False
            if op.kind == "Cast" and (
                ret_dtype is None or not ret_dtype.is_fixedpoint
            ):
                # replicated Cast only moves precision within/into the
                # fixed family; anything else must go via a host
                return False
            if op.kind in _MIXED_SENSITIVE_KINDS:
                dts = [
                    ty.dtype
                    for ty in (sig.return_type, *sig.input_types)
                    if getattr(ty, "dtype", None) is not None
                ]
                if any(d.is_integer for d in dts) and any(
                    d.is_fixedpoint for d in dts
                ):
                    return False
        if not isinstance(plc, (HostPlacement, ReplicatedPlacement,
                                Mirrored3Placement)):
            return False
        if isinstance(plc, HostPlacement):
            # host ops never consume AES-typed values in the stacked
            # world except as opaque pass-through (Input/Output)
            if op.kind not in ("Input", "Output", "Identity") and any(
                ty is not None and ty.name in AES_TY_NAMES
                for ty in op.signature.input_types
            ):
                return False
    return True


def lift_aes_input(sess: StackedSession, comp, op, arr, plc_name: str):
    """AES boundary values in the stacked layout: ciphertexts stay host
    bit tensors (shared at Decrypt); a replicated-placement key shares
    straight into the party-stacked bit layout."""
    from ..computation import ReplicatedPlacement as _Rep
    from . import aes

    plc_obj = comp.placements[plc_name]
    ret = op.signature.return_type
    if (
        isinstance(plc_obj, _Rep)
        and ret.name in ("AesKey", "ReplicatedAesKey")
    ):
        # jnp.asarray directly: `arr` may be a jit tracer
        bits = jnp.asarray(arr).astype(jnp.uint8)
        from ..parallel import spmd_math as sm

        return aes.StackedAesKey(sm.share_bits(sess.spmd, bits))
    return aes.lift_input(sess.host, comp, op, arr, plc_name)


def execute_op(sess: StackedSession, comp: Computation, op: Operation,
               args: list):
    """Execute one logical operation in the stacked layout."""
    if sess.mesh is not None and sess.mesh.size > 1:
        # GSPMD will partition this program, and cannot partition a
        # Mosaic kernel: the exact XLA path runs across chips
        with _rk.declined():
            return _execute_op(sess, comp, op, args)
    return _execute_op(sess, comp, op, args)


def _execute_op(sess: StackedSession, comp: Computation, op: Operation,
                args: list):
    plc = comp.placement_of(op)
    if isinstance(plc, HostPlacement):
        h_args = [
            to_host(sess, plc.name, a)
            if isinstance(a, _STACKED_VALUES)
            else a
            for a in args
        ]
        return logical._execute_host(sess.host, comp, op, plc, h_args)
    if isinstance(plc, ReplicatedPlacement):
        # an op that carries the attribute ``scope`` (what a predictor
        # says the op is part of: ``layers.DenseStack`` tags a layer's
        # dot and bias ``dense``) is traced under ``moose/<scope>``: the
        # device trace splits by it, and nothing else reads it
        scope = op.attributes.get("scope")
        with (
            jax.named_scope(f"moose/{scope}") if scope
            else contextlib.nullcontext()
        ):
            return _execute_rep(sess, comp, op, plc, args)
    if isinstance(plc, Mirrored3Placement):
        return logical._execute_mir(sess.host, comp, op, plc, args)
    raise TypeError(f"unsupported placement {plc!r} for op {op.name}")
