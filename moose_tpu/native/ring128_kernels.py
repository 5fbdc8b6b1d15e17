"""Pallas TPU kernels for the hot ring64/ring128 stacked primitives.

The known TPU-jit miscompile (DEVELOP.md "Known issue") lives in XLA's
whole-program passes over LARGE fusions of emulated 64/128-bit integer
math — the fixed(24,40) protocol sigmoid's b2a/polynomial region is the
sharpest reproducer, and it forced the PR-2 validated-jit ladder down to
per-op pinning on the single hottest path in the system (the user
path ran op by op where a hand-written program ran whole).  These
kernels sidestep that class of bug structurally: each hot primitive is
ONE opaque Mosaic program whose internals XLA cannot re-fuse, so the
128-bit stacked world compiles as a whole-graph jit with zero pinned
ops.

Design:

- Mosaic has no 64-bit vector lanes, so every kernel operates on
  **uint32 word planes** — a ring64 value is 2 planes, ring128 is 4;
  the u64<->u32 split/recombine happens OUTSIDE the kernel as one fused
  XLA elementwise pass.  Inside, values are lists of 16-bit limbs held
  in u32 lanes (products of 16-bit limbs are exact in u32; column sums
  stay far below 2^32), with explicit carry normalization.
- Real Mosaic kernels on TPU; ``interpret=True`` everywhere else, so
  tier-1 CI exercises the IDENTICAL kernel code on CPU.
- Selection rides the ``MOOSE_TPU_PALLAS`` knob (``1`` force on, ``0``
  force off, unset = auto: on iff the backend is TPU) with
  **per-primitive XLA fallback**: each (kernel, width) is self-checked
  bit-exactly against its lax twin on first use — the twin compiled for
  the CPU backend, so that kernel and reference never share a compiler
  (:func:`_twin_eval`) — and a divergence or error falls that primitive
  back to the XLA path for the rest of the process, visibly:
  ``moose_tpu_pallas_fallback_total{kernel=...,reason=...}`` and the
  exception's text in :func:`report`.  Kernels decline under a device
  mesh (:func:`declined`), and a kernel the chip showed trouble around
  is switched off by name with the reason (``_SWITCHED_OFF``).
- Kernel inventory: ``ring_mul`` (elementwise two-limb multiply),
  ``cross_terms_mul`` (the fused v_i = x_i*(y_i+y_{i+1}) + x_{i+1}*y_i
  of secure mul, ``parallel/spmd.py:_cross_terms``),
  ``trunc_combine`` (the full elementwise tail of probabilistic
  truncation after its five PRF draws, ``spmd._trunc_pr_adt``),
  ``bit_decompose``/``msb`` (plain-bit extraction + carry-save + the
  Kogge-Stone adder inner loop of ``parallel/spmd_math.py``, consuming
  pre-drawn AND banks), ``horner`` (the fused fixed-point polynomial
  ladder of ``spmd_math.polynomial_eval`` — the fx_sigmoid / exp
  region where the miscompile actually bites), and
  ``dot_cross_terms`` (party-batched 8-bit-limb matmul cross terms).

Honest status: the elementwise/bit/polynomial kernels are the point —
they replace exactly the emulated-integer fusion region XLA miscompiles.
The dot kernel is correctness-proven but OFF by default
(``MOOSE_TPU_PALLAS_DOT=1`` opts in): component ring dots already jit
exactly on TPU (DEVELOP.md localization) through the limb_int8 MXU
path, which beats the kernel's padded-tile layout on the small-n
predictor shapes; it ships as the fabric for future fused dot+truncate
work, like the threefry kernel before it.

PRF-draw discipline: kernels never draw randomness.  Callers pre-draw
the exact sequence the lax path would (same session-counter order), so
a computation is bit-identical with kernels on, off, or mixed — pinned
by ``tests/test_ring128_kernels.py``.
"""

from __future__ import annotations

import contextlib
import functools
import os
import threading
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

U8 = jnp.uint8
U32 = jnp.uint32
U64 = jnp.uint64
MASK16 = np.uint32(0xFFFF)
MASK32 = np.uint64(0xFFFFFFFF)

# elementwise block: multiples of the int32 VPU tile (8, 128)
_BLOCK_ROWS = 8
_BLOCK_COLS = 128
_BLOCK = _BLOCK_ROWS * _BLOCK_COLS


# ---------------------------------------------------------------------------
# Selection knob + per-primitive fallback state + first-use self-check
# ---------------------------------------------------------------------------

# Kernels switched off by name, each with what the chip showed.  A
# kernel leaves this table with a chip run that shows the cause gone.
_SWITCHED_OFF: Dict[str, str] = {
    "ring_mul": (
        "PR 22, TPU v5e: with this kernel in the served logreg's jitted "
        "stacked plan (fixed(24,40), ring128, batch 1) the answer is off "
        "by ~2^47 for some master keys (2 of 32 on one input), exact for "
        "the same keys eagerly, with every kernel off, and with only "
        "this kernel off (then 0 of 40); knocking out any other kernel "
        "leaves it wrong.  The kernel passes its own check on the chip, "
        "so the suspect is XLA:TPU's fusion of the u64<->u32 plane "
        "plumbing around it (the value-dependent whole-program "
        "miscompile of DEVELOP.md \"Known issue\").  PERF.md has the runs."
    ),
}

_OVERRIDE: Optional[bool] = None
_STATE: Dict[Tuple[str, int], str] = {}  # (kernel, width) -> "ok"/"fallback:.."
# (kernel, width) -> text of the exception behind a "fallback:*" verdict
_ERRORS: Dict[Tuple[str, int], str] = {}
_STATE_LOCK = threading.RLock()
_KEY_LOCKS: Dict[Tuple[str, int], "threading.Lock"] = {}
_DECLINED = threading.local()


@contextlib.contextmanager
def declined():
    """Every dispatch on this thread answers False inside the block.

    Two callers.  A first-use self-check, whose lax twin must stay PURE
    lax (and the non-reentrant-lock deadlock a twin's dispatch would
    cause is moot).  And a trace that GSPMD will partition over a device
    mesh (``dialects/stacked.py`` with a mesh of more than one device;
    an ambient ``with mesh:`` is seen by :func:`dispatch` itself):
    Mosaic refuses it — "Mosaic kernels cannot be automatically
    partitioned. Please wrap the call in a shard_map", seen compiling
    the stacked plans for a described v5e:2x2 (PR 22) — and
    ``bit_decompose``/``msb``/``horner`` move data across the party axis
    inside the kernel, so a per-party ``shard_map`` is a redesign, not a
    wrapper (ROADMAP R7).  Until then the exact XLA path runs across
    chips, on every platform alike."""
    was = getattr(_DECLINED, "active", False)
    _DECLINED.active = True
    try:
        yield
    finally:
        _DECLINED.active = was


def _under_device_mesh() -> bool:
    from ..parallel import spmd

    mesh = spmd._ambient_mesh()
    return mesh is not None and mesh.size > 1


def set_enabled(value: Optional[bool]) -> None:
    """Programmatic override of MOOSE_TPU_PALLAS: True/False force,
    None restores the env/auto default (tests, bench A/B)."""
    global _OVERRIDE
    _OVERRIDE = value


def enabled() -> bool:
    """Whether Pallas kernels are selected: programmatic override wins,
    then MOOSE_TPU_PALLAS (1/0), else auto — on iff the backend is TPU
    (interpret-mode kernels are correctness tools, not a CPU speedup)."""
    if _OVERRIDE is not None:
        return _OVERRIDE
    env = os.environ.get("MOOSE_TPU_PALLAS")
    if env is not None and env != "":
        if env not in ("0", "1"):
            from ..errors import ConfigurationError

            raise ConfigurationError(
                f"MOOSE_TPU_PALLAS must be '0' or '1', got {env!r}"
            )
        return env == "1"
    return jax.default_backend() == "tpu"


def dot_enabled() -> bool:
    """The env-only view of the dot opt-in (the absolute knob:
    ``MOOSE_TPU_PALLAS_DOT=1`` forces the kernel wherever the family is
    on).  The dispatch gate itself is shape-aware: with the knob unset
    it asks the autotuner's measured per-shape-class policy
    (``compilation.autotune.dot_kernel_wanted``) — predictor-small
    shapes keep limb_int8, measured-faster MXU shapes get the kernel."""
    return enabled() and os.environ.get("MOOSE_TPU_PALLAS_DOT") == "1"


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def reset_state() -> None:
    """Forget self-check verdicts and fallbacks (tests)."""
    with _STATE_LOCK:
        _STATE.clear()
        _ERRORS.clear()


def report() -> dict:
    """Bench/debug surface: the knob verdict plus per-(kernel, width)
    state ("ok" after a clean first-use check, "fallback:<reason>"),
    for each fallback the exception that caused it — on a TPU backend a
    ``fallback:error`` is a compile refusal or a crash, and whoever
    attests the kernel path (``chip_smoke.py``) fails on it — and the
    kernels switched off by name, with the reason."""
    with _STATE_LOCK:
        return {
            "enabled": enabled(),
            "kernels": {
                f"{k}/{w}": v for (k, w), v in sorted(_STATE.items())
            },
            "errors": {
                f"{k}/{w}": v for (k, w), v in sorted(_ERRORS.items())
            },
            "switched_off": dict(_SWITCHED_OFF),
        }


def _count_dispatch(kernel: str) -> None:
    from .. import metrics

    metrics.counter(
        "moose_tpu_pallas_dispatch_total",
        "trace-time routings of a primitive into its Pallas kernel",
        labels=("kernel",),
    ).inc(kernel=kernel)


def _count_fallback(kernel: str, reason: str) -> None:
    from .. import metrics

    metrics.counter(
        "moose_tpu_pallas_fallback_total",
        "Pallas primitives demoted to the XLA path",
        labels=("kernel", "reason"),
    ).inc(kernel=kernel, reason=reason)


def record_fallback(kernel: str, width: int, reason: str,
                    exc: Optional[BaseException] = None) -> None:
    """Pin a (kernel, width) to the XLA path for the process (divergence
    from the lax twin, compile refusal or runtime error), with the
    metric, one log line, and the exception's text kept for
    :func:`report`."""
    from ..logger import get_logger

    with _STATE_LOCK:
        _STATE[(kernel, width)] = f"fallback:{reason}"
        if exc is not None:
            _ERRORS[(kernel, width)] = (
                f"{type(exc).__name__}: {exc}"[:2000]
            )
    _count_fallback(kernel, reason)
    get_logger().warning(
        "pallas kernel %s/ring%d fell back to XLA (%s)%s",
        kernel, width, reason, f": {exc}" if exc is not None else "",
    )


def dispatch(kernel: str, width: int, shape=None) -> bool:
    """True when ``kernel`` should run at ``width``: knob on, width
    supported, and the first-use bit-exactness self-check against the
    lax twin passed.  A failed check records a permanent per-process
    fallback; a pass is cached.  The check runs EAGERLY on canned
    shapes (it needs concrete values to compare), so calling this from
    inside a jit trace is safe — the verdict is a Python bool.

    ``shape`` (``(m, k, n)``, dot only) routes the decision through the
    autotuner's measured per-shape-class policy when the absolute knob
    ``MOOSE_TPU_PALLAS_DOT`` is unset: classes where the A/B micro
    measured the MXU kernel faster than limb_int8 XLA turn it on; the
    rest — and every call without a shape — keep the XLA path."""
    if width not in (64, 128) or kernel in _SWITCHED_OFF:
        return False
    if getattr(_DECLINED, "active", False) or _under_device_mesh():
        return False  # see declined()
    if kernel == "dot_cross_terms":
        if not enabled():
            return False
        env = os.environ.get("MOOSE_TPU_PALLAS_DOT")
        if env == "0":
            return False
        if env != "1":
            from ..compilation import autotune

            if not autotune.dot_kernel_wanted(width, shape):
                return False
    elif not enabled():
        return False
    key = (kernel, width)
    state = _STATE.get(key)
    if state is None:
        # per-key lock: first uses of DIFFERENT (kernel, width) pairs
        # check concurrently; only the verdict publishes under the
        # global lock (a module-wide lock would serialize every
        # thread's first session behind seconds of sequential checks)
        with _STATE_LOCK:
            state = _STATE.get(key)
            key_lock = _KEY_LOCKS.setdefault(key, threading.Lock())
        if state is None:
            with key_lock:
                state = _STATE.get(key)
                if state is None:
                    state = _run_first_use_check(kernel, width)
    if state == "ok":
        _count_dispatch(kernel)
        from .. import profiling

        # trace-time marker on the profile timeline: which primitives
        # actually routed into their Pallas kernels during this capture
        profiling.record_instant(
            "pallas_dispatch", kernel=kernel, width=width,
        )
        return True
    return False


def _run_first_use_check(kernel: str, width: int) -> str:
    # Dispatch legitimately happens at TRACE time (protocol code under
    # jax.jit — e.g. a plan the registry restored straight to "jit"
    # mode).  The check needs CONCRETE values to compare, so it runs on
    # a fresh thread: trace contexts are thread-local, so the worker
    # executes eagerly no matter what the calling thread is tracing —
    # without this, the check's jitted comparisons would stage into the
    # outer trace and mis-pin the kernel to fallback:error.
    box: Dict[str, BaseException] = {}

    def worker(span):
        try:  # under the span of whoever met the kernel first
            with telemetry.attach(span), declined():  # both thread-local
                _CHECKS[kernel](width)
        except BaseException as e:  # noqa: BLE001 — classified below
            box["exc"] = e

    from .. import telemetry

    with telemetry.span("pallas_selfcheck", kernel=kernel, width=width) as sp:
        t = threading.Thread(
            target=worker, args=(sp,), name=f"pallas-check-{kernel}-{width}"
        )
        t.start()
        t.join()
        exc = box.get("exc")
        # a wrong kernel must not serve (diverged); one the backend refused
        # cannot (error).  Both keep the exact XLA path and stay visible
        reason = "diverged" if isinstance(exc, AssertionError) else "error"
        sp.attrs["verdict"] = "ok" if exc is None else reason
    if exc is None:
        with _STATE_LOCK:
            _STATE[(kernel, width)] = "ok"
        return "ok"
    if not isinstance(exc, Exception):
        raise exc  # KeyboardInterrupt / SystemExit: not a verdict
    record_fallback(kernel, width, reason, exc)
    return f"fallback:{reason}"


# ---------------------------------------------------------------------------
# u64 <-> u32-plane <-> 16-bit-limb plumbing
# ---------------------------------------------------------------------------


def _n_planes(width: int) -> int:
    return width // 32


def _to_planes(lo, hi) -> jax.Array:
    """(lo, hi) u64 arrays -> (L, n) u32 word planes, little-endian
    (one split implementation: :func:`_planes_keep` with no kept
    leading dims)."""
    return _planes_keep(lo, hi, 0)


def _from_planes(planes, shape, width: int):
    """(L, n) u32 planes -> (lo, hi) u64 arrays of ``shape``."""
    lo = planes[0].astype(U64) | (planes[1].astype(U64) << np.uint64(32))
    lo = lo.reshape(shape)
    if width == 64:
        return lo, None
    hi = planes[2].astype(U64) | (planes[3].astype(U64) << np.uint64(32))
    return lo, hi.reshape(shape)


def _tile(planes, rows: int = _BLOCK_ROWS) -> jax.Array:
    """(..., n) -> (..., R, 128) with R a multiple of ``rows``
    (zero-padded).  u32 kernels block 8 rows (the int32 VPU tile); the
    uint8 bit kernels block 32 (the int8 tile)."""
    n = planes.shape[-1]
    block = rows * _BLOCK_COLS
    pad = (-n) % block
    if pad:
        planes = jnp.pad(
            planes, [(0, 0)] * (planes.ndim - 1) + [(0, pad)]
        )
    return planes.reshape(
        planes.shape[:-1] + ((n + pad) // _BLOCK_COLS, _BLOCK_COLS)
    )


def _untile(tiles, n: int) -> jax.Array:
    return tiles.reshape(tiles.shape[:-2] + (-1,))[..., :n]


# -- in-kernel 16-bit-limb arithmetic (u32 lanes, explicit carries) ---------
# A ring value inside a kernel is a list of width//16 u32 arrays, each
# normalized to < 2^16.  All helpers are plain traced jnp, so they work
# identically compiled by Mosaic and in interpret mode.


def _ksplit(planes):
    """u32 word planes -> 16-bit limb list (little-endian)."""
    out = []
    for p in planes:
        out.append(p & MASK16)
        out.append(p >> np.uint32(16))
    return out


def _kjoin(limbs):
    """Normalized 16-bit limb list -> u32 word planes."""
    return [
        limbs[2 * i] | (limbs[2 * i + 1] << np.uint32(16))
        for i in range(len(limbs) // 2)
    ]


def _knorm(limbs):
    out = []
    carry = None
    for limb in limbs:
        t = limb if carry is None else limb + carry
        out.append(t & MASK16)
        carry = t >> np.uint32(16)
    return out


def _kadd(a, b):
    return _knorm([x + y for x, y in zip(a, b)])


def _kneg(a):
    comp = [MASK16 - x for x in a]
    comp[0] = comp[0] + np.uint32(1)
    return _knorm(comp)


def _ksub(a, b):
    return _kadd(a, _kneg(b))


def _kmul(a, b):
    """Schoolbook product mod 2^(16*len(a)): 16-bit limb products are
    exact in u32; columns accumulate split lo/hi halves (each column
    sums <= 2*len 16-bit terms, far below 2^32) then normalize."""
    nl = len(a)
    zero = jnp.zeros_like(a[0])
    cols = [zero] * (nl + 1)
    for i in range(nl):
        for j in range(nl - i):
            p = a[i] * b[j]
            cols[i + j] = cols[i + j] + (p & MASK16)
            cols[i + j + 1] = cols[i + j + 1] + (p >> np.uint32(16))
    return _knorm(cols[:nl])


def _kshl(a, amount: int):
    nl = len(a)
    ls, bs = amount // 16, amount % 16
    zero = jnp.zeros_like(a[0])
    out = []
    for i in range(nl):
        if i - ls < 0:
            out.append(zero)
            continue
        v = a[i - ls] << np.uint32(bs)
        if i - ls - 1 >= 0 and bs:
            v = v | (a[i - ls - 1] >> np.uint32(16 - bs))
        out.append(v & MASK16)
    return out


def _kshr(a, amount: int):
    nl = len(a)
    ls, bs = amount // 16, amount % 16
    zero = jnp.zeros_like(a[0])
    out = []
    for i in range(nl):
        if i + ls >= nl:
            out.append(zero)
            continue
        v = a[i + ls] >> np.uint32(bs)
        if i + ls + 1 < nl and bs:
            v = v | (a[i + ls + 1] << np.uint32(16 - bs))
        out.append(v & MASK16)
    return out


def _kconst(value: int, nl: int):
    """Static ring constant as broadcastable u32 scalars."""
    return [
        np.uint32((int(value) >> (16 * i)) & 0xFFFF) for i in range(nl)
    ]


def _ktrunc(a0, a1, r, mr, mrt, mrm, z0, width: int, amount: int):
    """The elementwise tail of probabilistic truncation given its five
    PRF draws — limb-for-limb the math of ``spmd._trunc_pr_adt`` after
    the draws.  Returns the (z0, z1, y1) replicated stack."""
    nl = width // 16
    k = width - 1
    r_msb = _kshr(r, width - 1)
    r_top = _kshr(_kshl(r, 1), amount + 1)
    r1 = _ksub(r, mr)
    rt1 = _ksub(r_top, mrt)
    rm1 = _ksub(r_msb, mrm)

    a0p = _kadd(a0, _kconst(1 << (k - 1), nl))
    m0 = _kadd(a0p, mr)
    m1 = _kadd(a1, r1)
    c = _kadd(m0, m1)
    ctop = _kshr(_kshl(c, 1), amount + 1)
    cmsb_bit = c[nl - 1] >> np.uint32(15)  # public 0/1 lane
    zero = jnp.zeros_like(cmsb_bit)
    cmsb = [cmsb_bit] + [zero] * (nl - 1)

    def overflow(rm, first: bool):
        p = [limb * cmsb_bit for limb in rm]
        o = _ksub(rm, _kshl(p, 1))
        if first:
            o = _kadd(o, cmsb)
        return _kshl(o, k - amount)

    of0 = overflow(mrm, True)
    of1 = overflow(rm1, False)
    y0 = _ksub(
        _kadd(_ksub(ctop, mrt), of0),
        _kconst(1 << (k - amount - 1), nl),
    )
    y1 = _kadd(_kneg(rt1), of1)
    z1 = _ksub(y0, z0)
    return z0, z1, y1


# ---------------------------------------------------------------------------
# Elementwise kernel family: flat (L, R, 128) u32 plane stacks
# ---------------------------------------------------------------------------


# literal 0s in an index map would trace as i64 under this package's
# x64 mode and fail Mosaic legalization (PR 22's first refusal on a
# chip); every block index below is built from this i32 zero
_I0 = np.int32(0)


def _flat_spec(a):
    return _full_lead_spec(a.shape[:-2])


def _flat_call(name: str, body, ins, out_lead, n_grid_rows: int):
    out_shape = jax.ShapeDtypeStruct(
        out_lead + (n_grid_rows * _BLOCK_ROWS, _BLOCK_COLS), U32
    )
    return pl.pallas_call(
        body,
        name=name,
        grid=(n_grid_rows,),
        in_specs=[_flat_spec(a) for a in ins],
        out_specs=_flat_spec(out_shape),
        out_shape=out_shape,
        interpret=_interpret(),
    )(*ins)


def _read_limbs(ref, L: int):
    return _ksplit([ref[i] for i in range(L)])


def _write_limbs(ref, limbs, offset: int = 0):
    for i, plane in enumerate(_kjoin(limbs)):
        ref[offset + i] = plane


def _mul_body(x_ref, y_ref, o_ref, *, L):
    _write_limbs(
        o_ref, _kmul(_read_limbs(x_ref, L), _read_limbs(y_ref, L))
    )


def ring_mul(lo1, hi1, lo2, hi2, width: int):
    """Elementwise ring multiply mod 2^width (the two-limb u64 multiply
    of ``ring.mul``), one fused Mosaic program."""
    shape = lo1.shape
    n = int(np.prod(shape)) if shape else 1
    L = _n_planes(width)
    a = _tile(_to_planes(lo1, hi1))
    b = _tile(_to_planes(lo2, hi2))
    out = _flat_call(
        "ring_mul", functools.partial(_mul_body, L=L), [a, b], (L,),
        a.shape[-2] // _BLOCK_ROWS,
    )
    return _from_planes(_untile(out, n), shape, width)


def _cross_mul_body(x0_ref, x1_ref, y0_ref, y1_ref, o_ref, *, L):
    x0 = _read_limbs(x0_ref, L)
    x1 = _read_limbs(x1_ref, L)
    y0 = _read_limbs(y0_ref, L)
    y1 = _read_limbs(y1_ref, L)
    v = _kadd(_kmul(x0, _kadd(y0, y1)), _kmul(x1, y0))
    _write_limbs(o_ref, v)


def cross_terms_mul(x0, x1, y0, y1, width: int):
    """Fused v = x0*(y0+y1) + x1*y0 (the regrouped cross terms of
    secure mul, ``spmd._cross_terms`` with an elementwise contraction):
    one HBM round trip instead of four elementwise XLA passes.  Each
    argument is a (lo, hi) pair; the party axis rides flattened.  The
    x and y operands may broadcast against each other as ``ring.mul``
    lets them (a softmax divides rows x classes by a rows x 1 sum): the
    kernel walks flat lanes, so each is brought to the common shape
    first."""
    shape = jnp.broadcast_shapes(x0[0].shape, y0[0].shape)
    n = int(np.prod(shape)) if shape else 1
    L = _n_planes(width)

    def full(pair):
        return (
            None if part is None else jnp.broadcast_to(part, shape)
            for part in pair
        )

    tiles = [_tile(_to_planes(*full(v))) for v in (x0, x1, y0, y1)]
    out = _flat_call(
        "cross_terms_mul",
        functools.partial(_cross_mul_body, L=L), tiles, (L,),
        tiles[0].shape[-2] // _BLOCK_ROWS,
    )
    return _from_planes(_untile(out, n), shape, width)


def _trunc_body(a0_ref, a1_ref, r_ref, mr_ref, mrt_ref, mrm_ref, z0_ref,
                o_ref, *, L, width, amount):
    z0, z1, y1 = _ktrunc(
        _read_limbs(a0_ref, L), _read_limbs(a1_ref, L),
        _read_limbs(r_ref, L), _read_limbs(mr_ref, L),
        _read_limbs(mrt_ref, L), _read_limbs(mrm_ref, L),
        _read_limbs(z0_ref, L), width, amount,
    )
    for party, limbs in enumerate((z0, z1, y1)):
        for i, plane in enumerate(_kjoin(limbs)):
            o_ref[party, i] = plane


def trunc_combine(a0, a1, draws, width: int, amount: int, shape):
    """The full elementwise tail of ``spmd._trunc_pr_adt`` — masks,
    reveal, overflow correction, downshift, additive-to-replicated —
    fused into one Mosaic program.  ``draws`` is the (r, m_r, m_rt,
    m_rm, z0) tuple pre-drawn by the caller in the lax path's exact
    session order.  Returns the stacked (3, *shape) (z_lo, z_hi)."""
    n = int(np.prod(shape)) if shape else 1
    L = _n_planes(width)
    ins = [_tile(_to_planes(*v)) for v in (a0, a1, *draws)]
    R = ins[0].shape[-2]
    out_shape = jax.ShapeDtypeStruct((3, L, R, _BLOCK_COLS), U32)
    out = pl.pallas_call(
        functools.partial(
            _trunc_body, L=L, width=width, amount=amount
        ),
        name="trunc_combine",
        grid=(R // _BLOCK_ROWS,),
        in_specs=[_flat_spec(a) for a in ins],
        out_specs=_flat_spec(out_shape),
        out_shape=out_shape,
        interpret=_interpret(),
    )(*ins)
    flat = _untile(out, n)  # (3, L, n)
    z_lo = (
        flat[:, 0].astype(U64) | (flat[:, 1].astype(U64) << np.uint64(32))
    ).reshape((3,) + tuple(shape))
    if width == 64:
        return z_lo, None
    z_hi = (
        flat[:, 2].astype(U64) | (flat[:, 3].astype(U64) << np.uint64(32))
    ).reshape((3,) + tuple(shape))
    return z_lo, z_hi


class ShapeUnsupported(Exception):
    """A shape guard rejected this invocation (too big for VMEM, k out
    of the exactness bound, ...): the caller falls back to the XLA path
    for THIS call only — the (kernel, width) verdict is untouched."""


# ---------------------------------------------------------------------------
# Bit kernels: plain-bit extraction + carry-save + Kogge-Stone adder
# (the inner loop of spmd_math.bit_decompose / msb), uint8 XOR shares
# ---------------------------------------------------------------------------


def _planes_keep(lo, hi, n_lead: int) -> jax.Array:
    """Like :func:`_to_planes` but flattening only the dims AFTER the
    first ``n_lead`` (the party/slot stacking prefix)."""
    lo = jnp.asarray(lo, U64)
    flat = lo.reshape(lo.shape[:n_lead] + (-1,))
    planes = [
        (flat & MASK32).astype(U32), (flat >> np.uint64(32)).astype(U32)
    ]
    if hi is not None:
        hi = jnp.asarray(hi, U64).reshape(flat.shape)
        planes += [
            (hi & MASK32).astype(U32), (hi >> np.uint64(32)).astype(U32)
        ]
    return jnp.stack(planes)


def _roll_party(a):
    """roll(-1) over a static size-3 leading party axis (Mosaic-safe:
    concatenation of static slices, no gather)."""
    return jnp.concatenate([a[1:], a[:1]], axis=0)


def _unpack_bits(words, k: int, axis: int):
    """u32 word planes (bit j of the value at bit j%32 of word j//32)
    -> the k uint8 0/1 bit planes, stacked on ``axis``."""
    return jnp.stack(
        [
            ((words[j // 32] >> np.uint32(j % 32)) & np.uint32(1))
            .astype(U8)
            for j in range(k)
        ],
        axis=axis,
    )


def bank_words_shape(width: int, n: int) -> tuple:
    """(L, R, 128): one party's AND bank over ``n`` lanes as the bit
    kernels read it, :func:`_tile`'s tiling at ``_BITS_ROWS`` (the pad
    lanes' masks are drawn and ignored)."""
    block = _BITS_ROWS * _BLOCK_COLS
    return (_n_planes(width), -(-n // block) * _BITS_ROWS, _BLOCK_COLS)


def unpack_bank(bank, width: int, shape):
    """One (3, L, R, 128) u32 bank as the (3, k, *shape) uint8 masks
    ``b_and`` unpacks in VMEM, one broadcast shift: the lax twin's."""
    lanes = _untile(bank, int(np.prod(shape)))[:, :, None]  # (3, L, 1, n)
    bit = (lanes >> np.arange(32, dtype=U32).reshape(32, 1)) & np.uint32(1)
    return bit.astype(U8).reshape((3, width) + tuple(shape))


def _bits_body(x_ref, banks_ref, o_ref, *, L, width, msb_only):
    k = width
    # x_ref[i] is (3, 2, 8, 128) u32 -> B is (3, 2, k, 8, 128) u8
    B = _unpack_bits([x_ref[i] for i in range(L)], k, axis=2)
    # the three summand selections of spmd_math._summand_mask — party j
    # holds x_j at pair slots (j, 0) and (j-1, 1) — assembled by static
    # stacking (Pallas kernels cannot capture ndarray mask constants)
    zero = jnp.zeros_like(B[0, 0])

    def summand(j: int):
        rows = []
        for p in range(3):
            s0 = B[p, 0] if p == j else zero
            s1 = B[p, 1] if p == (j - 1) % 3 else zero
            rows.append(jnp.stack([s0, s1]))
        return jnp.stack(rows)

    b0, b1, b2 = summand(0), summand(1), summand(2)

    bank_idx = [0]

    def b_and(x, y):
        # stacked replicated AND over Z_2 consuming one pre-drawn bank
        # (spmd_math.bits_and with the PRF draw hoisted out)
        x0, x1 = x[:, 0], x[:, 1]
        y0, y1 = y[:, 0], y[:, 1]
        v = (x0 & (y0 ^ y1)) ^ (x1 & y0)
        s = _unpack_bits(  # (3, k, 8, 128) u8
            [banks_ref[bank_idx[0], :, i] for i in range(L)], k, axis=1
        )
        bank_idx[0] += 1
        z = v ^ (s ^ _roll_party(s))
        return jnp.stack([z, _roll_party(z)], axis=1)

    def b_shl(x, d):
        if d == 0:
            return x
        if d >= k:
            return jnp.zeros_like(x)
        zero = jnp.zeros_like(x[:, :, :d])
        return jnp.concatenate([zero, x[:, :, : k - d]], axis=2)

    # carry-save: s = b0^b1^b2 ; c = (b0&b1) ^ ((b0^b1)&b2)
    s = b0 ^ b1 ^ b2
    c = b_and(b0, b1) ^ b_and(b0 ^ b1, b2)
    x_, y_ = s, b_shl(c, 1)
    # Kogge-Stone: log2(k) rounds of two ANDs over the whole tensor
    p = x_ ^ y_
    g = b_and(x_, y_)
    p_run = p
    d = 1
    while d < k:
        g = g ^ b_and(p_run, b_shl(g, d))
        if d * 2 < k:
            p_run = b_and(p_run, b_shl(p_run, d))
        d *= 2
    out = p ^ b_shl(g, 1)
    if msb_only:
        o_ref[...] = out[:, :, k - 1]
    else:
        o_ref[...] = out


def _full_lead_spec(lead, rows: int = _BLOCK_ROWS):
    nlead = len(lead)
    return pl.BlockSpec(
        lead + (rows, _BLOCK_COLS),
        functools.partial(
            lambda i, nlead: (_I0,) * nlead + (i, _I0), nlead=nlead
        ),
        memory_space=pltpu.VMEM,
    )


# 8 data rows per block, the u32 tile of the packed inputs.  The bit
# planes the body unpacks are uint8 (3, 2, k, rows, 128) values, about
# ten of them live at once: ~1 MiB each at ring128 with 8 rows, four
# times that at the uint8-native 32-row tile, which would not fit VMEM.
_BITS_ROWS = 8


def _bits_call(lo, hi, width: int, banks, msb_only: bool):
    shape = lo.shape[2:]
    n = int(np.prod(shape)) if shape else 1
    L = _n_planes(width)
    xt = _tile(_planes_keep(lo, hi, 2), _BITS_ROWS)  # (L, 3, 2, R, 128)
    R = xt.shape[-2]
    assert banks.shape[2:] == (L, R, _BLOCK_COLS), banks.shape  # drawn so
    out_lead = (3, 2) if msb_only else (3, 2, width)
    out_shape = jax.ShapeDtypeStruct(out_lead + (R, _BLOCK_COLS), U8)
    out = pl.pallas_call(
        functools.partial(
            _bits_body, L=L, width=width, msb_only=msb_only
        ),
        name="msb" if msb_only else "bit_decompose",
        grid=(R // _BITS_ROWS,),
        in_specs=[
            _full_lead_spec((L, 3, 2), _BITS_ROWS),
            _full_lead_spec((banks.shape[0], 3, L), _BITS_ROWS),
        ],
        out_specs=_full_lead_spec(out_lead, _BITS_ROWS),
        out_shape=out_shape,
        interpret=_interpret(),
    )(xt, banks)
    return _untile(out, n).reshape(out_lead + tuple(shape))


def bit_decompose(lo, hi, width: int, banks):
    """Arithmetic -> binary sharing (``spmd_math.bit_decompose``) as ONE
    Mosaic program: plain-bit planes of the held shares, static summand
    masks, carry-save, and the full Kogge-Stone adder, on the pre-drawn
    AND banks (``spmd_math._draw_adder_banks``: (n_ands, 3) banks of
    :func:`bank_words_shape`).  Returns (3, 2, k, *shape) uint8 bits."""
    return _bits_call(lo, hi, width, banks, msb_only=False)


def msb(lo, hi, width: int, banks):
    """:func:`bit_decompose` writing only the top bit plane
    (3, 2, *shape) — same compute, 1/k-th the HBM output traffic (the
    comparison path msb/less/greater needs nothing else)."""
    return _bits_call(lo, hi, width, banks, msb_only=True)


def adder_bank_count(width: int) -> int:
    """How many AND banks the fused decompose/adder kernel consumes, by
    replaying its structure (callers size the pre-draw with this; the
    order is: 2 carry-save ANDs, the adder's initial g = x AND y, then
    per round the g update and — while d*2 < k — the p_run update)."""
    n = 2  # carry-save
    n += 1  # g = x AND y
    d = 1
    while d < width:
        n += 1  # g ^= p_run AND shl(g, d)
        if d * 2 < width:
            n += 1  # p_run AND shl(p_run, d)
        d *= 2
    return n


# ---------------------------------------------------------------------------
# Fused Horner polynomial (spmd_math.polynomial_eval): the fx_sigmoid /
# exp region the TPU miscompile actually bites
# ---------------------------------------------------------------------------


def _horner_body(x0_ref, x1_ref, zb_ref, td_ref, o_ref, *, L, width, f,
                 raws, steps):
    nl = width // 16
    x0 = _ksplit([x0_ref[i] for i in range(L)])  # limbs (3, 8, 128)
    x1 = _ksplit([x1_ref[i] for i in range(L)])
    xsum = _kadd(x0, x1)
    # party masks built in-kernel (no captured ndarray constants)
    pid = jax.lax.broadcasted_iota(U32, (3, 1, 1), 0)
    mask_p0 = (pid == np.uint32(0)).astype(U32)
    mask_p2 = (pid == np.uint32(2)).astype(U32)

    def const_at(raw: int, mask):
        # trivial public sharing: x_0 = raw held at pair slots
        # (party0, slot0) / (party2, slot1) — mask selects the party
        return [
            np.uint32((int(raw) >> (16 * i)) & 0xFFFF) * mask
            for i in range(nl)
        ]

    acc0 = const_at(raws[0], mask_p0)
    acc1 = const_at(raws[0], mask_p2)
    for st in range(steps):
        # cross terms of fx_mul(acc, x): v_i = acc0_i*(x0_i + x1_i)
        #                                      + acc1_i*x0_i
        v = _kadd(_kmul(acc0, xsum), _kmul(acc1, x0))
        # zero-share: alpha_i = s_i - s_{i+1}
        s = _ksplit([zb_ref[st, i] for i in range(L)])
        z = _kadd(v, _ksub(s, [_roll_party(limb) for limb in s]))
        # fused truncate from the 2-party additive form
        a0 = _kadd([limb[0] for limb in z], [limb[1] for limb in z])
        a1 = [limb[2] for limb in z]
        dr = [
            _ksplit([td_ref[st, d, i] for i in range(L)])
            for d in range(5)
        ]
        z0, z1, y1 = _ktrunc(a0, a1, *dr, width, f)
        zst = [
            jnp.stack([z0[i], z1[i], y1[i]]) for i in range(nl)
        ]
        acc0, acc1 = zst, [_roll_party(limb) for limb in zst]
        # + public coefficient (only share x_0 adjusted)
        acc0 = _kadd(acc0, const_at(raws[st + 1], mask_p0))
        acc1 = _kadd(acc1, const_at(raws[st + 1], mask_p2))
    for i, plane in enumerate(_kjoin(acc0)):
        o_ref[0, i] = plane
    for i, plane in enumerate(_kjoin(acc1)):
        o_ref[1, i] = plane


def horner(x0, x1, width: int, raws, f: int, zbanks, tdraws, shape):
    """Fused fixed-point Horner ladder (``polynomial_eval``): every
    step's cross terms, zero-share add, probabilistic truncation, and
    public-coefficient add run inside ONE Mosaic program — no XLA
    fusion decisions anywhere in the polynomial region.

    ``x0``/``x1`` are the (lo, hi) pair-slot arrays (3, *shape);
    ``raws`` the encoded coefficients highest-first (raws[0] seeds the
    accumulator); ``zbanks`` the per-step zero-share banks stacked
    (steps, 3, *shape) as (lo, hi); ``tdraws`` the per-step truncation
    draws stacked (steps, 5, *shape) as (lo, hi) — both pre-drawn in
    the lax path's exact session order.  Returns the (slot0, slot1)
    pair arrays of the resulting sharing as ((lo, hi), (lo, hi))."""
    steps = len(raws) - 1
    n = int(np.prod(shape)) if shape else 1
    L = _n_planes(width)
    x0t = _tile(_planes_keep(x0[0], x0[1], 1))  # (L, 3, R, 128)
    x1t = _tile(_planes_keep(x1[0], x1[1], 1))
    zbt = jnp.moveaxis(
        _tile(_planes_keep(zbanks[0], zbanks[1], 2)), 0, 1
    )  # (steps, L, 3, R, 128)
    tdt = jnp.moveaxis(
        _tile(_planes_keep(tdraws[0], tdraws[1], 2)), 0, 1
    )  # (steps, 5, L, R, 128) after the second moveaxis below
    tdt = jnp.moveaxis(tdt, 2, 1)
    R = x0t.shape[-2]
    out_shape = jax.ShapeDtypeStruct((2, L, 3, R, _BLOCK_COLS), U32)
    out = pl.pallas_call(
        functools.partial(
            _horner_body, L=L, width=width, f=f,
            raws=tuple(int(r) for r in raws), steps=steps,
        ),
        name="horner",
        grid=(R // _BLOCK_ROWS,),
        in_specs=[
            _full_lead_spec((L, 3)),
            _full_lead_spec((L, 3)),
            _full_lead_spec((steps, L, 3)),
            _full_lead_spec((steps, 5, L)),
        ],
        out_specs=_full_lead_spec((2, L, 3)),
        out_shape=out_shape,
        interpret=_interpret(),
    )(x0t, x1t, zbt, tdt)
    flat = _untile(out, n)  # (2, L, 3, n)

    def slot(si):
        lo = (
            flat[si, 0].astype(U64)
            | (flat[si, 1].astype(U64) << np.uint64(32))
        ).reshape((3,) + tuple(shape))
        if width == 64:
            return lo, None
        hi = (
            flat[si, 2].astype(U64)
            | (flat[si, 3].astype(U64) << np.uint64(32))
        ).reshape((3,) + tuple(shape))
        return lo, hi

    return slot(0), slot(1)


# ---------------------------------------------------------------------------
# Party-batched dot cross terms (opt-in; see module docstring)
# ---------------------------------------------------------------------------

_DOT_CHUNK = 256  # 8-bit limb products < 2^16; 256-term f32 dots < 2^24
# Mosaic's scoped VMEM limit is 16 MiB on v5e; the margin covers the
# compiler's own matmul scratch, which _dot_vmem_bytes cannot see
_DOT_VMEM_BUDGET = 14 << 20


def _dot_vmem_bytes(bm: int, bn: int, kp: int, width: int) -> int:
    """Upper bound on what one grid step of :func:`_dot_body` holds in
    VMEM: the four u32 operand blocks and the output block, each
    double-buffered by the Pallas pipeline; the f32 8-bit limbs of all
    four operands (live across the whole diagonal loop — the term the
    pre-PR-22 search left out, which is what Mosaic refused at ring128
    1000^3); and the u32 diagonal and column accumulators."""
    L, in8, nl = width // 32, width // 8, width // 16
    operands = 2 * bm * kp + 2 * kp * bn
    io = 4 * L * 2 * (operands + bm * bn)
    limbs = 4 * in8 * operands
    acc = 4 * (in8 + nl + 2) * bm * bn
    return io + limbs + acc


def _dot_body(x0_ref, x1_ref, y0_ref, ys_ref, o_ref, *, width):
    L = width // 32
    in8 = width // 8
    nl = width // 16

    def limbs8(ref):
        planes = [ref[i, 0] for i in range(L)]  # (m, k) / (k, n)
        out = []
        for l8 in range(in8):
            p = planes[l8 // 4]
            # Mosaic has no u32<->f32 cast; the limb is < 2^8, so the
            # detour through int32 is exact
            out.append(
                ((p >> np.uint32(8 * (l8 % 4))) & np.uint32(0xFF))
                .astype(jnp.int32).astype(jnp.float32)
            )
        return out

    a0 = limbs8(x0_ref)
    a1 = limbs8(x1_ref)
    b0 = limbs8(y0_ref)
    bs = limbs8(ys_ref)
    k = a0[0].shape[-1]
    chunks = [
        (c, min(c + _DOT_CHUNK, k)) for c in range(0, k, _DOT_CHUNK)
    ]
    m, n = a0[0].shape[0], b0[0].shape[-1]
    zero = jnp.zeros((m, n), U32)

    def diags(a, b):
        ds = []
        for s in range(in8):
            acc = None
            for i in range(min(s + 1, in8)):
                j = s - i
                if j >= in8:
                    continue
                for (c0, c1) in chunks:
                    p = jax.lax.dot_general(
                        a[i][:, c0:c1], b[j][c0:c1, :],
                        (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32,
                    ).astype(jnp.int32).astype(U32)  # < 2^24: exact
                    acc = p if acc is None else acc + p
            ds.append(acc if acc is not None else zero)
        return ds

    cols = [zero] * (nl + 2)

    def accumulate(ds):
        # byte-aligned diagonals folded into 16-bit columns; values stay
        # far below 2^32 (each term < 2^16, < 100 terms per column)
        for s, d in enumerate(ds):
            half = s // 2
            if s % 2 == 0:
                cols[half] = cols[half] + (d & MASK16)
                cols[half + 1] = cols[half + 1] + (d >> np.uint32(16))
            else:
                cols[half] = cols[half] + (
                    (d & np.uint32(0xFF)) << np.uint32(8)
                )
                cols[half + 1] = cols[half + 1] + (
                    (d >> np.uint32(8)) & MASK16
                )
                cols[half + 2] = cols[half + 2] + (d >> np.uint32(24))

    accumulate(diags(a0, bs))
    accumulate(diags(a1, b0))
    out_limbs = _knorm(cols[:nl])
    for i, plane in enumerate(_kjoin(out_limbs)):
        o_ref[i, 0] = plane


def _dot_tile_plan(m: int, k: int, n: int, width: int):
    """Deterministic tile/segment search for the dot kernel: returns
    ``(bm, bn, kseg)`` — m/n block sizes and the host-side contraction
    segment length.  Preference order: fewest k segments (each segment
    is a separate pallas call accumulated with a ring add), then the
    largest ``bm``, then ``bn`` that fit the VMEM budget.  The per-call
    contraction is bounded by the u32 diagonal exactness limit
    ``(255 // in8) * _DOT_CHUNK``.  Raises :class:`ShapeUnsupported`
    only when nothing fits (degenerate dims)."""
    if m <= 0 or k <= 0 or n <= 0:
        raise ShapeUnsupported(f"degenerate dot shape ({m},{k},{n})")
    in8 = width // 8
    max_k = (255 // in8) * _DOT_CHUNK
    mp, np_ = -(-m // 8) * 8, -(-n // 128) * 128

    def ladder(top, steps):
        out = [top]
        out.extend(s for s in steps if s < top)
        return out

    bms = ladder(mp, (512, 256, 128, 64, 32, 16, 8))
    bns = ladder(np_, (512, 256, 128))
    for segs in range(1, -(-k // 128) + 1):
        kseg = -(-k // segs)
        if kseg > max_k:
            continue
        kp = -(-kseg // 128) * 128
        for bm in bms:
            for bn in bns:
                if _dot_vmem_bytes(bm, bn, kp, width) <= _DOT_VMEM_BUDGET:
                    return bm, bn, kseg
    raise ShapeUnsupported(
        f"no dot tiling fits VMEM for ({m},{k},{n}) ring{width}"
    )


def dot_cross_terms(x0, x1, y0, ysum, width: int, *, tile_plan=None):
    """Fused party-batched matmul cross terms
    v_p = x0_p @ (y0+y1)_p + x1_p @ y0_p over 8-bit limbs on f32 MXU
    dots (exact: products < 2^16, 256-term chunks < 2^24, u32 diagonal
    accumulation).  ``ysum`` is precomputed by the caller (one cheap
    ring add).  Arguments are (lo, hi) pairs shaped (3, m, k) /
    (3, k, n).

    MXU-shaped work is tiled: the grid runs (party, m-tiles, n-tiles)
    with per-tile operands in VMEM, and contractions past the u32
    exactness / VMEM bound are split into k segments on the host — dot
    distributes over ring addition mod 2^w, so per-segment partials
    accumulate exactly with a ring add.  ``tile_plan`` overrides the
    deterministic search (tests force multi-tile grids on small
    shapes).  Raises :class:`ShapeUnsupported` only for degenerate
    shapes."""
    from ..dialects import ring

    a_lo = x0[0]
    if a_lo.ndim != 3 or y0[0].ndim != 3:
        raise ShapeUnsupported("dot kernel needs (3, m, k) @ (3, k, n)")
    _, m, k = a_lo.shape
    n = y0[0].shape[-1]
    L = _n_planes(width)
    bm, bn, kseg = (
        tile_plan if tile_plan is not None
        else _dot_tile_plan(m, k, n, width)
    )
    kp = -(-kseg // 128) * 128
    mt, nt = -(-m // bm), -(-n // bn)
    mp, np_ = mt * bm, nt * bn

    def prep(lo, hi, rows, cols_, r_pad, c_pad):
        planes = _planes_keep(lo, hi, 3).reshape(-1, 3, rows, cols_)
        return jnp.pad(
            planes,
            ((0, 0), (0, 0), (0, r_pad - rows), (0, c_pad - cols_)),
        )

    def slice_x(v, c0, c1):
        hi = None if v[1] is None else v[1][:, :, c0:c1]
        return prep(v[0][:, :, c0:c1], hi, m, c1 - c0, mp, kp)

    def slice_y(v, c0, c1):
        hi = None if v[1] is None else v[1][:, c0:c1, :]
        return prep(v[0][:, c0:c1, :], hi, c1 - c0, n, kp, np_)

    def spec(rows, cols_, index):
        return pl.BlockSpec(
            (L, 1, rows, cols_), index, memory_space=pltpu.VMEM,
        )

    call = pl.pallas_call(
        functools.partial(_dot_body, width=width),
        name="dot_cross_terms",
        grid=(3, mt, nt),
        in_specs=[
            spec(bm, kp, lambda p, i, j: (_I0, p, i, _I0)),
            spec(bm, kp, lambda p, i, j: (_I0, p, i, _I0)),
            spec(kp, bn, lambda p, i, j: (_I0, p, _I0, j)),
            spec(kp, bn, lambda p, i, j: (_I0, p, _I0, j)),
        ],
        out_specs=spec(bm, bn, lambda p, i, j: (_I0, p, i, j)),
        out_shape=jax.ShapeDtypeStruct((L, 3, mp, np_), U32),
        interpret=_interpret(),
    )

    acc = None
    for c0 in range(0, k, kseg):
        c1 = min(c0 + kseg, k)
        out = call(
            slice_x(x0, c0, c1), slice_x(x1, c0, c1),
            slice_y(y0, c0, c1), slice_y(ysum, c0, c1),
        )[:, :, :m, :n]
        lo = out[0].astype(U64) | (out[1].astype(U64) << np.uint64(32))
        hi = (
            None if width == 64
            else out[2].astype(U64) | (out[3].astype(U64) << np.uint64(32))
        )
        acc = (lo, hi) if acc is None else ring.add(*acc, lo, hi)
    return acc


# ---------------------------------------------------------------------------
# First-use self-checks: kernel vs lax twin, bit-exact, canned shapes
# (incl. a non-aligned trailing dim) — the per-kernel analogue of the
# PR-2 ladder's jit-vs-eager bit-exactness discipline.
# ---------------------------------------------------------------------------


def _check_rng():
    return np.random.default_rng(0xC0FFEE)


def _jit_eval(fn):
    """Run a zero-arg closure under jit: interpret-mode pallas calls
    cost ~0.4s per EAGER invocation (the interpreter machinery, not the
    math), so the first-use checks trace once and execute compiled —
    they run at dispatch time inside user processes."""
    return jax.jit(fn)()


def _twin_eval(fn):
    """:func:`_jit_eval` for a check's lax twin, on the CPU backend.

    The twin is the reference, so it must not share the kernel's
    compiler.  On the chip (PR 22) XLA:TPU compiled the Kogge-Stone twin
    of ``msb``/``bit_decompose`` wrong at the check's own shape — from
    the first adder round on, at both widths — while the Mosaic kernel
    agreed stage by stage with the same body run on the CPU; judged
    against the TPU twin, two right kernels were pinned
    ``fallback:diverged``.  XLA:CPU is the reference the whole test
    suite already trusts.  Where no CPU backend is initialised
    (``JAX_PLATFORMS=tpu``) the twin runs on the default device, as
    before."""
    try:
        cpu = jax.devices("cpu")[0]
    except RuntimeError:
        return _jit_eval(fn)
    with jax.default_device(cpu):
        return jax.device_get(_jit_eval(fn))


def _rand_ring(rng, shape, width: int):
    lo = jnp.asarray(rng.integers(0, 1 << 64, size=shape, dtype=np.uint64))
    if width == 64:
        return lo, None
    hi = jnp.asarray(rng.integers(0, 1 << 64, size=shape, dtype=np.uint64))
    return lo, hi


def _assert_bitwise(got, want, label: str):
    g_lo, g_hi = got
    w_lo, w_hi = want
    assert np.array_equal(np.asarray(g_lo), np.asarray(w_lo)), (
        f"{label}: lo limb diverged"
    )
    if w_hi is not None:
        assert np.array_equal(np.asarray(g_hi), np.asarray(w_hi)), (
            f"{label}: hi limb diverged"
        )


# one shape, deliberately NOT tile-aligned; the test suite sweeps more
_CHECK_SHAPES = ((3, 5),)


def _check_mul(width: int) -> None:
    from ..dialects import ring

    rng = _check_rng()
    for shape in _CHECK_SHAPES + ((9,),):
        x = _rand_ring(rng, shape, width)
        y = _rand_ring(rng, shape, width)
        _assert_bitwise(
            _jit_eval(lambda: ring_mul(*x, *y, width)),
            _twin_eval(lambda: ring.mul(*x, *y)),
            f"ring_mul{shape}",
        )


def _check_cross(width: int) -> None:
    from ..dialects import ring

    rng = _check_rng()
    for shape in ((3, 4, 5),):
        vals = [_rand_ring(rng, shape, width) for _ in range(4)]
        x0, x1, y0, y1 = vals

        def want_fn():
            ys = ring.add(*y0, *y1)
            return ring.add(*ring.mul(*x0, *ys), *ring.mul(*x1, *y0))

        _assert_bitwise(
            _jit_eval(lambda: cross_terms_mul(x0, x1, y0, y1, width)),
            _twin_eval(want_fn),
            f"cross_terms_mul{shape}",
        )


def _check_trunc(width: int) -> None:
    from ..parallel import spmd

    rng = _check_rng()
    for shape in _CHECK_SHAPES:
        a0 = _rand_ring(rng, shape, width)
        a1 = _rand_ring(rng, shape, width)
        draws = tuple(_rand_ring(rng, shape, width) for _ in range(5))
        for amount in (7,):
            want = _twin_eval(
                lambda: spmd._trunc_combine_lax(
                    a0, a1, draws, width, amount
                )
            )
            got = _jit_eval(
                lambda: trunc_combine(a0, a1, draws, width, amount, shape)
            )
            _assert_bitwise(got, want, f"trunc_combine{shape}/{amount}")


def _check_bits_common(width: int, msb_only: bool) -> None:
    from ..parallel import spmd_math as sm

    rng = _check_rng()
    k = width
    n_ands = adder_bank_count(width)
    for shape in ((3, 5), (6,)):
        lo = jnp.asarray(
            rng.integers(0, 1 << 64, size=(3, 2) + shape, dtype=np.uint64)
        )
        hi = (
            jnp.asarray(rng.integers(
                0, 1 << 64, size=(3, 2) + shape, dtype=np.uint64
            ))
            if width == 128 else None
        )
        banks = jnp.asarray(rng.integers(
            0, 1 << 32, dtype=np.uint32,
            size=(n_ands, 3) + bank_words_shape(k, int(np.prod(shape))),
        ))
        want = _twin_eval(
            lambda: sm._bit_decompose_with_banks(lo, hi, width, banks)
        )
        if msb_only:
            got = _jit_eval(lambda: msb(lo, hi, width, banks))
            want = want[:, :, k - 1]
        else:
            got = _jit_eval(lambda: bit_decompose(lo, hi, width, banks))
        assert np.array_equal(np.asarray(got), np.asarray(want)), (
            f"{'msb' if msb_only else 'bit_decompose'}{shape} diverged"
        )


def _check_bits(width: int) -> None:
    _check_bits_common(width, msb_only=False)


def _check_msb(width: int) -> None:
    _check_bits_common(width, msb_only=True)


def _check_horner(width: int) -> None:
    from ..parallel import spmd, spmd_math as sm

    rng = _check_rng()
    f = 12 if width == 64 else 23
    coeffs = [1.0, 0.7, -0.21, 0.043]
    raws = [
        int(round(c * (1 << f))) % (1 << width) for c in reversed(coeffs)
    ]
    steps = len(raws) - 1
    for shape in ((4, 5),):
        x_lo = jnp.asarray(rng.integers(
            0, 1 << 64, size=(3, 2) + shape, dtype=np.uint64
        ))
        x_hi = (
            jnp.asarray(rng.integers(
                0, 1 << 64, size=(3, 2) + shape, dtype=np.uint64
            ))
            if width == 128 else None
        )
        zb = _rand_ring(rng, (steps, 3) + shape, width)
        td = _rand_ring(rng, (steps, 5) + shape, width)
        # lax twin: the unfused polynomial ladder fed the same draws
        # through a replay session
        queue = []
        for st in range(steps):
            queue.append((
                zb[0][st], None if zb[1] is None else zb[1][st]
            ))
            for d in range(5):
                queue.append((
                    td[0][st, d], None if td[1] is None else td[1][st, d]
                ))
        x_rep = spmd.SpmdRep(x_lo, x_hi, width)
        want = _twin_eval(
            lambda: sm._horner_lax(
                sm._ReplaySession(queue), x_rep, raws, f
            )
        )
        (s0_lo, s0_hi), (s1_lo, s1_hi) = _jit_eval(lambda: horner(
            (x_lo[:, 0], None if x_hi is None else x_hi[:, 0]),
            (x_lo[:, 1], None if x_hi is None else x_hi[:, 1]),
            width, raws, f, zb, td, shape,
        ))
        got_lo = jnp.stack([s0_lo, s1_lo], axis=1)
        assert np.array_equal(np.asarray(got_lo), np.asarray(want.lo)), (
            f"horner{shape}: lo diverged"
        )
        if width == 128:
            got_hi = jnp.stack([s0_hi, s1_hi], axis=1)
            assert np.array_equal(
                np.asarray(got_hi), np.asarray(want.hi)
            ), f"horner{shape}: hi diverged"


def _check_dot(width: int) -> None:
    from ..dialects import ring
    from ..parallel import spmd

    rng = _check_rng()
    # the last row forces a multi-tile grid (2 m-tiles x 2 n-tiles) AND
    # host-side k segmentation (2 segments) on a small shape — the
    # tiled/segmented code paths the MXU shapes exercise, checked at
    # first-use cost
    for (m, k, n, plan) in (
        (4, 37, 3, None),
        (2, 300, 5, None),
        (10, 300, 130, (8, 128, 256)),
    ):
        x0 = _rand_ring(rng, (3, m, k), width)
        x1 = _rand_ring(rng, (3, m, k), width)
        y0 = _rand_ring(rng, (3, k, n), width)
        y1 = _rand_ring(rng, (3, k, n), width)
        ys = ring.add(*y0, *y1)
        def want_fn():
            va = spmd._dot_contract(*x0, *ys)
            vb = spmd._dot_contract(*x1, *y0)
            return ring.add(*va, *vb)

        want = _twin_eval(want_fn)
        got = _jit_eval(
            lambda: dot_cross_terms(x0, x1, y0, ys, width, tile_plan=plan)
        )
        _assert_bitwise(got, want, f"dot_cross_terms({m},{k},{n})")


_CHECKS: Dict[str, Callable[[int], None]] = {
    "ring_mul": _check_mul,
    "cross_terms_mul": _check_cross,
    "trunc_combine": _check_trunc,
    "bit_decompose": _check_bits,
    "msb": _check_msb,
    "horner": _check_horner,
    "dot_cross_terms": _check_dot,
}
