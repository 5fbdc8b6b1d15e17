"""Runtime values.

TPU-native re-design of the reference's value layer (``moose/src/host/mod.rs``,
``moose/src/replicated/mod.rs:74-77``, ``moose/src/additive/mod.rs:48``,
``moose/src/mirrored/mod.rs:47``).  All tensor payloads are JAX arrays and all
wrappers are registered as pytrees, so a whole interpreted computation can be
traced and compiled by XLA as a single program — this replaces the reference's
per-op tokio task graph (XLA schedules instead).

Ring representation (TPU has no native u128):
- ring64  -> one ``uint64`` array (XLA integer arithmetic wraps, which is
  exactly ring semantics),
- ring128 -> two-limb ``(hi, lo)`` ``uint64`` arrays.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import numpy as np

from . import dtypes as dt

# ---------------------------------------------------------------------------
# Host-placed values (single owner)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class HostUnit:
    plc: str

    def ty_name(self) -> str:
        return "Unit"


@dataclasses.dataclass
class HostString:
    value: str
    plc: str

    def ty_name(self) -> str:
        return "HostString"


@dataclasses.dataclass
class HostShape:
    """Shapes are runtime values in the IR (reference HostShape); under XLA
    they must be static, so we carry them as Python tuples (trace-time
    constants)."""

    value: tuple[int, ...]
    plc: str

    def ty_name(self) -> str:
        return "HostShape"


@dataclasses.dataclass
class HostSeed:
    """128-bit seed (reference HostSeed).  Carried as a uint32[4] array so
    seed derivation stays on-device and jittable.

    ``origin`` is provenance metadata for the keystream draw oracle: the
    ``(key origin, sync_key)`` pair the seed was derived from (set by the
    sessions; None for seeds minted outside instrumented paths).  It never
    influences execution."""

    value: Any  # uint32[4]
    plc: str
    origin: Any = None

    def ty_name(self) -> str:
        return "HostSeed"


@dataclasses.dataclass
class HostPrfKey:
    """PRF key words (uint32[4]).  ``origin`` is draw-oracle provenance —
    the PrfKeyGen op name or session key index that minted the key; it
    never influences execution."""

    value: Any  # uint32[4]
    plc: str
    origin: Any = None

    def ty_name(self) -> str:
        return "HostPrfKey"


@dataclasses.dataclass
class HostTensor:
    """Plaintext float/int/bool tensor owned by one host."""

    value: Any  # jnp array
    plc: str
    dtype: dt.DType

    def ty_name(self) -> str:
        mapping = {
            "float32": "HostFloat32Tensor",
            "float64": "HostFloat64Tensor",
            "int32": "HostInt32Tensor",
            "int64": "HostInt64Tensor",
            "uint32": "HostUint32Tensor",
            "uint64": "HostUint64Tensor",
            "bool": "HostBitTensor",
        }
        return mapping[self.dtype.name]

    @property
    def shape(self):
        return self.value.shape


@dataclasses.dataclass
class Float64Halves:
    """A float64 tensor on its way from a TPU to the host, as the two
    float32 arrays the chip holds it in: the value is ``float64(hi) +
    float64(lo)``.  ``carried`` is the device's own word (a bool
    scalar) that the halves hold every element's bits; where it is
    false the float64 itself, ``whole``, is fetched instead.  Only the
    staging of a result for the user makes one
    (``execution/interpreter.py``, ``_stage_user_value``) and only
    :func:`to_numpy` reads one; no dialect op takes it."""

    hi: Any  # float32
    lo: Any  # float32, same shape
    carried: Any  # bool scalar
    whole: HostTensor

    @property
    def joined(self) -> bool:
        """Whether :func:`to_numpy` joins the halves (reads ``carried``:
        blocks until the device has it)."""
        return bool(np.asarray(self.carried))


@dataclasses.dataclass
class HostBitTensor:
    """A tensor of bits, one bit per ``uint8`` lane (the reference bit-packs
    into u8 words, ``host/bitarray.rs:10``; on TPU we keep one-bit-per-lane
    for vectorization and pack only at (de)serialization time)."""

    value: Any  # uint8 array of 0/1
    plc: str

    def ty_name(self) -> str:
        return "HostBitTensor"

    @property
    def shape(self):
        return self.value.shape


@dataclasses.dataclass
class HostRingTensor:
    """Element of Z_{2^64} or Z_{2^128} (reference HostRingTensor).

    ``lo`` is always a uint64 array; ``hi`` is present iff width == 128.
    """

    lo: Any
    hi: Optional[Any]
    width: int  # 64 or 128
    plc: str

    def ty_name(self) -> str:
        return f"HostRing{self.width}Tensor"

    @property
    def shape(self):
        return self.lo.shape


@dataclasses.dataclass
class HostFixedTensor:
    """Fixed-point tensor = ring tensor + precision metadata
    (reference host/mod.rs:352)."""

    tensor: HostRingTensor
    integral_precision: int
    fractional_precision: int

    @property
    def plc(self) -> str:
        return self.tensor.plc

    def ty_name(self) -> str:
        return f"HostFixed{self.tensor.width}Tensor"


# ---------------------------------------------------------------------------
# Replicated (3-party) values
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class RepTensor:
    """Replicated secret sharing: x = x0 + x1 + x2, party i holds
    (x_i, x_{i+1}) (reference replicated/mod.rs:74-77).

    ``shares[i]`` is the pair held by party i; each element is a
    HostRingTensor or HostBitTensor placed on owner i.
    """

    shares: tuple  # ((x00, x10), (x11, x21), (x22, x02))
    plc: str  # replicated placement name

    def ty_name(self) -> str:
        inner = self.shares[0][0]
        if isinstance(inner, HostBitTensor):
            return "ReplicatedBitTensor"
        return f"ReplicatedRing{inner.width}Tensor"

    @property
    def shape(self):
        return self.shares[0][0].shape


@dataclasses.dataclass
class RepFixedTensor:
    tensor: RepTensor
    integral_precision: int
    fractional_precision: int

    @property
    def plc(self) -> str:
        return self.tensor.plc

    def ty_name(self) -> str:
        inner = self.tensor.shares[0][0]
        return f"ReplicatedFixed{inner.width}Tensor"


@dataclasses.dataclass
class RepSetup:
    """Pairwise PRF keys: keys[i] = (k_i, k_{i+1}) held by party i
    (reference replicated/setup.rs:5-8)."""

    keys: tuple  # ((k00,k10),(k11,k21),(k22,k02)) of HostPrfKey
    plc: str


@dataclasses.dataclass
class RepBitArray:
    """N-bit bit-decomposition: a replicated bit tensor with a leading bit
    axis of static length (reference RepBitArray)."""

    tensor: RepTensor  # of HostBitTensor shares, leading axis = bits
    num_bits: int

    @property
    def plc(self) -> str:
        return self.tensor.plc

    def ty_name(self) -> str:
        return f"ReplicatedBitArray{self.num_bits}"


@dataclasses.dataclass
class AdtTensor:
    """2-party additive sharing x = x0 + x1 (reference additive/mod.rs:48)."""

    shares: tuple  # (x0, x1) HostRingTensors
    plc: str

    def ty_name(self) -> str:
        return f"AdditiveRing{self.shares[0].width}Tensor"


@dataclasses.dataclass
class Mir3Tensor:
    """Public value mirrored on 3 hosts (reference mirrored/mod.rs:47)."""

    values: tuple  # (v0, v1, v2)
    plc: str

    def ty_name(self) -> str:
        inner = self.values[0]
        if isinstance(inner, HostRingTensor):
            return f"Mirrored3Ring{inner.width}Tensor"
        if isinstance(inner, HostBitTensor):
            return "Mirrored3BitTensor"
        return f"Mirrored3{inner.dtype.name.capitalize()}Tensor"


@dataclasses.dataclass
class Mir3FixedTensor:
    tensor: Mir3Tensor
    integral_precision: int
    fractional_precision: int

    @property
    def plc(self) -> str:
        return self.tensor.plc

    def ty_name(self) -> str:
        inner = self.tensor.values[0]
        return f"Mirrored3Fixed{inner.width}Tensor"


# ---------------------------------------------------------------------------
# AES / encrypted values (reference encrypted/mod.rs)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class HostAesKey:
    bits: Any  # HostBitTensor with leading axis 128
    plc: str

    def ty_name(self) -> str:
        return "HostAesKey"


@dataclasses.dataclass
class RepAesKey:
    bits: RepBitArray

    @property
    def plc(self) -> str:
        return self.bits.plc

    def ty_name(self) -> str:
        return "ReplicatedAesKey"


@dataclasses.dataclass
class AesTensor:
    """AES-128-GCM-style ciphertext of a fixed-point tensor: per-element
    96-bit nonce + ciphertext bits (reference host/mod.rs AesTensorT)."""

    nonce_bits: Any  # HostBitTensor [..., 96]
    cipher_bits: Any  # HostBitTensor [..., 128]
    plc: str

    def ty_name(self) -> str:
        return "AesTensor"


# ---------------------------------------------------------------------------
# Pytree registration: placement/meta is static aux data, arrays are leaves.
# ---------------------------------------------------------------------------


def _register(cls, array_fields, static_fields):
    def flatten(v):
        return (
            tuple(getattr(v, f) for f in array_fields),
            tuple(getattr(v, f) for f in static_fields),
        )

    def unflatten(aux, children):
        kwargs = dict(zip(array_fields, children))
        kwargs.update(dict(zip(static_fields, aux)))
        return cls(**kwargs)

    jax.tree_util.register_pytree_node(cls, flatten, unflatten)


_register(HostUnit, (), ("plc",))
_register(HostString, (), ("value", "plc"))
_register(HostShape, (), ("value", "plc"))
_register(HostSeed, ("value",), ("plc",))
_register(HostPrfKey, ("value",), ("plc",))
_register(HostTensor, ("value",), ("plc", "dtype"))
# ``whole`` is no leaf: nothing waits for the float64 or starts its copy
# to the host unless the halves turn out not to carry it
_register(Float64Halves, ("hi", "lo", "carried"), ("whole",))
_register(HostBitTensor, ("value",), ("plc",))
_register(
    HostRingTensor, ("lo", "hi"), ("width", "plc")
)
_register(
    HostFixedTensor,
    ("tensor",),
    ("integral_precision", "fractional_precision"),
)
_register(RepTensor, ("shares",), ("plc",))
_register(
    RepFixedTensor,
    ("tensor",),
    ("integral_precision", "fractional_precision"),
)
_register(RepSetup, ("keys",), ("plc",))
_register(RepBitArray, ("tensor",), ("num_bits",))
_register(AdtTensor, ("shares",), ("plc",))
_register(Mir3Tensor, ("values",), ("plc",))
_register(
    Mir3FixedTensor,
    ("tensor",),
    ("integral_precision", "fractional_precision"),
)
_register(HostAesKey, ("bits",), ("plc",))
_register(RepAesKey, ("bits",), ())
_register(AesTensor, ("nonce_bits", "cipher_bits"), ("plc",))


# ---------------------------------------------------------------------------
# numpy conversion helpers (the Python<->runtime boundary)
# ---------------------------------------------------------------------------


def host_tensor_from_numpy(arr: np.ndarray, plc: str) -> HostTensor | HostBitTensor:
    arr = np.asarray(arr)
    if arr.dtype == np.bool_:
        return HostBitTensor(arr.astype(np.uint8), plc)
    return HostTensor(arr, plc, dt.from_numpy(arr.dtype))


def ring_to_limbs(value: HostRingTensor):
    """Persistence form of a ring tensor: uint64 limb planes with a
    leading limb axis — ``(1, *shape)`` for ring64, ``(2, *shape)``
    (lo, hi) for ring128.  Unlike :func:`to_numpy`'s object-int form
    this round-trips through ``.npy`` storage losslessly, which is what
    secret-shared checkpoints (``SaveShares``/``LoadShares``) need."""
    import jax.numpy as jnp

    limbs = [value.lo] if value.width == 64 else [value.lo, value.hi]
    return jnp.stack([jnp.asarray(l).astype(jnp.uint64) for l in limbs])


def limbs_to_ring(arr, width: int, plc: str) -> HostRingTensor:
    """Inverse of :func:`ring_to_limbs`: lift a ``(n_limbs, *shape)``
    uint64 array back into a :class:`HostRingTensor` of ``width``."""
    import jax.numpy as jnp

    want = 1 if width == 64 else 2
    arr = jnp.asarray(arr)
    if arr.ndim < 1 or arr.shape[0] != want:
        raise ValueError(
            f"ring{width} limb array needs leading axis {want}, found "
            f"shape {tuple(arr.shape)}"
        )
    arr = arr.astype(jnp.uint64)
    return HostRingTensor(
        arr[0], arr[1] if width == 128 else None, width, plc
    )


def to_numpy(value) -> np.ndarray:
    """Convert a host-level runtime value back to numpy for the user."""
    if isinstance(value, HostTensor):
        return np.asarray(value.value)
    if isinstance(value, Float64Halves):
        if not value.joined:
            return np.asarray(value.whole.value)
        # one vectorised pass into the array the caller receives: the
        # float32 reads are widened in the ufunc's buffer, so no float64
        # temporary of the result's size exists beside it
        return np.add(
            np.asarray(value.hi), np.asarray(value.lo), dtype=np.float64
        )
    if isinstance(value, HostBitTensor):
        return np.asarray(value.value).astype(bool)
    if isinstance(value, HostRingTensor):
        if value.width == 64:
            return np.asarray(value.lo).astype(np.uint64)
        hi = np.asarray(value.hi).astype(object)
        lo = np.asarray(value.lo).astype(object)
        return (hi << 64) + lo
    if isinstance(value, HostShape):
        return np.asarray(value.value, dtype=np.int64)
    if isinstance(value, HostString):
        return value.value
    if isinstance(value, HostUnit):
        return None
    raise TypeError(f"cannot convert {type(value).__name__} to numpy")
