"""moose_tpu: a TPU-native secure multi-party computation framework.

A from-scratch re-design of the capabilities of the reference Moose framework
(compiler + runtime + Python eDSL for placement-pinned dataflow computations
with 3-party replicated secret sharing over Z_{2^64}/Z_{2^128}) built on
JAX/XLA: host kernels are jnp programs, the 3 parties ride a named mesh axis
with ICI collectives, and whole computations compile to single fused XLA
programs instead of per-op task graphs.

The public surface mirrors ``pymoose`` (reference pymoose/pymoose/__init__.py)
so existing ``@pm.computation`` graphs run unchanged.
"""

import jax

# Ring arithmetic needs 64-bit lanes; must be set before any jnp usage.
jax.config.update("jax_enable_x64", True)

from . import dtypes  # noqa: E402
from .dtypes import (  # noqa: E402
    bool_,
    fixed,
    fixed64,
    fixed128,
    float32,
    float64,
    int32,
    int64,
    uint32,
    uint64,
)
from .computation import (  # noqa: E402
    AdditivePlacement,
    Computation,
    HostPlacement,
    Mirrored3Placement,
    Operation,
    ReplicatedPlacement,
)
from .vtypes import (  # noqa: E402
    AesKeyType,
    AesTensorType,
    BytesType,
    FloatType,
    IntType,
    ShapeType,
    StringType,
    TensorType,
    UnitType,
)
from .edsl.base import (  # noqa: E402
    Argument,
    abs,
    add,
    add_n,
    argmax,
    atleast_2d,
    avg_pool2d,
    cast,
    computation,
    concatenate,
    constant,
    conv2d,
    decrypt,
    div,
    dot,
    equal,
    exp,
    expand_dims,
    gather,
    get_current_placement,
    get_current_runtime,
    greater,
    host_placement,
    identity,
    index_axis,
    inverse,
    less,
    load,
    load_shares,
    log,
    log2,
    logical_and,
    logical_or,
    logical_xor,
    max_pool2d,
    maximum,
    mean,
    mirrored_placement,
    mul,
    mux,
    neg,
    ones,
    output,
    relu,
    replicated_placement,
    reshape,
    save,
    save_shares,
    select,
    set_current_runtime,
    shape,
    sigmoid,
    sliced,
    softmax,
    sqrt,
    square,
    squeeze,
    strided_slice,
    sub,
    sum,
    transpose,
    zeros,
)

__version__ = "0.1.0"


def __getattr__(name):
    # Lazy imports of heavier subsystems to keep `import moose_tpu` light.
    lazy = {
        "LocalMooseRuntime": ("runtime", "LocalMooseRuntime"),
        "GrpcMooseRuntime": ("runtime", "GrpcMooseRuntime"),
        "runtime": ("runtime", None),
        "predictors": ("predictors", None),
        "elk_compiler": ("elk_compiler", None),
        "parallel": ("parallel", None),
        "telemetry": ("telemetry", None),
        "metrics": ("metrics", None),
        "flight": ("flight", None),
    }
    if name in lazy:
        import importlib

        mod_name, attr = lazy[name]
        try:
            mod = importlib.import_module(f".{mod_name}", __name__)
        except ModuleNotFoundError as e:
            # keep hasattr()-style feature detection working
            raise AttributeError(
                f"module 'moose_tpu' has no attribute {name!r} ({e})"
            ) from e
        return mod if attr is None else getattr(mod, attr)
    raise AttributeError(f"module 'moose_tpu' has no attribute {name!r}")
