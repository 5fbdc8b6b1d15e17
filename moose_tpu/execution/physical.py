"""Physical executor: runs a *lowered* (host-level) computation.

Counterpart of the reference's per-worker executor over compiled physical
graphs (``moose/src/execution/asynchronous.rs:456-529``), re-designed for
XLA: in local mode the whole host-op graph is traced through the eager
session under ``jax.jit`` into one fused program (PRF keys enter as runtime
arguments so the compiled program is reusable with fresh randomness); in
distributed mode (``identity=...``) the worker walks the same graph eagerly,
executing only its own ops, and Send/Receive hit the networking backend —
the exact role-filtering discipline of the reference
(execution/context.rs:60-74).
"""

from __future__ import annotations

import secrets
from typing import Any, Optional

import numpy as np

from .. import dtypes as dt
from ..computation import Computation
from ..dialects import host
from ..errors import (
    KernelError,
    MissingArgumentError,
    StorageError,
    UnimplementedError,
)
from ..values import (
    HostBitTensor,
    HostPrfKey,
    HostRingTensor,
    HostSeed,
    HostShape,
    HostString,
    HostTensor,
    HostUnit,
)
from .session import EagerSession


def _fresh_key_words(domain: str = "") -> np.ndarray:
    """Fresh 128-bit key words; under MOOSE_TPU_FIXED_KEYS (test-only,
    gated — see interpreter.master_key_words) derived from ``domain``
    (the key op's name) so lowered-plan evaluations are reproducible."""
    import os

    if os.environ.get("MOOSE_TPU_FIXED_KEYS"):
        from .interpreter import master_key_words

        return master_key_words(f"physical|{domain}")
    return np.frombuffer(secrets.token_bytes(16), dtype=np.uint32)


def _ring_width_of(ty_name: str) -> int:
    return 128 if "128" in ty_name else 64


def _sample_from_seed(sess, plc, shp, seed, ret_name: str, attrs):
    """Shared Sample/SampleSeeded dispatch: bit tensor vs bit-valued ring
    (max_value == 1) vs uniform ring draw."""
    if ret_name == "HostBitTensor":
        return sess.sample_bit_tensor_seeded(plc, shp, seed)
    width = _ring_width_of(ret_name)
    if attrs.get("max_value") == 1:
        return sess.sample_bits_seeded(plc, shp, seed, width)
    return sess.sample_uniform_seeded(plc, shp, seed, width)


def execute_kernel(sess: EagerSession, op, plc: str, args: list):
    """Execute one host-level operation with concrete values."""
    kind = op.kind
    A = op.attributes
    ret = op.signature.return_type

    if kind == "Identity":
        return sess.place(plc, args[0])
    if kind == "Constant":
        value = A["value"]
        if ret.name == "HostShape":
            return HostShape(tuple(int(d) for d in value), plc)
        if ret.name == "HostString":
            return HostString(value, plc)
        if ret.name.startswith("HostRing"):
            return sess.ring_constant(plc, value, _ring_width_of(ret.name))
        if ret.name == "HostBitTensor":
            import jax.numpy as jnp

            return HostBitTensor(
                jnp.asarray(np.asarray(value).astype(np.uint8)), plc
            )
        return sess.constant(plc, np.asarray(value), ret.dtype)
    if kind == "Fill":
        return sess.fill(plc, args[0], A["value"], ret.name)
    if kind == "Zeros":
        return sess.zeros(plc, args[0], ret.dtype or dt.float64)
    if kind == "Ones":
        return sess.ones(plc, args[0], ret.dtype or dt.float64)
    if kind == "PrfKeyGen":
        # normally handled by the plan (keys enter as runtime inputs so the
        # jitted program stays reusable); eager fallback for direct calls
        # (domain = op name so fixed-keys mode gives DISTINCT keys per op)
        import jax.numpy as jnp

        return HostPrfKey(
            jnp.asarray(_fresh_key_words(op.name)), plc, origin=op.name
        )
    if kind == "DeriveSeed":
        return sess.derive_seed(plc, args[0], A["sync_key"])
    if kind == "SampleSeeded":
        return _sample_from_seed(sess, plc, args[0], args[1], ret.name, A)
    if kind == "Sample":
        # eager/distributed fallback for unseeded draws; the plan-driven
        # path feeds the fresh seed through `keys` instead
        # (_run_physical_ops)
        import jax.numpy as jnp

        seed = HostSeed(
            jnp.asarray(_fresh_key_words(op.name)), plc,
            origin=(("fresh", op.name), None),
        )
        return _sample_from_seed(sess, plc, args[0], seed, ret.name, A)
    if kind == "Add":
        return sess.add(plc, args[0], args[1])
    if kind == "Sub":
        return sess.sub(plc, args[0], args[1])
    if kind == "Mul":
        return sess.mul(plc, args[0], args[1])
    if kind == "Div":
        return sess.div(plc, args[0], args[1])
    if kind == "Dot":
        return sess.dot(plc, args[0], args[1])
    if kind == "Conv2D":
        return sess.conv2d(
            plc, args[0], args[1],
            tuple(A.get("strides", (1, 1))), A.get("padding", "VALID"),
        )
    if kind == "Im2Col":
        return sess.im2col(
            plc, args[0], A["kh"], A["kw"],
            tuple(A.get("strides", (1, 1))), A.get("padding", "VALID"),
        )
    if kind in ("AvgPool2D", "MaxPool2D"):
        method = (
            sess.avg_pool2d if kind == "AvgPool2D" else sess.max_pool2d
        )
        strides = A.get("strides")
        return method(
            plc, args[0], tuple(A["pool_size"]),
            tuple(strides) if strides is not None else None,
            A.get("padding", "VALID"),
        )
    if kind == "And":
        return sess.and_(plc, args[0], args[1])
    if kind == "Or":
        return sess.or_(plc, args[0], args[1])
    if kind == "Xor":
        return sess.xor(plc, args[0], args[1])
    if kind == "Neg":
        if isinstance(args[0], HostBitTensor):
            return sess.bit_neg(plc, args[0])
        return sess.neg(plc, args[0])
    if kind == "Sum":
        return sess.sum(plc, args[0], A.get("axis"))
    if kind == "Mean":
        return sess.mean(plc, args[0], A.get("axis"))
    if kind == "Shl":
        return sess.shl(plc, args[0], A["amount"])
    if kind == "Shr":
        if A.get("arithmetic"):
            return sess.shr_arith(plc, args[0], A["amount"])
        return sess.shr(plc, args[0], A["amount"])
    if kind == "BitExtract":
        return sess.bit_extract(plc, args[0], A["bit_idx"])
    if kind == "RingInject":
        return sess.ring_inject(
            plc, args[0], A["bit_idx"], _ring_width_of(ret.name)
        )
    if kind == "BitDecompose":
        return sess.decompose_bits(plc, args[0])
    if kind == "BitCompose":
        return sess.compose_bits(plc, args[0], _ring_width_of(ret.name))
    if kind == "RingFixedpointEncode":
        return sess.ring_fixedpoint_encode(
            plc, args[0], A["scaling_exp"], _ring_width_of(ret.name)
        )
    if kind == "RingFixedpointDecode":
        return sess.ring_fixedpoint_decode(
            plc, args[0], A["scaling_exp"], ret.dtype or dt.float64
        )
    if kind == "RingFixedpointMean":
        return sess.ring_fixedpoint_mean(
            plc, args[0], A.get("axis"), A["scaling_exp"]
        )
    if kind == "Cast":
        x = args[0]
        target = A["dtype"]
        if isinstance(x, HostRingTensor):
            x = sess.lift_ring_lo(plc, x, dt.uint64)
            if target.name == "uint64":
                return x
        return sess.cast(plc, x, target)
    if kind == "Exp":
        return sess.exp(plc, args[0])
    if kind == "Log":
        return sess.log(plc, args[0])
    if kind == "Log2":
        return sess.log2(plc, args[0])
    if kind == "Sqrt":
        return sess.sqrt(plc, args[0])
    if kind == "Sigmoid":
        return sess.sigmoid(plc, args[0])
    if kind == "Relu":
        return sess.relu(plc, args[0])
    if kind == "Abs":
        return sess.abs(plc, args[0])
    if kind == "Sign":
        return sess.sign(plc, args[0])
    if kind == "Pow2":
        return sess.pow2(plc, args[0])
    if kind == "Softmax":
        return sess.softmax(plc, args[0], A["axis"])
    if kind == "Argmax":
        return sess.argmax(plc, args[0], A["axis"])
    if kind == "Maximum":
        return sess.maximum(plc, args)
    if kind == "Inverse":
        return sess.inverse(plc, args[0])
    if kind == "Less":
        return sess.less(plc, args[0], args[1])
    if kind == "Greater":
        return sess.greater(plc, args[0], args[1])
    if kind == "Equal":
        return sess.equal(plc, args[0], args[1])
    if kind == "Mux":
        return sess.mux(plc, args[0], args[1], args[2])
    if kind == "Select":
        return sess.select(plc, args[0], A["axis"], args[1])
    if kind == "Reshape":
        return sess.reshape(plc, args[0], args[1])
    if kind == "Broadcast":
        return sess.broadcast(plc, args[0], args[1])
    if kind == "Slice":
        spec = A.get("slices", A.get("slice_spec"))
        if spec is not None:
            slices = tuple(
                Ellipsis
                if s == "..."
                else (slice(*s) if isinstance(s, (tuple, list)) else s)
                for s in spec
            )
            return sess.strided_slice(plc, args[0], slices)
        return sess.slice(plc, args[0], A["begin"], A["end"])
    if kind == "ExpandDims":
        return sess.expand_dims(plc, args[0], A["axis"])
    if kind == "Squeeze":
        return sess.squeeze(plc, args[0], A.get("axis"))
    if kind == "Concat":
        return sess.concat(plc, args, A.get("axis", 0))
    if kind == "IndexAxis":
        return sess.index_axis(plc, args[0], A["axis"], A["index"])
    if kind == "Transpose":
        return sess.transpose(plc, args[0], A.get("axes"))
    if kind == "Diag":
        return sess.diag(plc, args[0])
    if kind == "ShlDim":
        return sess.shl_dim(plc, args[0], A["amount"], A["bit_length"])
    if kind == "AtLeast2D":
        return sess.at_least_2d(plc, args[0], A.get("to_column_vector", False))
    if kind == "Shape":
        return sess.shape(plc, args[0])
    if kind == "AddN":
        # variadic sum (reference AddNOp, computation.rs Signature::variadic)
        out = args[0]
        for a in args[1:]:
            out = sess.add(plc, out, a)
        return out
    raise UnimplementedError(f"physical op {kind} ({op.name})")


_DYNAMIC_SHAPE_KINDS = frozenset({"Select"})


def _recv_sources(comp: Computation, order) -> dict:
    """Map each Receive op to the env name of its Send's input: in-process
    execution needs no rendezvous store — the received value IS the sent
    value (and expressing it as a dataflow edge lets the segmented
    executor carry it across segment boundaries like any other value)."""
    send_of: dict[str, str] = {}
    for n in order:
        op = comp.operations[n]
        if op.kind == "Send":
            send_of[op.attributes["rendezvous_key"]] = op.inputs[0]
    out = {}
    for n in order:
        op = comp.operations[n]
        if op.kind == "Receive":
            out[n] = send_of[op.attributes["rendezvous_key"]]
    return out


def _run_physical_ops(sess, comp, names, static_env, env, outputs, saves,
                      keys, dyn, recv_src, trace_ops=False,
                      fault_kinds=frozenset()):
    """Execute host-level ops in order against ``env`` — shared by the
    whole-graph core and the per-segment cores.  ``fault_kinds``
    (self-check jit candidates only) injects a synthetic divergence into
    ops of the listed kinds — see ``interpreter._fault_kinds``."""
    import jax
    import jax.numpy as jnp

    from .. import telemetry
    from .interpreter import _fault_perturb, _lift_array

    for n in names:
        op = comp.operations[n]
        plc = comp.placement_of(op).name
        if n in env:
            continue
        if op.kind == "Send":
            env[n] = HostUnit(plc)
            continue
        if op.kind == "Receive":
            env[n] = host.place(env[recv_src[n]], plc)
            continue
        if op.kind == "PrfKeyGen":
            env[n] = HostPrfKey(jnp.asarray(keys[n]), plc, origin=n)
            continue
        if op.kind == "Sample":
            # unseeded draw (reference SampleOp): fresh 128-bit seed per
            # evaluation, fed like PrfKeyGen keys so the jitted program
            # stays reusable
            env[n] = _sample_from_seed(
                sess, plc, env[op.inputs[0]],
                HostSeed(jnp.asarray(keys[n]), plc,
                         origin=(("fresh", n), None)),
                op.signature.return_type.name, op.attributes,
            )
            continue
        if op.kind in ("Input", "Load"):
            env[n] = _lift_array(dyn[n], op, plc)
            continue
        if op.kind == "Save":
            key = env[op.inputs[0]]
            if not isinstance(key, HostString):
                raise KernelError(
                    f"Save {n}: key must be a string, found "
                    f"{type(key).__name__}"
                )
            saves[(plc, key.value)] = env[op.inputs[1]]
            env[n] = HostUnit(plc)
            continue
        if op.kind == "Output":
            value = env[op.inputs[0]]
            env[n] = value
            # keyed by Output tag like the reference's executor
            # (execution/asynchronous.rs:623); op name when untagged
            outputs[op.attributes.get("tag", n)] = value
            continue
        args = [env[i] for i in op.inputs]
        if trace_ops:
            # block inside the span: async dispatch would otherwise
            # misattribute device time (see interpreter.build_plan)
            with telemetry.span(f"op:{op.kind}"):
                env[n] = jax.block_until_ready(
                    execute_kernel(sess, op, plc, args)
                )
        else:
            env[n] = execute_kernel(sess, op, plc, args)
        if fault_kinds and op.kind in fault_kinds:
            env[n] = _fault_perturb(env[n])


def _build_plan(comp: Computation, arguments: dict, use_jit: bool,
                segment_limit=None, jit_segments: bool = True,
                fault_kinds=frozenset()):
    """Build (and jit) the execution closure for one (computation,
    binding) pair; cached by PhysicalInterpreter across calls."""
    import jax

    order = comp.toposort_names()
    if any(comp.operations[n].kind in _DYNAMIC_SHAPE_KINDS for n in order):
        use_jit = False

    key_ops = [
        n for n in order
        if comp.operations[n].kind in ("PrfKeyGen", "Sample")
    ]
    dyn_names: list[str] = []
    static_env: dict[str, Any] = {}
    for n in order:
        op = comp.operations[n]
        plc = comp.placement_of(op).name
        if op.kind == "Input":
            val = arguments.get(n)
            if val is None:
                raise MissingArgumentError(f"missing argument {n!r}")
            if isinstance(val, str):
                static_env[n] = HostString(val, plc)
            else:
                dyn_names.append(n)
        elif op.kind == "Load":
            dyn_names.append(n)

    import weakref

    from .. import telemetry

    comp_ref = weakref.ref(comp)
    recv_src = _recv_sources(comp, order)
    # per-op spans in eager mode only (see interpreter.build_plan)
    trace_ops = telemetry.trace_ops_enabled() and not use_jit

    from .interpreter import _segment_limit

    limit = segment_limit if segment_limit is not None else _segment_limit()
    if use_jit and len(order) > limit:
        fn = _build_segmented_physical(
            comp_ref, order, static_env, dyn_names, key_ops, recv_src,
            limit, jit_segments, fault_kinds,
        )
        return order, key_ops, dyn_names, static_env, fn

    def core(keys: dict, dyn: dict):
        comp = comp_ref()
        if comp is None:  # pragma: no cover - defensive
            raise KernelError("computation was garbage-collected")
        sess = EagerSession()
        env: dict[str, Any] = dict(static_env)
        outputs: dict[str, Any] = {}
        saves: dict[tuple, Any] = {}
        _run_physical_ops(
            sess, comp, order, static_env, env, outputs, saves, keys,
            dyn, recv_src, trace_ops, fault_kinds,
        )
        return outputs, saves

    fn = jax.jit(core) if (use_jit and jit_segments) else core
    return order, key_ops, dyn_names, static_env, fn


def _build_segmented_physical(comp_ref, order, static_env, dyn_names,
                              key_ops, recv_src, limit=None,
                              jit_segments: bool = True,
                              fault_kinds=frozenset()):
    """Lowered-graph segmentation over the SHARED orchestrator
    (interpreter.build_segmented_runner).  Receive ops read their Send's
    input through ``recv_src``, so cross-segment transfers are ordinary
    boundary values; each segment receives only its own PRF keys."""
    from .interpreter import build_segmented_runner

    comp = comp_ref()

    def effective_inputs(n):
        op = comp.operations[n]
        if op.kind == "Receive":
            return [recv_src[op.name]]
        return op.inputs

    key_set = set(key_ops)

    def seg_exec(si, names, keys, dyn, env, outputs, saves):
        comp = comp_ref()
        if comp is None:  # pragma: no cover - defensive
            raise KernelError("computation was garbage-collected")
        sess = EagerSession()
        _run_physical_ops(
            sess, comp, names, static_env, env, outputs, saves,
            keys, dyn, recv_src, False, fault_kinds,
        )

    # per-segment key narrowing needs the chunking; compute it once and
    # hand the same result to the orchestrator
    from .interpreter import _segment_limit, plan_segments

    segmentation = plan_segments(
        order, static_env, effective_inputs,
        limit if limit is not None else _segment_limit(),
    )
    keys_of = [
        [n for n in names if n in key_set] for names in segmentation[0]
    ]

    return build_segmented_runner(
        order, static_env, dyn_names, effective_inputs, limit,
        jit_segments, seg_exec,
        lambda keys, si: {n: keys[n] for n in keys_of[si]},
        segmentation=segmentation,
    )


def _physical_plan_builder(comp, arguments, use_jit, segment_limit,
                           jit_segments, fault_kinds=frozenset()):
    """builder hook for the shared ``_SelfCheckRunner``: physical plans
    take every PRF key as a runtime input and bake sync keys as graph
    attributes, so eager and jitted execution from the same ``keys``
    dict must be bit-identical (no nonce pinning)."""
    plan = _build_plan(
        comp, arguments, use_jit, segment_limit=segment_limit,
        jit_segments=jit_segments, fault_kinds=fault_kinds,
    )
    return plan, plan[4]


# host-boundary / trivial kinds the per-op rung never jit-wraps: there
# is nothing to fuse and nothing the miscompile class can touch
_PER_OP_EAGER_KINDS = frozenset({
    "Input", "Load", "Save", "Output", "Send", "Receive", "PrfKeyGen",
    "Constant", "Identity",
})


def _physical_per_op_builder(comp, arguments, eager_plan, fault_kinds,
                             nonce_seed, pinned=()):
    """per-op-rung builder hook for lowered plans (the shared
    ``_SelfCheckRunner``'s ``per_op_builder``): ops take their PRF keys
    as runtime inputs — no nonce pinning needed — and each Receive reads
    its Send's input as an ordinary dataflow edge, so per-op programs
    compose exactly like segments do."""
    import weakref

    from .interpreter import _per_op_limit, _PerOpPlan, _SelfCheckBase

    order, key_ops, dyn_names, static_env, _ = eager_plan
    limit = _per_op_limit()
    if limit <= 0:
        return None
    seg_size = 1
    if len(order) > limit:
        # Too many ops for one-program-per-op validation (the cap bounds
        # how many tiny XLA programs the rung may compile).  Physical
        # plans are deterministic given their key dict, so the rung
        # still applies at coarser granularity: validate and pin
        # ``seg_size``-op CHUNKS — at least the ladder's finest segment
        # rung, grown until the chunk count fits the cap.  A bench-scale
        # lowered predictor (~10k host ops) lands here with only its
        # divergent chunks eager instead of the whole plan.
        finest = _SelfCheckBase.LADDER[-2]
        seg_size = max(finest, -(-len(order) // limit))
    comp_ref = weakref.ref(comp)
    recv_src = _recv_sources(comp, order)
    key_set = set(key_ops)

    def effective_inputs(n):
        op = comp.operations[n]
        if op.kind == "Receive":
            return [recv_src[op.name]]
        return op.inputs

    def seg_exec(si, names, keys, dyn, env, outputs, saves,
                 fault=frozenset()):
        comp = comp_ref()
        if comp is None:  # pragma: no cover - defensive
            raise KernelError("computation was garbage-collected")
        sess = EagerSession()
        _run_physical_ops(
            sess, comp, names, static_env, env, outputs, saves,
            keys, dyn, recv_src, False, fault,
        )

    always = {
        n for n in order
        if comp.operations[n].kind in _PER_OP_EAGER_KINDS
    }
    # chunking mirrors _PerOpPlan's own (consecutive seg_size slices of
    # the same order), so per-chunk key narrowing stays aligned
    keys_of = [
        [n for n in order[i:i + seg_size] if n in key_set]
        for i in range(0, len(order), seg_size)
    ]
    return _PerOpPlan(
        order, static_env, dyn_names, effective_inputs, seg_exec,
        fault_kinds,
        lambda keys, si: {n: keys[n] for n in keys_of[si]},
        always_eager=always, pinned=pinned, seg_size=seg_size,
    )


class PhysicalInterpreter:
    """Executes lowered computations with plan/jit caching (same weak-key
    discipline as the logical Interpreter)."""

    def __init__(self):
        import weakref

        self._cache = weakref.WeakKeyDictionary()
        # resolved plan shape of the most recent evaluate() — the
        # runtime lifts this into last_timings/last_plan
        self.last_plan_info: dict = {}

    def _plan_info(self, comp, use_jit, fn) -> dict:
        from .interpreter import _segment_limit, _SelfCheckRunner

        runner = getattr(fn, "__self__", None)
        if isinstance(runner, _SelfCheckRunner):
            return runner.plan_info()
        if not use_jit:
            mode = "eager"
        elif len(comp.operations) > _segment_limit():
            mode = "segmented"
        else:
            mode = "whole-graph"
        return {
            "plan_mode": mode, "pinned_ops": [], "plan_state": "static",
            "verdict": "none", "validations_run": 0,
        }

    def evaluate(
        self,
        comp: Computation,
        storage: dict,
        arguments: Optional[dict] = None,
        use_jit: bool = True,
    ) -> dict:
        from .interpreter import _selfcheck_runs, heavy_jit_gate

        arguments = arguments or {}
        gated = heavy_jit_gate(len(comp.operations), use_jit)
        selfcheck = use_jit and not gated and _selfcheck_runs() > 0
        use_jit = gated
        per_comp = self._cache.get(comp)
        if per_comp is None:
            per_comp = self._cache[comp] = {}
        from .interpreter import binding_cache_key

        cache_key = binding_cache_key(arguments, (use_jit, selfcheck))
        plan = per_comp.get(cache_key)
        if plan is None:
            if selfcheck:
                from .interpreter import _SelfCheckRunner

                runner = _SelfCheckRunner(
                    comp, arguments, _selfcheck_runs(),
                    builder=_physical_plan_builder, pin_nonces=False,
                    per_op_builder=_physical_per_op_builder,
                    plan_key="physical",
                )
                order, key_ops, dyn_names, static_env, _ = runner.eager_plan
                plan = (order, key_ops, dyn_names, static_env, runner.run)
            else:
                plan = _build_plan(comp, arguments, use_jit)
            per_comp[cache_key] = plan
        order, key_ops, dyn_names, static_env, fn = plan

        from .interpreter import _device_cache

        dyn = {}
        for n in dyn_names:
            op = comp.operations[n]
            plc = comp.placement_of(op).name
            if op.kind == "Input":
                val = arguments[n]
                if not isinstance(val, np.ndarray):
                    val = np.asarray(val)
                dyn[n] = _device_cache.put(val)
            else:  # Load
                key_op = comp.operations[op.inputs[0]]
                key = key_op.attributes.get("value")
                if key is None:
                    key_val = static_env.get(op.inputs[0])
                    if isinstance(key_val, HostString):
                        key = key_val.value
                store = storage.get(plc, {})
                if key not in store:
                    raise StorageError(
                        f"no value for key {key!r} in storage of {plc!r}"
                    )
                val = store[key]
                if not isinstance(val, np.ndarray):
                    val = np.asarray(val)
                dyn[n] = _device_cache.put(val)

        from .. import telemetry

        keys = {n: _fresh_key_words(n) for n in key_ops}
        with telemetry.span("execute", jit=use_jit) as sp:
            outputs, saves = fn(keys, dyn)
            # plan shape AFTER the run: a validating evaluation may have
            # promoted/demoted/pinned during the call
            info = self._plan_info(comp, use_jit, fn)
            info["ops"] = len(comp.operations)
            self.last_plan_info = info
            sp.attrs["plan_mode"] = info["plan_mode"]
            sp.attrs["pinned_ops"] = len(info["pinned_ops"])

        from .interpreter import (
            _fetch_user_value,
            _save_user_value,
            stage_results,
        )

        # what the conversions still compute on the device is dispatched,
        # and every device-to-host transfer started, before any
        # conversion blocks
        names, staged, staged_saves = stage_results(outputs, saves)
        for (plc_name, key), value in staged_saves.items():
            storage.setdefault(plc_name, {})[key] = _save_user_value(value)
        return {
            name: _fetch_user_value(value)
            for name, value in zip(names, staged)
        }


_DEFAULT = PhysicalInterpreter()


def execute_physical(
    comp: Computation,
    storage: dict,
    arguments: Optional[dict] = None,
    use_jit: bool = True,
) -> dict:
    """Execute a lowered computation locally (all hosts in one process,
    one fused XLA program)."""
    return _DEFAULT.evaluate(comp, storage, arguments, use_jit)
