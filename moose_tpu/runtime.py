"""User-facing runtimes.

API-compatible re-design of the reference's runtime wrappers
(``pymoose/pymoose/runtime.py`` + ``pymoose/src/bindings.rs``):

- ``LocalMooseRuntime``: several virtual hosts in one process with dict
  storage; the whole computation compiles to a single XLA program (the
  reference instead spins up one async executor per identity over an
  in-memory fake network).
- ``GrpcMooseRuntime``: drives remote workers over gRPC choreography (see
  ``moose_tpu/distributed/``).
"""

from __future__ import annotations

from typing import Dict, List, Optional

from .computation import Computation
from .dialects import logical as _logical_dialect
from .edsl import base as edsl_base
from .edsl import tracer
from .execution.interpreter import Interpreter


def _lift_computation(computation, arguments):
    if isinstance(computation, edsl_base.AbstractComputation):
        computation = tracer.trace(computation)
    if not isinstance(computation, Computation):
        raise ValueError(
            "`computation` must be an AbstractComputation or Computation, "
            f"found {type(computation)}"
        )
    return computation, dict(arguments or {})


class LocalMooseRuntime:
    def __init__(
        self,
        identities: List[str],
        storage_mapping: Optional[Dict[str, Dict]] = None,
        use_jit: Optional[bool] = None,
        layout: Optional[str] = None,
        mesh=None,
    ):
        import os

        if use_jit is None:
            use_jit = os.environ.get("MOOSE_TPU_JIT", "1") != "0"
        # execution layout for replicated protocol math:
        #   "auto" (default) — stacked-where-supported: graphs with
        #     replicated-placement ops route through the party-stacked
        #     backend when ``stacked_dialect.supports()`` admits them,
        #     and demote to per-host on rejection or validated-jit
        #     ladder exhaustion (the reference has ONE lowering
        #     pipeline; with the Pallas ring kernels closing the
        #     fixed(24,40) miscompile, stacked is the fast default
        #     rather than an opt-in — ROADMAP item 1);
        #   "per-host" — six separately-labelled per-party arrays
        #     (dialects/logical.py), the lowering-compatible layout;
        #   "stacked" — party-stacked SPMD arrays (dialects/stacked.py):
        #     one (party=3, slot=2, ...) array per sharing, reshares as
        #     rolls/collective-permutes, shardable over a device mesh
        #     (pass ``mesh=spmd.make_mesh(...)``).  Graphs with ops the
        #     stacked dialect does not cover fall back to per-host.
        if layout is None:
            layout = os.environ.get("MOOSE_TPU_LAYOUT", "auto")
        if layout not in ("auto", "per-host", "stacked"):
            raise ValueError(
                f"unknown layout {layout!r}; expected 'auto', "
                "'per-host' or 'stacked'"
            )
        self.layout = layout
        self._stacked = None
        if layout in ("auto", "stacked"):
            from .dialects.stacked import StackedDialect

            self._stacked = Interpreter(
                dialect=StackedDialect(mesh=mesh)
            )
        self.use_jit = use_jit
        storage_mapping = storage_mapping or {}
        for identity in storage_mapping:
            if identity not in identities:
                raise ValueError(
                    f"unknown identity {identity} in `storage_mapping`, "
                    f"must be one of {identities}"
                )
        self.identities = list(identities)
        # plain dicts are defensively copied; storage OBJECTS
        # (FilesystemStorage, training.CheckpointStore — anything with a
        # .load) are kept as-is, the runtime reads/writes through their
        # protocol
        self.storage = {
            identity: (
                store
                if hasattr(store := storage_mapping.get(identity, {}),
                           "load")
                else dict(store)
            )
            for identity in identities
        }
        import weakref

        self._interpreter = Interpreter()
        # traced-IR cache so repeated evaluations of the same
        # AbstractComputation reuse the compiled XLA executable; weak-keyed
        # on the object itself (an id() key could be reused after GC)
        self._trace_cache = weakref.WeakKeyDictionary()
        # (traced computation, passes, binding) -> lowered Computation;
        # holds compiled graphs strongly so the physical interpreter's
        # weak-keyed jit cache stays warm
        self._compiled_cache = weakref.WeakKeyDictionary()
        from .execution.physical import PhysicalInterpreter

        self._physical = PhysicalInterpreter()
        # serialized-computation memo for evaluate_compiled (see there)
        from collections import OrderedDict

        self._bin_cache: "OrderedDict[bytes, Computation]" = OrderedDict()
        # phase timings (micros) of the most recent evaluate_computation,
        # plus the resolved plan shape (`plan_mode`, `pinned_ops`)
        self.last_timings: Dict[str, int] = {}
        # resolved plan of the most recent evaluation: plan_mode
        # (eager / per-op / segmented / whole-graph), pinned_ops (names
        # the per-op rung eager-ized), run_errors (jit candidates that
        # failed to compile or run), layout (stacked / per-host)
        self.last_plan: Dict = {}
        self._last_plan_info = None
        # computations whose stacked execution raised a typed dispatch
        # rejection (TypeMismatchError): skip straight to per-host on
        # later evaluations instead of failing mid-run again
        self._stacked_rejected = weakref.WeakSet()

    def set_default(self):
        edsl_base.set_current_runtime(self)

    def evaluate_computation(
        self,
        computation,
        arguments=None,
        compiler_passes=None,
    ):
        from . import telemetry

        with telemetry.span("evaluate_computation") as root:
            result = self._evaluate_computation(
                computation, arguments, compiler_passes
            )
        # coarse phase timings in micros (Local analogue of the reference's
        # per-role elapsed-time map, pymoose/src/bindings.rs:320-328)
        self.last_timings = telemetry.phase_timings(root)
        self._surface_plan(root)
        return result

    def _surface_plan(self, root) -> None:
        """Surface the executors' resolved plan shape as the typed
        ``last_plan`` dict: which mode the validated-jit ladder settled
        on (eager / per-op / segmented / whole-graph), which ops the
        per-op rung pinned eager, and which layout ran."""
        from . import telemetry

        info = dict(self._last_plan_info or {})
        if "plan_mode" not in info:
            # fallback: read the `execute` span's attributes directly
            mode = telemetry.find_attr(root, "plan_mode")
            if mode is None:
                return
            info["plan_mode"] = mode
        # the typed plan surface: these four keys are always present
        # (plan_mode is guaranteed by the branch above).  last_timings
        # carries timings ONLY — the deprecated plan_mode/pinned_ops
        # aliases that rode there for one release are gone;
        # runtime.last_plan is the single plan surface.
        info["pinned_ops"] = list(info.get("pinned_ops", ()))
        # jit candidates the validated-jit ladder saw fail to compile or
        # run (it retried and demoted; a plan without a ladder raises)
        info["run_errors"] = list(info.get("run_errors", ()))
        info.setdefault("layout", None)
        self.last_plan = info

    def _evaluate_computation(
        self,
        computation,
        arguments=None,
        compiler_passes=None,
    ):
        from . import telemetry

        if isinstance(computation, edsl_base.AbstractComputation):
            traced = self._trace_cache.get(computation)
            if traced is None:
                with telemetry.span("trace"):
                    traced = tracer.trace(computation)
                self._trace_cache[computation] = traced
            computation = traced
        computation, arguments = _lift_computation(computation, arguments)
        use_jit = self.use_jit
        self._last_plan_info = None
        lowered = any(
            op.kind in self._LOWERED_KINDS
            for op in computation.operations.values()
        )
        if self._stacked is not None and compiler_passes is None:
            from .dialects import stacked as stacked_dialect
            from .errors import TypeMismatchError
            from .logger import get_logger

            if (
                not lowered
                and computation not in self._stacked_rejected
                and (
                    self.layout == "stacked"
                    or self._wants_stacked(computation)
                )
                and stacked_dialect.supports(computation)
            ):
                if self._stacked.plan_exhausted(
                    computation, arguments, use_jit=use_jit
                ):
                    # cross-layout demotion routing (VERDICT r5 weak
                    # #1): the stacked plan's validated-jit ladder
                    # exhausted — every rung including per-op diverged —
                    # so stacked execution would pay per-op eager
                    # dispatch forever.  The per-host auto-lowered
                    # segmented route runs the identical computation
                    # validated-exact, so route there instead of
                    # pinning the slow plan.
                    get_logger().warning(
                        "stacked plan exhausted its validated-jit "
                        "ladder; rerouting computation to the per-host "
                        "path"
                    )
                else:
                    try:
                        result = self._stacked.evaluate(
                            computation, self.storage, arguments,
                            use_jit=use_jit,
                        )
                    except TypeMismatchError as e:
                        # supports() admitted the graph but a kernel
                        # rejected a value shape mid-dispatch; nothing
                        # is written to storage before a plan returns,
                        # so retrying on the per-host path is safe
                        self._stacked_rejected.add(computation)
                        get_logger().warning(
                            "stacked backend rejected the computation "
                            "(%s); falling back to the per-host path", e
                        )
                    else:
                        self._last_plan_info = dict(
                            self._stacked.last_plan_info or {},
                            layout="stacked",
                        )
                        return result
            # fall through: lowered graphs, unsupported/rejected ops and
            # exhausted ladders keep the per-host path (documented
            # fallback)
        if compiler_passes is None and use_jit and not lowered:
            # (already-lowered graphs skip this: re-running the lowering
            # pipeline over host-level ring ops would fail — they go to
            # the physical executor below, whose segmented plans bound
            # compile size the same way)
            # protocol-heavy replicated graphs expand to tens of
            # thousands of host ops inside ONE logical op (a secure
            # softmax is ~11k), far past the point where a single XLA
            # program compiles in reasonable time.  Route them through
            # the explicit lowering pipeline: the lowered graph exposes
            # host-op granularity, which the physical executor compiles
            # as bounded segments (results are identical — the compiler
            # tests pin lowered-matches-eager)
            compiler_passes = self._auto_lower_passes(computation)
            # the TPU heavy-graph jit guard (DEVELOP.md "Known issue")
            # lives in the EXECUTORS (interpreter.heavy_jit_gate), so it
            # also covers evaluate_compiled and explicit compiler_passes
        if compiler_passes is not None:
            # explicit pass pipeline: lower to the host-level graph and run
            # it through the physical executor (the reference's LocalRuntime
            # always compiles; our default instead jit-fuses the logical
            # graph directly — same results, fewer layers).  Compiled
            # graphs are cached per (computation, passes, binding) so
            # repeated evaluations reuse the lowered graph and its XLA
            # executable.
            from .compilation import compile_computation
            from .compilation.lowering import arg_specs_from_arguments
            from .execution.interpreter import binding_cache_key

            specs = arg_specs_from_arguments(
                arguments, storage=self.storage, comp=computation
            )
            # callable passes have no stable identity (an id()-based key
            # could be reused after GC) — run them uncached
            cacheable = all(isinstance(p, str) for p in compiler_passes)
            compiled = None
            key = None
            if cacheable:
                per_comp = self._compiled_cache.get(computation)
                if per_comp is None:
                    per_comp = self._compiled_cache[computation] = {}
                # the key includes the storage-derived Load specs: a
                # storage write that changes a loaded value's shape must
                # miss the cache
                key = (
                    tuple(compiler_passes),
                    binding_cache_key(arguments, self.use_jit),
                    tuple(sorted(
                        (n, s) if isinstance(s, (str, int, float))
                        else (n, tuple(s[0]), str(s[1]))
                        for n, s in specs.items()
                    )),
                )
                compiled = per_comp.get(key)
            if compiled is None:
                with telemetry.span("compile"):
                    compiled = compile_computation(
                        computation, passes=compiler_passes, arg_specs=specs
                    )
                if cacheable:
                    per_comp[key] = compiled
            result = self._physical.evaluate(
                compiled, self.storage, arguments, use_jit=use_jit
            )
            self._last_plan_info = dict(
                self._physical.last_plan_info or {}, layout="per-host"
            )
            return result
        if lowered:
            # already-lowered host-level graphs (e.g. the reference's
            # *-compiled.moose artifacts parsed from textual) carry ring
            # ops the logical dialect doesn't know; execute them on the
            # physical interpreter like evaluate_compiled does
            result = self._physical.evaluate(
                computation, self.storage, arguments, use_jit=use_jit
            )
            self._last_plan_info = dict(
                self._physical.last_plan_info or {}, layout="per-host"
            )
            return result
        result = self._interpreter.evaluate(
            computation, self.storage, arguments, use_jit=use_jit
        )
        self._last_plan_info = dict(
            self._interpreter.last_plan_info or {}, layout="per-host"
        )
        return result

    @staticmethod
    def _wants_stacked(computation) -> bool:
        """Under layout='auto', only graphs with replicated-placement
        ops gain anything from the stacked backend — host-only graphs
        keep the per-host path (identical kernels, no conversion
        layer).  Explicit layout='stacked' skips this screen."""
        from .computation import ReplicatedPlacement

        return any(
            isinstance(
                computation.placements.get(op.placement_name),
                ReplicatedPlacement,
            )
            for op in computation.operations.values()
        )

    # Rough lowered-size weights for replicated-placement math ops
    # Rough lowered-size weights (host-op equivalents; see
    # logical.EXPANSION_WEIGHTS).  Used to decide WHETHER to lower;
    # shared with the stacked dialect's effective-size estimate for the
    # TPU heavy-jit gate.
    _EXPANSION_WEIGHTS = _logical_dialect.EXPANSION_WEIGHTS

    def _auto_lower_passes(self, computation):
        """DEFAULT_PASSES when the graph's estimated lowered size exceeds
        the jit segment limit, else None (stay on the fused logical
        path).  AES-typed graphs stay logical by choice: lowering CAN
        carry them (deployment needs it), but the decrypt circuit
        explodes to ~200k host ops, while the fused AES evaluator runs
        the same circuit as a handful of level-batched jax ops."""
        from .compilation import DEFAULT_PASSES
        from .computation import AES_TY_NAMES, ReplicatedPlacement
        from .execution.interpreter import _segment_limit

        limit = _segment_limit()
        total = 0
        for op in computation.operations.values():
            for ty in (op.signature.return_type, *op.signature.input_types):
                if ty is not None and ty.name in AES_TY_NAMES:
                    return None
            plc = computation.placements.get(op.placement_name)
            if isinstance(plc, ReplicatedPlacement):
                total += self._EXPANSION_WEIGHTS.get(op.kind, 20)
            else:
                total += 3
            if total > limit:
                return list(DEFAULT_PASSES)
        return None

    # op kinds that only a lowered (host-level) graph contains — the
    # positive marker for routing to the physical executor.  All-host
    # graphs WITHOUT these are plain logical computations and keep the
    # logical path (which knows AddN, Softmax, ...).
    _LOWERED_KINDS = frozenset({
        "RingFixedpointEncode", "RingFixedpointDecode",
        "RingFixedpointMean", "PrfKeyGen", "DeriveSeed", "SampleSeeded",
        "Sample", "Send", "Receive", "RingInject", "BitCompose",
        "BitDecompose", "BitExtract", "Shl", "Shr", "Fill", "ShlDim",
        "Im2Col",
    })

    def evaluate_compiled(self, comp_bin, arguments=None):
        from .serde import deserialize_computation

        # memoize deserialization strongly by the (hashable) bytes: the
        # Computation object keys the physical interpreter's weak plan
        # cache, so a fresh object per call would re-jit every time
        comp = self._bin_cache.get(comp_bin)
        if comp is None:
            comp = deserialize_computation(comp_bin)
            self._bin_cache[comp_bin] = comp
            while len(self._bin_cache) > 32:  # bounded LRU
                self._bin_cache.popitem(last=False)
        else:
            # refresh recency: a hot computation must not be evicted
            # ahead of cold later entries
            self._bin_cache.move_to_end(comp_bin)
        lowered = any(
            op.kind in self._LOWERED_KINDS
            for op in comp.operations.values()
        )
        if lowered:
            # already-compiled host-level graphs (elk_compiler output)
            # execute on the physical interpreter; the logical dialect
            # doesn't know host-level ring ops
            from . import telemetry

            with telemetry.span("evaluate_compiled") as root:
                result = self._physical.evaluate(
                    comp, self.storage, dict(arguments or {}),
                    use_jit=self.use_jit,
                )
            self.last_timings = telemetry.phase_timings(root)
            self._last_plan_info = dict(
                self._physical.last_plan_info or {}, layout="per-host"
            )
            self._surface_plan(root)
            return result
        return self.evaluate_computation(comp, arguments)

    def read_value_from_storage(self, identity: str, key: str):
        return self.storage[identity][key]

    def write_value_to_storage(self, identity: str, key: str, value):
        if identity not in self.storage:
            raise ValueError(f"unknown identity {identity}")
        self.storage[identity][key] = value
        return value


class GrpcMooseRuntime:
    """Client runtime for a cluster of gRPC workers (reference
    GrpcMooseRuntime, execution/grpc.rs:11-146)."""

    def __init__(self, identities: Dict, tls=None):
        # Masks for genuinely-distributed parties must come from a real PRF
        # (ADVICE r1: the rbg default is not cryptographic).
        from .dialects.ring import require_strong_prf

        require_strong_prf("GrpcMooseRuntime")
        self.identities = {
            (
                role.name
                if isinstance(role, edsl_base.HostPlacementExpression)
                else role
            ): addr
            for role, addr in identities.items()
        }
        try:
            from .distributed.client import GrpcClientRuntime
        except ModuleNotFoundError as e:
            raise NotImplementedError(
                "the distributed gRPC runtime is not available in this "
                "build; use LocalMooseRuntime for single-process execution"
            ) from e

        self._client = GrpcClientRuntime(self.identities, tls=tls)
        # per-role elapsed micros of the most recent run (reference
        # GrpcMooseRuntime, pymoose/src/bindings.rs:320-328)
        self.last_timings: Dict[str, int] = {}
        # supervisor outcome of the most recent run: attempts,
        # per-party errors, injected chaos faults (mirrors
        # LocalMooseRuntime.last_plan)
        self.last_session_report: Dict = {}
        # resolved per-role worker plans of the most recent run
        # ({party: {"plan_mode", "pinned_segments"}}) — the distributed
        # mirror of LocalMooseRuntime.last_plan
        self.last_plan_modes: Dict = {}

    def set_default(self):
        edsl_base.set_current_runtime(self)

    def evaluate_computation(self, computation, arguments=None,
                             timeout: float = 120.0):
        computation, arguments = _lift_computation(computation, arguments)
        try:
            outputs, timings = self._client.run_computation(
                computation, arguments, timeout=timeout
            )
        finally:
            self.last_session_report = dict(
                self._client.last_session_report
            )
            self.last_plan_modes = dict(
                self.last_session_report.get("plan_modes") or {}
            )
        self.last_timings = dict(timings)
        return outputs, timings
