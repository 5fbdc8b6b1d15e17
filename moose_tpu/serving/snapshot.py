"""Durable warm-state snapshots for the serving registry.

A blitzen replica's warm state is expensive: tracing each predictor,
compiling every batch bucket, and driving the validated-jit ladder to
steady state takes minutes, during which the replica cannot serve.  A
snapshot persists everything that survives a process restart so a new
replica cold-starts warm in seconds:

- the **traced computation** of every registered model (reference
  serde msgpack — the same bytes ``elk``/``dasher`` exchange);
- the **resolved plan state** of the validated-jit ladder per plan key
  (ladder level, settled mode, pinned ops), lifted straight from the
  interpreter's plan registry — a restored plan re-enters at its
  settled rung, so the first post-restore evaluation jit-compiles but
  NEVER re-validates (no eager reference run, ``validating_after_warm``
  stays 0);
- the **lowered computations** the runtime auto-compiled during warmup
  (per-host routed models), keyed exactly as the runtime's compiled
  cache keys them, each with its own plan state;
- the **Pallas kernel verdicts** (per ``(kernel, width)`` first-use
  bit-exactness outcomes) — fallback pins always restore (skipping a
  doomed kernel is safe anywhere); ``ok`` verdicts restore only when
  the snapshot was taken on the SAME jax backend;
- **AOT-exported compiled batch buckets** where ``jax.export`` supports
  the resolved plan (a promoted whole-graph jit): serialized StableHLO
  artifacts, verdict-tagged per bucket, verified loadable at restore
  (``unsupported:*`` verdicts record exactly why a bucket could not be
  exported — segmented/per-op plans compose multiple XLA programs in
  Python and are rebuilt from plan state + the persistent compilation
  cache instead);
- under ``MOOSE_TPU_FIXED_KEYS``, a per-bucket **probe digest**: the
  blake2b of a canned deterministic evaluation, recomputed at load so a
  restored replica is proven BIT-IDENTICAL to the replica that wrote
  the snapshot before it serves traffic.

Layout (versioned, atomic)::

    <dir>/snapshot-<n>/MANIFEST.json      # format, versions, checksums
    <dir>/snapshot-<n>/<model>.comp       # serde computation bytes
    <dir>/snapshot-<n>/<model>.lowered.<i>  # auto-lowered graphs
    <dir>/snapshot-<n>/<model>.aot.<bucket> # jax.export artifacts
    <dir>/CURRENT                         # points at the live snapshot

Writers stage a complete ``snapshot-<n>`` directory, fsync it, then
atomically repoint ``CURRENT`` — a crash mid-write leaves the previous
snapshot live and the orphan staging directory is pruned on the next
save.  Readers resolve ``CURRENT``, verify the manifest checksum chain,
and fall back to fresh registration on ANY validation failure (typed
:class:`~moose_tpu.errors.SnapshotError` — never serve suspect state).

Invalidation rules (any mismatch rejects the snapshot): snapshot format
version, package version, per-file blake2b checksums, the model-source
digests the caller passes (blitzen digests the ONNX bytes + feature
count + dtype), and the fixed-keys probe digests.  A jax backend
mismatch only drops the kernel ``ok`` verdicts (re-checked on first
use) — the rest of the snapshot stays usable.
"""

from __future__ import annotations

import contextlib
import hashlib
import json
import os
import tempfile
import time
from pathlib import Path
from typing import Dict, Optional, Tuple

import numpy as np

from .. import __version__ as _pkg_version
from ..errors import SnapshotError
from ..logger import get_logger

SNAPSHOT_FORMAT = 1
_CURRENT = "CURRENT"


# -- helpers ----------------------------------------------------------------


def _blake(data: bytes) -> str:
    return hashlib.blake2b(data, digest_size=16).hexdigest()


def _freeze(obj):
    """Recursively convert JSON lists back into the tuples the runtime
    cache keys are made of.  Every sequence inside a plan-cache key is a
    tuple of (bool | int | float | str | tuple), so a blanket
    list->tuple restore reproduces the exact key object."""
    if isinstance(obj, list):
        return tuple(_freeze(x) for x in obj)
    return obj


def _probe_rows(bucket: int, row_shape: Tuple[int, ...]) -> np.ndarray:
    """The canned deterministic probe input for one bucket — the same
    generator discipline registry warmup uses, so probe evaluations
    replay a shape the plan already compiled."""
    rng = np.random.default_rng(bucket)
    return rng.normal(size=(bucket, *row_shape))


def _fixed_keys_active() -> bool:
    return bool(os.environ.get("MOOSE_TPU_FIXED_KEYS"))


def _result_digest(arr: np.ndarray) -> str:
    arr = np.asarray(arr)
    meta = f"{arr.shape}|{arr.dtype}".encode()
    return _blake(meta + np.ascontiguousarray(arr).tobytes())


@contextlib.contextmanager
def _fleet_lock(directory: Path, exclusive: bool):
    """Cross-process advisory lock on the snapshot directory: replicas
    legitimately SHARE a snapshot dir (that is the fleet warm-start
    story), so concurrent writers (two replicas draining at once) must
    serialize publication, and a reader mid-restore must never see its
    snapshot pruned out from under it.  Writers take the lock
    exclusively around publish+prune; readers take it shared while
    slurping blobs into memory (never across the re-warm)."""
    import fcntl

    directory.mkdir(parents=True, exist_ok=True)
    with open(directory / ".lock", "a+b") as fd:
        fcntl.flock(
            fd, fcntl.LOCK_EX if exclusive else fcntl.LOCK_SH
        )
        try:
            yield
        finally:
            fcntl.flock(fd, fcntl.LOCK_UN)


# -- plan-state capture -----------------------------------------------------


def _plan_states_of(comp) -> Dict[str, dict]:
    """JSON-able copy of the interpreter plan registry's entry for one
    computation: {plan_key: {level, mode, pinned}}."""
    from ..execution.interpreter import _registry

    out = {}
    for plan_key, state in (_registry().get(comp) or {}).items():
        out[plan_key] = {
            "level": int(state["level"]),
            "mode": state["mode"],
            "pinned": sorted(state["pinned"] or ()),
        }
    return out


def _restore_plan_states(comp, states: Dict[str, dict]) -> None:
    from ..execution.interpreter import _registry

    entry = _registry().setdefault(comp, {})
    for plan_key, state in states.items():
        entry[plan_key] = {
            "level": int(state["level"]),
            "mode": state["mode"],
            "pinned": frozenset(state["pinned"] or ()),
        }


def _kernel_verdicts() -> Dict[str, str]:
    from ..native import ring128_kernels

    return dict(ring128_kernels.report().get("kernels") or {})


def _restore_kernel_verdicts(verdicts: Dict[str, str],
                             same_backend: bool) -> int:
    """Reinstall per-(kernel, width) verdicts.  ``fallback:*`` pins are
    always safe to restore (they only route a primitive to its XLA
    twin); ``ok`` verdicts skip the first-use bit-exactness check, so
    they restore only when the snapshot's jax backend matches."""
    from ..native import ring128_kernels

    restored = 0
    with ring128_kernels._STATE_LOCK:
        for key, verdict in verdicts.items():
            kernel, _, width = key.partition("/")
            try:
                state_key = (kernel, int(width))
            except ValueError:
                continue
            if verdict == "ok" and not same_backend:
                continue
            if state_key not in ring128_kernels._STATE:
                ring128_kernels._STATE[state_key] = verdict
                restored += 1
    return restored


# -- AOT export (best-effort) ----------------------------------------------


def _resolved_runners(runtime, comp):
    """Yield (bucket_binding_key, runner) for every _SelfCheckRunner the
    runtime's interpreters cached for ``comp``."""
    from ..execution.interpreter import _SelfCheckRunner

    for interp in (
        getattr(runtime, "_stacked", None),
        getattr(runtime, "_interpreter", None),
    ):
        if interp is None:
            continue
        for key, entry in (interp._cache.get(comp) or {}).items():
            fn = entry[1] if isinstance(entry, tuple) else entry
            runner = getattr(fn, "__self__", None)
            if isinstance(runner, _SelfCheckRunner):
                yield key, runner


def _bucket_of_binding(key, input_name: str) -> Optional[int]:
    """Recover the batch-bucket size from a binding cache key: the
    leading dim of the input's recorded shape."""
    for part in key:
        if (
            isinstance(part, tuple)
            and len(part) == 3
            and part[0] == input_name
            and isinstance(part[1], tuple)
            and part[1]
        ):
            return int(part[1][0])
    return None


def _export_aot_buckets(
    runtime, model
) -> Dict[int, Tuple[bytes, str, str]]:
    """Try to AOT-serialize each bucket's resolved executable via
    ``jax.export``.  Only a plan promoted to whole-graph jit is a
    single exportable XLA program; everything else (segmented, per-op,
    eager, still-validating) records an ``unsupported:*`` verdict and
    relies on plan-state restore + the persistent compilation cache.
    Each value is ``(blob, verdict, plan_key)`` — the plan key lets the
    restore side stash the artifact under the binding the runner will
    actually look it up by."""
    out: Dict[int, Tuple[bytes, str, str]] = {}
    if os.environ.get("MOOSE_TPU_SNAPSHOT_AOT", "1") == "0":
        return out
    try:
        from jax import export as jax_export
    except Exception:  # pragma: no cover - ancient jax
        return out
    from ..execution.interpreter import master_key_words

    for key, runner in _resolved_runners(runtime, model.comp):
        bucket = _bucket_of_binding(key, model.input_name)
        if bucket is None or bucket in out:
            continue
        plan_key = getattr(runner, "_plan_key", "logical")
        if runner.mode != "jit" or runner.plan_mode != "whole-graph":
            out[bucket] = (
                b"",
                f"unsupported:plan-{runner.plan_mode}-{runner.mode}",
                plan_key,
            )
            continue
        try:
            import jax
            import jax.numpy as jnp

            probe = _probe_rows(bucket, model.row_shape)
            dyn = {model.input_name: jnp.asarray(probe)}
            # the plan returns runtime-value pytrees (HostTensor, ...)
            # jax.export cannot serialize; export a wrapper yielding
            # the flat leaves instead — the artifact is a raw compute
            # program, not a runtime-value producer
            inner = runner._jit_fn
            flat_fn = jax.jit(
                lambda mk, args: jax.tree_util.tree_leaves(
                    inner(mk, args)
                )
            )
            exported = jax_export.export(flat_fn)(
                master_key_words("logical"), dyn
            )
            out[bucket] = (exported.serialize(), "exported", plan_key)
        except Exception as e:  # noqa: BLE001 — best-effort by contract
            out[bucket] = (
                b"", f"unsupported:{type(e).__name__}", plan_key
            )
    return out


def verify_aot_artifact(blob: bytes):
    """Deserialize one exported bucket back into a callable (raises on
    a corrupt/incompatible artifact).  Callers may invoke the result as
    ``fn(master_key, {input_name: rows})`` on the platform the artifact
    was exported for."""
    from jax import export as jax_export

    exported = jax_export.deserialize(blob)
    return exported.call


# -- save -------------------------------------------------------------------


def save_snapshot(
    server_or_registry,
    directory,
    source_digests: Optional[Dict[str, str]] = None,
    only: Optional[set] = None,
) -> Path:
    """Write a complete warm-state snapshot of every registered model to
    ``directory`` and atomically repoint ``CURRENT`` at it.  Returns the
    new snapshot path.  ``source_digests`` (model name -> opaque digest
    of whatever the caller registered from, e.g. the ONNX bytes) become
    load-time invalidation keys.  ``only`` restricts the snapshot to the
    named models — a replica with ephemeral control-plane generations
    loaded snapshots just its durable set, so the restore side's
    source-digest set-equality check still holds."""
    from ..serde import serialize_computation

    registry = getattr(server_or_registry, "registry", server_or_registry)
    runtime = registry.runtime
    directory = Path(directory)
    directory.mkdir(parents=True, exist_ok=True)
    t0 = time.perf_counter()

    # the stage is private (unique temp name): blob writes and the
    # probe evaluations run UNLOCKED; only the publish below (sequence
    # number, rename, CURRENT repoint, prune) needs the fleet lock
    stage = Path(tempfile.mkdtemp(
        dir=directory, prefix="snapshot-staging."
    ))
    try:
        manifest = {
            "format": SNAPSHOT_FORMAT,
            "package_version": _pkg_version,
            "jax_backend": _jax_backend(),
            "fixed_keys": _fixed_keys_active(),
            "kernel_verdicts": _kernel_verdicts(),
            "models": {},
            "files": {},
        }
        for name in registry.names():
            if only is not None and name not in only:
                continue
            model = registry.get(name)
            entry = {
                "input_name": model.input_name,
                "row_shape": list(model.row_shape),
                "buckets": list(model.buckets),
                "warmup_report": {
                    str(b): dict(r)
                    for b, r in model.warmup_report.items()
                },
                "plan_states": _plan_states_of(model.comp),
                "stacked_rejected": model.comp in getattr(
                    runtime, "_stacked_rejected", ()
                ),
                "lowered": [],
                "aot": {},
                "probe_digests": {},
            }
            if source_digests and name in source_digests:
                entry["source_digest"] = source_digests[name]
            _write_blob(
                stage, manifest, f"{name}.comp",
                serialize_computation(model.comp),
            )
            entry["comp_file"] = f"{name}.comp"
            # auto-lowered graphs (per-host routed models) with their
            # own resolved plan states, keyed as the runtime keys them
            per_comp = getattr(runtime, "_compiled_cache", {}).get(
                model.comp
            ) or {}
            for i, (key, compiled) in enumerate(per_comp.items()):
                lowered = (
                    compiled[0] if isinstance(compiled, tuple) else compiled
                )
                fname = f"{name}.lowered.{i}"
                _write_blob(
                    stage, manifest, fname,
                    serialize_computation(lowered),
                )
                entry["lowered"].append({
                    "key": key,
                    "file": fname,
                    "plan_states": _plan_states_of(lowered),
                })
            for bucket, (blob, verdict, plan_key) in _export_aot_buckets(
                runtime, model
            ).items():
                record = {"verdict": verdict, "plan_key": plan_key}
                if blob:
                    fname = f"{name}.aot.{bucket}"
                    _write_blob(stage, manifest, fname, blob)
                    record["file"] = fname
                entry["aot"][str(bucket)] = record
            if _fixed_keys_active():
                # bit-exactness anchors: one canned evaluation per
                # bucket, digested — the load side must reproduce every
                # digest before the restored replica serves traffic
                for bucket in model.buckets:
                    result, _ = registry.evaluate(
                        model, _probe_rows(bucket, model.row_shape)
                    )
                    entry["probe_digests"][str(bucket)] = (
                        _result_digest(result)
                    )
            manifest["models"][name] = entry
        body = json.dumps(manifest, indent=1, sort_keys=True).encode()
        (stage / "MANIFEST.json").write_bytes(body)
        _fsync_dir_tree(stage)
        with _fleet_lock(directory, exclusive=True):
            final = directory / f"snapshot-{_next_seq(directory)}"
            os.rename(stage, final)
            _repoint_current(directory, final.name)
            _prune(directory, keep=final.name)
    except BaseException:
        _rmtree(stage)
        raise
    get_logger().info(
        "snapshot: wrote %s (%d model(s)) in %.2fs",
        final, len(manifest["models"]), time.perf_counter() - t0,
    )
    return final


def _write_blob(stage: Path, manifest: dict, fname: str,
                data: bytes) -> None:
    (stage / fname).write_bytes(data)
    manifest["files"][fname] = {
        "bytes": len(data), "blake2b": _blake(data),
    }


def _jax_backend() -> str:
    try:
        import jax

        return jax.default_backend()
    except Exception:  # pragma: no cover - jax always importable here
        return "unknown"


def _next_seq(directory: Path) -> int:
    seqs = [0]
    for p in directory.glob("snapshot-*"):
        tail = p.name.split("-", 1)[1].split(".", 1)[0]
        if tail.isdigit():
            seqs.append(int(tail))
    return max(seqs) + 1


def _repoint_current(directory: Path, name: str) -> None:
    tmp = directory / (_CURRENT + ".tmp")
    tmp.write_text(name + "\n")
    os.replace(tmp, directory / _CURRENT)


def _prune(directory: Path, keep: str, history: int = 1) -> None:
    """Drop crash-orphaned staging leftovers and all but ``history``
    predecessors.  A staging dir is only an orphan when it is OLD —
    a recent one may belong to another replica mid-save (staging is
    deliberately done outside the fleet lock)."""
    snaps = [
        p for p in directory.glob("snapshot-*")
        if p.is_dir() and p.name != keep
    ]
    now = time.time()
    stale = [
        p for p in snaps
        if "staging" in p.name and now - p.stat().st_mtime > 3600
    ]
    # numeric sort: lexicographic ordering would rank snapshot-10
    # before snapshot-9 and delete the true predecessor
    numbered = sorted(
        (
            p for p in snaps
            if "staging" not in p.name
            and p.name.split("-")[-1].isdigit()
        ),
        key=lambda p: int(p.name.split("-")[-1]),
    )
    stale += numbered[:-history] if history else numbered
    for p in stale:
        _rmtree(p)


def _rmtree(path: Path) -> None:
    import shutil

    with contextlib.suppress(OSError):
        shutil.rmtree(path)


def _fsync_dir_tree(stage: Path) -> None:
    with contextlib.suppress(OSError):
        for p in stage.iterdir():
            with open(p, "rb") as f:
                os.fsync(f.fileno())
        fd = os.open(stage, os.O_RDONLY)
        try:
            os.fsync(fd)
        finally:
            os.close(fd)


# -- load -------------------------------------------------------------------


def current_snapshot_path(directory) -> Optional[Path]:
    """Resolve ``CURRENT`` to the live snapshot directory (None when no
    snapshot has ever been written)."""
    directory = Path(directory)
    pointer = directory / _CURRENT
    if not pointer.exists():
        return None
    name = pointer.read_text().strip()
    path = directory / name
    return path if path.is_dir() else None


def read_manifest(snapshot_path: Path) -> dict:
    """Parse + checksum-verify a snapshot's manifest.  Raises
    :class:`SnapshotError` on any validation failure."""
    return _read_verified(snapshot_path)[0]


def _read_verified(snapshot_path: Path):
    """(manifest, {fname: bytes}) with every blob checksum-verified —
    the blobs come back IN MEMORY so the caller can release the fleet
    lock before the (slow) re-warm, immune to concurrent pruning."""
    try:
        manifest = json.loads(
            (snapshot_path / "MANIFEST.json").read_text()
        )
    except (OSError, json.JSONDecodeError) as e:
        raise SnapshotError(f"unreadable manifest in {snapshot_path}: {e}")
    if manifest.get("format") != SNAPSHOT_FORMAT:
        raise SnapshotError(
            f"snapshot format {manifest.get('format')!r} != supported "
            f"{SNAPSHOT_FORMAT}"
        )
    if manifest.get("package_version") != _pkg_version:
        raise SnapshotError(
            f"snapshot written by moose_tpu "
            f"{manifest.get('package_version')!r}, this build is "
            f"{_pkg_version!r}"
        )
    blobs: Dict[str, bytes] = {}
    for fname, spec in (manifest.get("files") or {}).items():
        try:
            data = (snapshot_path / fname).read_bytes()
        except OSError as e:
            raise SnapshotError(f"snapshot blob {fname} unreadable: {e}")
        if _blake(data) != spec.get("blake2b"):
            raise SnapshotError(
                f"snapshot blob {fname} failed its checksum"
            )
        blobs[fname] = data
    return manifest, blobs


def restore_registry(
    registry,
    directory,
    source_digests: Optional[Dict[str, str]] = None,
    rewarm: bool = True,
) -> dict:
    """Restore every model in the live snapshot under ``directory`` into
    ``registry`` (which must be empty of those names).  Returns a report
    ``{models, rewarm_s, probe_checked, aot}``.

    Restore order per model: deserialize the traced computation,
    reinstall its resolved plan states (and those of every lowered
    graph) in the interpreter plan registry, reinstall lowered graphs in
    the runtime's compiled cache, then — when ``rewarm`` — run ONE
    evaluation per bucket.  That evaluation jit-compiles (from the
    persistent compilation cache when enabled) but never validates: the
    ladder re-enters at its settled mode.  Under MOOSE_TPU_FIXED_KEYS
    the rewarm doubles as the bit-exactness proof against the writer's
    probe digests; any divergence raises :class:`SnapshotError` before
    the model is installed."""
    from ..serde import deserialize_computation
    from .registry import RegisteredModel

    directory = Path(directory)
    if not directory.is_dir():
        raise SnapshotError(f"no snapshot under {directory}")
    with _fleet_lock(directory, exclusive=False):
        snapshot_path = current_snapshot_path(directory)
        if snapshot_path is None:
            raise SnapshotError(f"no snapshot under {directory}")
        manifest, blobs = _read_verified(snapshot_path)
    models = manifest.get("models") or {}
    if not models:
        raise SnapshotError(f"snapshot {snapshot_path} holds no models")
    if source_digests is not None:
        if set(source_digests) != set(models):
            raise SnapshotError(
                f"snapshot models {sorted(models)} != requested "
                f"{sorted(source_digests)}"
            )
        for name, digest in source_digests.items():
            if models[name].get("source_digest") != digest:
                raise SnapshotError(
                    f"model {name!r}: source digest mismatch (the "
                    "model file changed since the snapshot was written)"
                )
    restored_kernels = _restore_kernel_verdicts(
        manifest.get("kernel_verdicts") or {},
        same_backend=manifest.get("jax_backend") == _jax_backend(),
    )
    check_probes = _fixed_keys_active() and manifest.get("fixed_keys")
    report = {
        "snapshot": str(snapshot_path),
        "models": [],
        "rewarm_s": 0.0,
        "probe_checked": 0,
        "kernel_verdicts_restored": restored_kernels,
        "aot": {},
    }
    t0 = time.perf_counter()
    runtime = registry.runtime
    # staged install: nothing lands in registry._models until EVERY
    # model restored and proved out — a failure on the Nth model must
    # leave the registry empty so the caller's fresh-registration
    # fallback can re-register all names without collisions
    staged: Dict[str, object] = {}
    for name, entry in models.items():
        comp = deserialize_computation(blobs[entry["comp_file"]])
        _restore_plan_states(comp, entry.get("plan_states") or {})
        if entry.get("stacked_rejected") and hasattr(
            runtime, "_stacked_rejected"
        ):
            runtime._stacked_rejected.add(comp)
        compiled_cache = getattr(runtime, "_compiled_cache", None)
        if compiled_cache is not None and entry.get("lowered"):
            per_comp = compiled_cache.setdefault(comp, {})
            for item in entry["lowered"]:
                lowered = deserialize_computation(blobs[item["file"]])
                per_comp[_freeze(item["key"])] = lowered
                _restore_plan_states(
                    lowered, item.get("plan_states") or {}
                )
        model = RegisteredModel(
            name=name,
            comp=comp,
            input_name=entry["input_name"],
            row_shape=tuple(entry["row_shape"]),
            buckets=tuple(int(b) for b in entry["buckets"]),
            warmup_report={
                int(b): dict(r)
                for b, r in (entry.get("warmup_report") or {}).items()
            },
        )
        aot_verdicts = {}
        aot_exec = os.environ.get(
            "MOOSE_TPU_SNAPSHOT_AOT_EXEC", "1"
        ) != "0"
        for bucket, record in (entry.get("aot") or {}).items():
            verdict = record.get("verdict", "")
            if verdict == "exported" and record.get("file"):
                try:
                    verify_aot_artifact(blobs[record["file"]])
                    verdict = "restored"
                    if aot_exec:
                        # stash the artifact so the restored runner's
                        # first call executes the exported program
                        # outright (skipping even the cached compile);
                        # the rewarm below proves bit-exactness against
                        # the writer's probe digests as usual
                        from ..execution.interpreter import (
                            preload_aot_artifact,
                        )

                        preload_aot_artifact(
                            comp,
                            record.get("plan_key", "logical"),
                            blobs[record["file"]],
                        )
                        verdict = "preloaded"
                except Exception as e:  # noqa: BLE001 — degrade, never
                    # fail the whole snapshot over an optional artifact
                    verdict = f"unloadable:{type(e).__name__}"
            aot_verdicts[bucket] = verdict
        report["aot"][name] = aot_verdicts
        if rewarm:
            for bucket in model.buckets:
                result, eval_report = registry.evaluate(
                    model, _probe_rows(bucket, model.row_shape)
                )
                if eval_report["validating"]:
                    raise SnapshotError(
                        f"model {name!r} bucket {bucket}: restored plan "
                        "re-entered validation — plan state did not "
                        "survive the snapshot"
                    )
                want = (entry.get("probe_digests") or {}).get(str(bucket))
                if check_probes and want is not None:
                    got = _result_digest(result)
                    if got != want:
                        raise SnapshotError(
                            f"model {name!r} bucket {bucket}: probe "
                            f"digest {got} != snapshot {want} — restored "
                            "state is not bit-identical"
                        )
                    report["probe_checked"] += 1
            # the rewarm just drove each bucket's first call: any
            # preloaded artifact that bound is now the executing
            # program — upgrade its verdict so callers can assert the
            # exported program (not a recompile) served the probe
            if "preloaded" in aot_verdicts.values():
                for key, runner in _resolved_runners(runtime, comp):
                    bucket = _bucket_of_binding(key, model.input_name)
                    if (
                        bucket is None
                        or aot_verdicts.get(str(bucket)) != "preloaded"
                    ):
                        continue
                    state = getattr(runner, "aot_state", None)
                    if state == "adopted":
                        aot_verdicts[str(bucket)] = "executed"
                    elif state == "fallback":
                        aot_verdicts[str(bucket)] = "restored"
        staged[name] = model
        report["models"].append(name)
    registry._models.update(staged)
    report["rewarm_s"] = time.perf_counter() - t0
    from ..metrics import counter

    aot_counter = counter(
        "moose_tpu_serving_aot_buckets_total",
        "AOT bucket artifacts by restore verdict",
        labels=("verdict",),
    )
    executed = 0
    for verdicts in report["aot"].values():
        for verdict in verdicts.values():
            aot_counter.inc(verdict=verdict.split(":", 1)[0])
            executed += verdict == "executed"
    get_logger().info(
        "snapshot: restored %d model(s) from %s in %.2fs "
        "(%d probe digest(s) verified, %d kernel verdict(s), "
        "%d AOT bucket(s) executing)",
        len(report["models"]), snapshot_path, report["rewarm_s"],
        report["probe_checked"], restored_kernels, executed,
    )
    return report
