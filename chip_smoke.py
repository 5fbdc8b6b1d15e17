"""The quickest proof that moose_tpu still starts on the chip.

    python chip_smoke.py                # one TPU chip, as the driver runs it
    python chip_smoke.py --four-chips   # parties as a mesh axis; four chips

Drives the main path once through the entry points a user calls, with
nothing set that a user would not get by default (no ``MOOSE_TPU_*``
variable, one process, no child):

1. **Secure dot at the reference's own size**: ``@pm.computation`` ->
   ``LocalMooseRuntime`` with the default layout, 1000x1000 @ 1000x1000
   at ``pm.fixed(14, 23)`` (ring128, the reference benchmark's
   precision) and at ``pm.fixed(8, 17)`` (ring64), two calls each,
   against float64 ``x @ y``.
2. **A served predictor at the reference's logreg width**: sklearn
   logistic regression with 100 features -> ONNX -> ``from_onnx`` ->
   ``InferenceServer.register_model(buckets=(1, 128))`` -> ``predict``
   at batch 1 and batch 128, jit on, against ``predict_proba``.
3. **Attestation**: fails when an answer is outside its tolerance, a
   Pallas kernel fell back, no kernel was dispatched at all, or a jit
   candidate failed to compile or run.  A plan the validated-jit ladder
   settled on by bit divergence is printed (``plan_mode``, pinned ops)
   and does not fail: that is the ladder doing its job.

``--four-chips`` runs the dot and the logreg forward through
``LocalMooseRuntime(layout="stacked", mesh=spmd.make_mesh())`` — three
parties on three devices, resharing as collective-permute — compares
each with the same call on one device and with the float reference, and
runs no other phase.

Every phase prints one JSON line.  Seconds are cold, include compiles,
and are not performance numbers.  The last line is
``{"ok": true, "device": {...}}`` with the device as JAX reports it; on
any failure the reasons are on earlier lines and the exit code is 1.
Anything but a TPU is a failure, never a CPU run — except under
``--rehearse``, the no-chip rehearsal of this script's own control flow
(tiny sizes, interpret-mode kernels forced on), whose last line says so.
"""

import argparse
import json
import sys
import time
import traceback

import numpy as np

SEED = 22
PARTIES = ["alice", "bob", "carole"]
FEATURES = 100  # the reference logreg benchmark's width
# logreg against sklearn: the repo's own bound for this comparison
# (tests/test_predictors.py, scripts/serve_smoke.py).  Errors seen are
# ~1e-8; a miscompiled truncation is off by ~2^47 (DEVELOP.md "Known
# issue"), so the bound separates the two by twenty orders of magnitude
LOGREG_TOL = 5e-3
PREDICTS_PER_BUCKET = 8
# The predictor default fixed(24, 40) holds exp(|t|) only below 2^24,
# |t| < 16.6: past that the secure sigmoid overflows by design, on the
# CPU and on the chip alike (PR 22 took five such rows for a
# miscompile).  The smoke's model and inputs stay well inside, and say
# so when they do not.
MAX_ABS_LOGIT = 14.0


def emit(record: dict) -> None:
    print(json.dumps(record, default=str), flush=True)


def dot_tolerance(x, y, frac: int) -> float:
    """Worst case of a K-term fixed-point dot against float64: each
    operand is rounded to 2^-frac (error <= 2^-(frac+1) per element, so
    <= K * (max|x| + max|y|) * 2^-(frac+1) over the sum, second-order
    term included in the slack), and the probabilistic truncation of
    the sum is off by at most one unit of 2^-frac either way."""
    k = x.shape[1]
    operand = k * (np.abs(x).max() + np.abs(y).max()) * 2.0 ** -(frac + 1)
    return float(operand + 2.0 ** (1 - frac))


def dot_computation(pm, fixed_dtype):
    alice, bob, carole = (pm.host_placement(name) for name in PARTIES)
    rep = pm.replicated_placement("rep", players=[alice, bob, carole])

    @pm.computation
    def secure_dot(
        x: pm.Argument(placement=alice, dtype=pm.float64),
        y: pm.Argument(placement=bob, dtype=pm.float64),
    ):
        with alice:
            xf = pm.cast(x, dtype=fixed_dtype)
        with bob:
            yf = pm.cast(y, dtype=fixed_dtype)
        with rep:
            z = pm.dot(xf, yf)
        with carole:
            return pm.cast(z, dtype=pm.float64)

    return secure_dot


def dot_cases(pm, n: int):
    """(label, dtype, frac, x, y): ring128 at the reference precision on
    N(0, 1) inputs as the reference's benchmark has it; ring64 at fixed(8, 17) on inputs
    in [-0.5, 0.5], so that a 1000-term sum stays below 2^8 and inside
    ``dtypes.fixed``'s ring64 headroom."""
    rng = np.random.default_rng(SEED)
    yield (
        "ring128", pm.fixed(14, 23), 23,
        rng.normal(size=(n, n)), rng.normal(size=(n, n)),
    )
    yield (
        "ring64", pm.fixed(8, 17), 17,
        rng.uniform(-0.5, 0.5, size=(n, n)),
        rng.uniform(-0.5, 0.5, size=(n, n)),
    )


def build_logreg():
    from sklearn.linear_model import LogisticRegression

    from moose_tpu import predictors
    from moose_tpu.predictors.sklearn_export import (
        logistic_regression_onnx,
    )

    rng = np.random.default_rng(SEED + 1)
    x = rng.normal(size=(512, FEATURES))
    w = rng.normal(size=FEATURES) / np.sqrt(FEATURES)
    # noisy labels: a separable fit grows its weights until the logits
    # leave the secure sigmoid's domain (MAX_ABS_LOGIT)
    y = (x @ w + rng.normal(size=512) > 0).astype(int)
    sk = LogisticRegression().fit(x, y)
    model = predictors.from_onnx(
        logistic_regression_onnx(sk, FEATURES).encode()
    )
    return model, sk


def counter_values(name: str) -> dict:
    """``{"kernel=...": n}`` of one counter family of the registry."""
    from moose_tpu import metrics

    return metrics.REGISTRY.snapshot().get(name, {}).get("values", {})


class Smoke:
    def __init__(self, cache_dir: str):
        self.cache_dir = cache_dir
        self.failures = []

    def fail(self, phase: str, reason: str) -> None:
        self.failures.append(f"{phase}: {reason}")

    def record(self, phase: str, *, cold_s, err, tol, plan, **extra):
        """One JSON line per phase, and the per-phase checks: answer
        within tolerance, no candidate that failed to compile or run."""
        from moose_tpu.native import ring128_kernels as rk

        emit({
            "phase": phase,
            "cold_seconds_including_compile": cold_s,
            "max_abs_error": err,
            "tolerance": tol,
            "last_plan": plan,
            "pallas": rk.report(),
            "pallas_dispatch_total": counter_values(
                "moose_tpu_pallas_dispatch_total"
            ),
            "pallas_fallback_total": counter_values(
                "moose_tpu_pallas_fallback_total"
            ),
            "compile_cache_dir": self.cache_dir,
            **extra,
        })
        if not (np.isfinite(err) and err <= tol):
            self.fail(phase, f"max abs error {err} outside tolerance {tol}")
        if plan.get("run_errors"):
            self.fail(
                phase, f"jit candidate(s) failed: {plan['run_errors']}"
            )
        if plan.get("pinned_ops") or plan.get("plan_mode") in (
            "per-op", "eager"
        ):
            # bit divergence handled by the ladder: a finding for
            # ROADMAP S4/D2, not a failure of the smoke
            emit({
                "phase": phase, "note": "ladder settled below whole-graph",
                "plan_mode": plan.get("plan_mode"),
                "pinned_ops": plan.get("pinned_ops"),
            })

    def run_phase(self, phase: str, fn, *args) -> None:
        try:
            fn(*args)
        except Exception as e:  # noqa: BLE001 — report, go on, exit 1
            emit({
                "phase": phase, "error": f"{type(e).__name__}: {e}",
                "traceback": traceback.format_exc()[-4000:],
            })
            self.fail(phase, f"raised {type(e).__name__}: {e}")

    def attest_kernels(self) -> None:
        from moose_tpu.native import ring128_kernels as rk

        report = rk.report()
        for key, verdict in report["kernels"].items():
            if verdict.startswith("fallback:"):
                self.fail(
                    "attestation",
                    f"pallas kernel {key} is {verdict}: "
                    f"{report['errors'].get(key, 'no exception text')}",
                )
        dispatched = sum(
            counter_values("moose_tpu_pallas_dispatch_total").values()
        )
        if not dispatched:
            self.fail("attestation", "no Pallas kernel was dispatched")
        emit({
            "phase": "attestation", "kernels": report["kernels"],
            "kernel_errors": report["errors"],
            "kernels_switched_off": report["switched_off"],
            "kernels_dispatched": dispatched, "failures": self.failures,
        })


def reference_proba(sk, x):
    """sklearn's answer, for inputs inside the secure sigmoid's domain."""
    worst = float(np.abs(x @ sk.coef_.T + sk.intercept_).max())
    assert worst < MAX_ABS_LOGIT, (
        f"|logit| {worst} is outside the fixed(24, 40) sigmoid's domain"
    )
    return sk.predict_proba(x)


def evaluate_twice(rt, comp, arguments):
    """Two calls, so a warm call is seen; returns the results and the
    two wall times."""
    outs, times = [], []
    for _ in range(2):
        t0 = time.perf_counter()
        (out,) = rt.evaluate_computation(comp, arguments=arguments).values()
        times.append(time.perf_counter() - t0)
        outs.append(np.asarray(out))
    return outs, times


def secure_dot(smoke: Smoke, phase: str, dtype, frac: int, x, y) -> None:
    import moose_tpu as pm
    from moose_tpu.runtime import LocalMooseRuntime

    rt = LocalMooseRuntime(PARTIES)
    outs, times = evaluate_twice(
        rt, dot_computation(pm, dtype), {"x": x, "y": y}
    )
    want = x @ y
    assert outs[0].shape == want.shape, outs[0].shape
    smoke.record(
        phase, cold_s=times[0], tol=dot_tolerance(x, y, frac),
        err=max(float(np.abs(o - want).max()) for o in outs),
        plan=rt.last_plan, second_call_seconds=times[1],
    )


def phase_secure_dot(smoke: Smoke, n: int) -> None:
    import moose_tpu as pm

    for label, dtype, frac, x, y in dot_cases(pm, n):
        phase = f"secure_dot_{label}_{n}x{n}"
        smoke.run_phase(phase, secure_dot, smoke, phase, dtype, frac, x, y)


def served_predicts(smoke: Smoke, phase, server, sk, rng, batch) -> None:
    # every call draws a fresh master key, and a miscompiled program is
    # wrong for some keys only (DEVELOP.md "Known issue"): a handful of
    # calls, each checked
    errs, times = [], []
    for _ in range(PREDICTS_PER_BUCKET):
        x = rng.normal(size=(batch, FEATURES))
        t0 = time.perf_counter()
        got = server.predict(
            "logreg", x, deadline_ms=600_000.0, timeout_s=900.0
        )
        times.append(time.perf_counter() - t0)
        want = reference_proba(sk, x)
        assert got.shape == want.shape, got.shape
        errs.append(float(np.abs(got - want).max()))
    smoke.record(
        phase, cold_s=times[0], err=max(errs), tol=LOGREG_TOL,
        plan=server.registry.runtime.last_plan,
        errors_per_call=errs, later_call_seconds=times[1:],
    )


def phase_served_logreg(smoke: Smoke, buckets) -> None:
    from moose_tpu.serving import InferenceServer, ServingConfig

    model, sk = build_logreg()
    rng = np.random.default_rng(SEED + 2)
    config = ServingConfig.from_env(max_batch=max(buckets))
    t0 = time.perf_counter()
    with InferenceServer(config=config) as server:
        registered = server.register_model(
            "logreg", model, row_shape=(FEATURES,), buckets=buckets
        )
        register_s = time.perf_counter() - t0
        emit({
            "phase": "served_logreg_register",
            "cold_seconds_including_compile": register_s,
            "warmup": {
                str(b): r for b, r in registered.warmup_report.items()
            },
            "last_plan": server.registry.runtime.last_plan,
        })
        for batch in buckets:
            phase = f"served_logreg_batch{batch}"
            smoke.run_phase(
                phase, served_predicts, smoke, phase, server, sk, rng, batch
            )
        snap = server.metrics_snapshot()
        emit({
            "phase": "served_logreg_metrics",
            **{
                key: snap.get(key) for key in (
                    "rows_served", "batches", "deadline_misses",
                    "retraces_after_warm", "validating_after_warm",
                )
            },
        })
        if snap.get("retraces_after_warm") or snap.get("deadline_misses"):
            smoke.fail("served_logreg", f"serving counters: {snap}")


def phase_four_chips(smoke: Smoke, n: int, batch: int) -> None:
    """The path that exists only across chips: the three parties as a
    mesh axis.  Each computation runs stacked over the mesh and stacked
    on one device; both must match the float reference, and each other
    up to the probabilistic truncation."""
    import jax

    import moose_tpu as pm
    from moose_tpu.parallel import spmd
    from moose_tpu.runtime import LocalMooseRuntime

    mesh = spmd.make_mesh()
    emit({
        "phase": "four_chips_mesh", "shape": dict(mesh.shape),
        "devices": [str(d) for d in mesh.devices.ravel()],
    })
    if mesh.shape["parties"] != 3:
        smoke.fail("four_chips_mesh", f"no party axis: {dict(mesh.shape)}")
        return

    def share_devices():
        # what the stacked dialect does to every fresh sharing
        # (dialects/stacked._share_ring): share, then constrain to the
        # mesh.  Code that has never seen more than one chip may have
        # put everything on the first.
        def share(master_key, x_f):
            sess = spmd.SpmdSession(master_key)
            xs = spmd.fx_encode_share(sess, x_f, 14, 23, 128)
            return spmd.constrain(xs.tensor, mesh, 0).lo

        x = np.random.default_rng(SEED).normal(size=(batch, FEATURES))
        with mesh:
            lo = jax.jit(share)(np.arange(4, dtype=np.uint32), x)
        devices = sorted(str(d) for d in lo.sharding.device_set)
        emit({
            "phase": "four_chips_share_array", "shape": lo.shape,
            "sharding": str(lo.sharding), "device_set": devices,
        })
        if len(devices) != 3:
            smoke.fail(
                "four_chips_share_array",
                f"a share array lives on {len(devices)} device(s), not 3",
            )

    smoke.run_phase("four_chips_share_array", share_devices)

    def compare(phase, comp, arguments, want, tol, between_tol):
        rt_mesh = LocalMooseRuntime(PARTIES, layout="stacked", mesh=mesh)
        rt_one = LocalMooseRuntime(PARTIES, layout="stacked")
        on_mesh, mesh_times = evaluate_twice(rt_mesh, comp, arguments)
        on_one, one_times = evaluate_twice(rt_one, comp, arguments)
        between = float(np.abs(on_mesh[1] - on_one[1]).max())
        smoke.record(
            phase, cold_s=mesh_times[0], tol=tol,
            err=max(
                float(np.abs(o - want).max()) for o in on_mesh + on_one
            ),
            plan=rt_mesh.last_plan, one_device_plan=rt_one.last_plan,
            second_call_seconds=mesh_times[1],
            one_device_seconds=one_times,
            mesh_vs_one_device_max_abs=between,
            mesh_vs_one_device_tolerance=between_tol,
        )
        if rt_one.last_plan.get("run_errors"):
            smoke.fail(phase, f"one device: {rt_one.last_plan['run_errors']}")
        if not between <= between_tol:
            smoke.fail(
                phase,
                f"mesh and one device differ by {between} > {between_tol}",
            )

    label, dtype, frac, x, y = next(iter(dot_cases(pm, n)))
    smoke.run_phase(
        "four_chips_secure_dot", compare,
        f"four_chips_secure_dot_{label}_{n}x{n}",
        dot_computation(pm, dtype), {"x": x, "y": y}, x @ y,
        dot_tolerance(x, y, frac),
        # the same integer sum truncated twice: one unit each way
        2.0 ** (1 - frac),
    )

    def logreg_forward():
        model, sk = build_logreg()
        comp = model.traced_predictor()
        (input_name,) = [
            name for name, op in comp.operations.items()
            if op.kind == "Input"
        ]
        xb = np.random.default_rng(SEED + 2).normal(size=(batch, FEATURES))
        compare(
            f"four_chips_logreg_batch{batch}", comp, {input_name: xb},
            reference_proba(sk, xb), LOGREG_TOL,
            # the same circuit on both sides: they differ by the +-1
            # unit of 2^-40 of each probabilistic truncation, amplified
            # through the division; 2^-20 leaves that a million units
            2.0 ** -20,
        )

    smoke.run_phase("four_chips_logreg", logreg_forward)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument(
        "--four-chips", action="store_true",
        help="run only the party-mesh path and its one-device twin",
    )
    parser.add_argument(
        "--rehearse", action="store_true",
        help="no-chip rehearsal of this script: allow the CPU platform, "
        "tiny sizes, interpret-mode kernels forced on",
    )
    args = parser.parse_args(argv)

    import jax

    devices = jax.devices()
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    emit({"phase": "devices", "device": device, "jax": jax.__version__})
    if device["platform"] != "tpu" and not args.rehearse:
        emit({"error": "no TPU: this smoke never runs on another platform"})
        return 1
    if args.four_chips and device["count"] < 4:
        emit({"error": f"--four-chips needs 4 devices, found {device}"})
        return 1

    from moose_tpu import compile_cache

    smoke = Smoke(compile_cache.enable())
    if args.rehearse:
        from moose_tpu.native import ring128_kernels as rk

        rk.set_enabled(True)  # the chip's default; interpret mode here
    n, buckets = (48, (1, 4)) if args.rehearse else (1000, (1, 128))

    t0 = time.perf_counter()
    if args.four_chips:
        phase_four_chips(smoke, n, max(buckets))
    else:
        phase_secure_dot(smoke, n)
        smoke.run_phase("served_logreg", phase_served_logreg, smoke, buckets)
    smoke.attest_kernels()
    emit({
        "phase": "total",
        "cold_seconds_including_compile": time.perf_counter() - t0,
    })
    if smoke.failures:
        emit({"ok": False, "failures": smoke.failures, "device": device})
        return 1
    last = {"ok": True, "device": device}
    if args.rehearse:
        last["rehearsal"] = True
    print(json.dumps(last), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
