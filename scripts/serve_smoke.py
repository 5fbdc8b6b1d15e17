"""CI load-smoke for the serving layer (`moose_tpu/serving/`).

Drives the in-process InferenceServer the way the blitzen daemon does:

1. LOW LOAD — 64 concurrent closed-loop client threads over a logreg
   predictor, generous deadlines.  Asserts: every request completes
   with the right answer, ZERO deadline misses, zero re-traces and zero
   ladder (validating) evaluations after warmup, and batch-fill metrics
   present in the telemetry snapshot.
2. OVERLOAD — the evaluation lock is held so the dispatcher stalls,
   then submissions continue until the bounded queue rejects one.
   Asserts the rejection is a typed ServerOverloadedError raised
   synchronously (never a hang: the whole phase runs under a watchdog
   budget), and that every admitted request still completes once the
   lock is released.

Prints one JSON summary line (the CI log artifact).

    JAX_PLATFORMS=cpu python scripts/serve_smoke.py
"""

import json
import os
import sys
import threading
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

# The smoke validates SCHEDULING semantics (coalescing, deadlines,
# backpressure, metrics) — eager execution keeps the CI step fast and
# deterministic; per-bucket compiled-plan performance is the
# served cell's concern (ROADMAP S7; chip_smoke.py serves jit-on).
os.environ.setdefault("MOOSE_TPU_JIT", "0")

CLIENTS = 64
REQUESTS_PER_CLIENT = 4
FEATURES = 12


def build_logreg():
    from sklearn.linear_model import LogisticRegression

    from moose_tpu import predictors
    from moose_tpu.predictors.sklearn_export import (
        logistic_regression_onnx,
    )

    rng = np.random.default_rng(3)
    x = rng.normal(size=(96, FEATURES))
    y = (rng.uniform(size=96) > 0.5).astype(int)
    sk = LogisticRegression().fit(x, y)
    model = predictors.from_onnx(
        logistic_regression_onnx(sk, FEATURES).encode()
    )
    return model, sk


def low_load_phase(server, sk) -> dict:
    rng = np.random.default_rng(17)
    rows = rng.normal(size=(CLIENTS, REQUESTS_PER_CLIENT, FEATURES))
    errors = []
    max_err = [0.0]
    lock = threading.Lock()

    def client(ci: int):
        try:
            for ri in range(REQUESTS_PER_CLIENT):
                x = rows[ci, ri]
                got = server.predict(
                    "logreg", x, deadline_ms=120_000.0, timeout_s=300.0
                )
                want = sk.predict_proba(x[np.newaxis])
                err = float(np.abs(got - want).max())
                with lock:
                    max_err[0] = max(max_err[0], err)
        except Exception as e:  # noqa: BLE001 — collected + re-raised
            errors.append((ci, repr(e)))

    threads = [
        threading.Thread(target=client, args=(ci,))
        for ci in range(CLIENTS)
    ]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=600)
    elapsed = time.perf_counter() - t0
    assert not errors, f"client failures: {errors[:5]}"
    assert max_err[0] < 5e-3, f"serving results diverged: {max_err[0]}"

    snap = server.metrics_snapshot()
    total = CLIENTS * REQUESTS_PER_CLIENT
    assert snap["rows_served"] == total, snap
    assert snap["deadline_misses"] == 0, snap
    assert snap["deadline_drops"] == 0, snap
    # the warm-registry promise: serving traffic NEVER re-traces or
    # lands on a validating (ladder) evaluation
    assert snap["retraces_after_warm"] == 0, snap
    assert snap["validating_after_warm"] == 0, snap
    # batch-fill telemetry must be present and sane
    assert snap["batch_fill_ratio"] is not None, snap
    assert 0.0 < snap["batch_fill_ratio"] <= 1.0, snap
    assert snap["batch_size_hist"], snap
    assert snap["request_latency_p99_s"] is not None, snap
    # 64 concurrent clients must coalesce: far fewer batches than rows
    assert snap["batches"] < total, snap
    return {
        "elapsed_s": elapsed,
        "requests_per_sec": total / elapsed,
        "batches": snap["batches"],
        "batch_fill_ratio": snap["batch_fill_ratio"],
        "p99_s": snap["request_latency_p99_s"],
    }


def overload_phase(server) -> dict:
    """The queue bound must REJECT (typed), not hang."""
    from moose_tpu.errors import ServerOverloadedError

    x = np.zeros(FEATURES)
    admitted = []
    rejected = 0
    budget_s = 30.0
    with server.registry.eval_lock:  # dispatcher stalls mid-batch
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < budget_s:
            try:
                admitted.append(
                    server.submit("logreg", x, deadline_ms=600_000.0)
                )
            except ServerOverloadedError:
                rejected += 1
                break
        assert rejected, (
            f"queue bound {server.config.queue_bound} never rejected "
            f"within {budget_s}s ({len(admitted)} admitted)"
        )
    for future in admitted:  # released: every admitted request completes
        future.result(timeout=300)
    snap = server.metrics_snapshot()
    assert snap["overloads"] >= 1, snap
    return {"admitted": len(admitted), "rejections": snap["overloads"]}


def main():
    from moose_tpu.serving import InferenceServer, ServingConfig

    model, sk = build_logreg()
    # queue_bound sits ABOVE the closed-loop in-flight ceiling (64
    # clients x 1 outstanding request each) so phase 1 is genuinely
    # low-load, while staying small enough that phase 2 hits the bound
    # (and drains) quickly
    config = ServingConfig.from_env(
        max_batch=32, max_wait_ms=4.0, queue_bound=96
    )
    t0 = time.perf_counter()
    with InferenceServer(config=config) as server:
        server.register_model("logreg", model, row_shape=(FEATURES,))
        register_s = time.perf_counter() - t0
        summary = {"register_s": register_s}
        summary["low_load"] = low_load_phase(server, sk)
        summary["overload"] = overload_phase(server)
    print(json.dumps(summary), flush=True)
    print("serve_smoke: OK", file=sys.stderr)


if __name__ == "__main__":
    main()
