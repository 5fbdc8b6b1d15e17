"""CI continuous-training-loop smoke (ISSUE 18): 2 blitzen replicas
(--admin) + the donner router (--admin) + the in-process ControlPlane
driving a REAL resumable TrainingSession — train -> canary -> promote,
then train -> poisoned canary -> auto-rollback, all under sustained
open-loop multi-tenant load.

What it proves (the acceptance gates):

1. **The loop closes**: a TrainingSession generation (epoch 1) is
   staged onto every replica over the admin wire, canaried at 50%
   through donner's deterministic tenant hash buckets, watched against
   its SLOs, and PROMOTED — the base model flip is atomic and the new
   weights provably serve.
2. **Auto-rollback fires on a real SLO breach**: generation 2 (epoch 2,
   trained by the same resumable session) is poisoned via the replicas'
   chaos knob (every request to its serving name stalls past the p99
   SLO); the control plane detects the breach from donner's sliding
   per-generation window and rolls back — ``generation_rolled_back``
   flight event with ``reason == "latency"`` plus the
   ``moose_tpu_controlplane_*`` counters asserted from a Prometheus
   scrape.
3. **Zero dropped requests**: the open-loop tenant stream sees EVERY
   request end 2xx across staging, canary split installs, the promote
   flip, the poisoned canary, and the rollback flip.
4. **Last-good is bit-identical**: after the rollback, quiet-phase
   probes on every replica answer byte-identically to the promoted
   generation's quiet-phase probe (MOOSE_TPU_FIXED_KEYS).

MOOSE_TPU_JIT=0 like the other smokes: this validates loop SEMANTICS;
compiled-path promote/rollback timing has no chip measurement yet
(ROADMAP S7).

    JAX_PLATFORMS=cpu python scripts/loop_smoke.py
"""

import json
import os
import re
import socket
import subprocess
import sys
import tempfile
import threading
import time
import urllib.error
import urllib.request
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["MOOSE_TPU_JIT"] = "0"
os.environ["MOOSE_TPU_FIXED_KEYS"] = "loop-smoke"
os.environ["MOOSE_TPU_ALLOW_WEAK_PRF"] = "1"

FEATURES = 4
PARTIES = ["alice", "bob", "carole"]
# eager CPU service time is ~2-3s/request, so the open loop must stay
# well under saturation or the GOOD generation breaches its own SLO
# from queueing alone (observed at 0.75 rps: p99 > 2.5s, queue-wait
# p99 ~4s)
REQUESTS_PER_SECOND = 0.3
CHAOS_DELAY_MS = 10_000.0  # poisoned generation: +10s per request
P99_SLO_S = 8.0  # strict canary SLO: above baseline noise, below chaos

ENV = {
    **os.environ,
    "MOOSE_TPU_SERVE_MAX_BATCH": "4",
    "MOOSE_TPU_SERVE_MAX_WAIT_MS": "5",
    "PYTHONPATH": str(ROOT),
    "PYTHONUNBUFFERED": "1",
}


def free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


class Proc:
    """A replica/router subprocess with captured, greppable stdout."""

    def __init__(self, name, argv):
        self.name = name
        self.lines = []
        self._lock = threading.Lock()
        self.popen = subprocess.Popen(
            argv, env=ENV, cwd=ROOT, text=True,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        )
        threading.Thread(target=self._pump, daemon=True).start()

    def _pump(self):
        for line in self.popen.stdout:
            with self._lock:
                self.lines.append(line.rstrip())

    def grep(self, pattern):
        with self._lock:
            for line in self.lines:
                m = re.search(pattern, line)
                if m:
                    return m
        return None

    def tail(self, n=15):
        with self._lock:
            return "\n".join(self.lines[-n:])


def wait_until(predicate, timeout_s, what):
    deadline = time.perf_counter() + timeout_s
    while time.perf_counter() < deadline:
        result = predicate()
        if result:
            return result
        time.sleep(0.25)
    raise AssertionError(f"timed out after {timeout_s}s waiting for {what}")


def http_get(url, timeout=10):
    try:
        with urllib.request.urlopen(url, timeout=timeout) as resp:
            return resp.status, resp.read()
    except urllib.error.HTTPError as e:
        return e.code, e.read()
    except Exception:
        return None, b""


def http_post(url, payload, timeout=120, headers=None):
    request = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json", **(headers or {})},
        method="POST",
    )
    try:
        with urllib.request.urlopen(request, timeout=timeout) as resp:
            return resp.status, resp.read()
    except urllib.error.HTTPError as e:
        return e.code, e.read()
    except Exception as e:
        return None, type(e).__name__.encode()


def prom_value(text, name):
    value = None
    for line in text.splitlines():
        if line.startswith(name) and not line.startswith("#"):
            value = float(line.rsplit(" ", 1)[1])
    return value


def main():
    # heavyweight imports AFTER env pinning
    from moose_tpu import flight
    from moose_tpu import metrics as metrics_mod
    from moose_tpu.bin.donner import _assign_generation
    from moose_tpu.predictors.trainers import LogregSGDTrainer
    from moose_tpu.runtime import LocalMooseRuntime
    from moose_tpu.serving import (
        CanaryConfig,
        ControlPlane,
        HttpFleetClient,
        SessionGenerationProducer,
    )
    from moose_tpu.storage import FilesystemStorage
    from moose_tpu.training import (
        CheckpointStore,
        TrainingConfig,
        TrainingSession,
    )
    from moose_tpu.training.export import logreg_onnx_bytes

    rng = np.random.default_rng(18)
    workdir = Path(tempfile.mkdtemp(prefix="loop_smoke_"))
    onnx_path = workdir / "base.onnx"
    onnx_path.write_bytes(
        logreg_onnx_bytes(rng.normal(size=(FEATURES, 1)) * 0.5)
    )
    snapshot_dir = workdir / "snapshots"

    # the long-lived training session: 3 parties, durable secret-shared
    # checkpoints, in THIS process (the control-plane process)
    stores = {
        p: CheckpointStore(
            FilesystemStorage(str(workdir / "ckpt" / p)),
            party=p, retain=2,
        )
        for p in PARTIES
    }
    runtime = LocalMooseRuntime(
        identities=PARTIES, storage_mapping=stores, use_jit=False
    )
    from moose_tpu.training.session import LocalTrainingCluster

    x_train = rng.normal(size=(8, FEATURES)) * 0.5
    y_train = (rng.uniform(size=(8, 1)) > 0.5).astype(np.float64)
    session = TrainingSession(
        LogregSGDTrainer(n_features=FEATURES, learning_rate=0.1),
        LocalTrainingCluster(runtime, PARTIES),
        TrainingConfig(epochs=1),
    )
    producer = SessionGenerationProducer(
        session, x_train, y_train, epochs_per_generation=1
    )

    ports = {"a": free_port(), "b": free_port()}
    bases = {k: f"http://127.0.0.1:{p}" for k, p in ports.items()}
    procs = {}
    summary = {}
    stop_load = threading.Event()
    outcomes = []
    outcomes_lock = threading.Lock()
    t_all = time.perf_counter()

    # 2 base-bucket + 2 canary-bucket tenants ('base' sorts first, so
    # [0, 0.5) of the hash ring is base at every 50/50 split)
    probe_split = {"base": 0.5, "zzz": 0.5}
    base_tenants = [
        t for t in (f"tenant-{i}" for i in range(10_000))
        if _assign_generation("m", t, probe_split) == "base"
    ][:2]
    canary_tenants = [
        t for t in (f"tenant-{i}" for i in range(10_000))
        if _assign_generation("m", t, probe_split) != "base"
    ][:2]
    tenants = base_tenants + canary_tenants

    try:
        # ---- phase 1: the fleet comes up (A fresh, B from snapshot)
        t0 = time.perf_counter()
        procs["a"] = Proc("a", [
            sys.executable, "-m", "moose_tpu.bin.blitzen",
            f"m={onnx_path}", "--features", f"m={FEATURES}",
            "--host", "127.0.0.1", "--port", str(ports["a"]),
            "--snapshot-dir", str(snapshot_dir),
            "--drain-timeout-s", "60", "--admin",
        ])
        wait_until(
            lambda: http_get(bases["a"] + "/readyz")[0] == 200,
            600, "replica a ready",
        )
        summary["fresh_register_s"] = time.perf_counter() - t0
        procs["b"] = Proc("b", [
            sys.executable, "-m", "moose_tpu.bin.blitzen",
            f"m={onnx_path}", "--features", f"m={FEATURES}",
            "--host", "127.0.0.1", "--port", str(ports["b"]),
            "--snapshot-dir", str(snapshot_dir),
            "--drain-timeout-s", "60", "--admin",
        ])
        wait_until(
            lambda: http_get(bases["b"] + "/readyz")[0] == 200,
            600, "replica b ready",
        )

        procs["donner"] = Proc("donner", [
            sys.executable, "-m", "moose_tpu.bin.donner",
            "--replica", bases["a"], "--replica", bases["b"],
            "--host", "127.0.0.1", "--port", "0",
            "--probe-interval-ms", "200", "--retries", "6", "--admin",
        ])
        m = wait_until(
            lambda: procs["donner"].grep(
                r"donner: routing .* on http://127\.0\.0\.1:(\d+)"
            ),
            30, "donner startup banner",
        )
        donner = f"http://127.0.0.1:{m.group(1)}"
        wait_until(
            lambda: http_get(donner + "/readyz")[0] == 200,
            30, "donner ready",
        )

        client = HttpFleetClient(
            donner, [bases["a"], bases["b"]], timeout_s=600.0
        )
        # two planes over the SAME producer/fleet: the good plane gets
        # a latency SLO the eager CPU path can actually meet; the
        # strict plane is the one the poisoned generation must breach
        plane_good = ControlPlane(client, "m", CanaryConfig(
            fraction=0.5, watch_s=3.0, min_requests=2,
            p99_slo_s=60.0, error_rate_slo=0.5, poll_s=0.25,
            timeout_s=600.0, cost_drift_max=1000,
        ))
        plane_strict = ControlPlane(client, "m", CanaryConfig(
            fraction=0.5, watch_s=3.0, min_requests=2,
            p99_slo_s=P99_SLO_S, error_rate_slo=0.5, poll_s=0.25,
            timeout_s=600.0, cost_drift_max=1000,
        ))

        def probe(base_url):
            status, body = http_post(
                base_url + "/v1/models/m:predict",
                {"x": [[0.25, -0.1, 0.3, 0.05]]},
            )
            assert status == 200, (base_url, status, body)
            return body

        y_seed = probe(bases["a"])
        assert probe(bases["b"]) == y_seed, "fleet disagrees at start"

        # ---- open-loop load: requests fire on the clock across the
        # tenant ring; missed ticks are dropped, never replayed
        def one_request(i, tenant):
            t = time.perf_counter()
            status, body = http_post(
                donner + "/v1/models/m:predict",
                {"x": [[0.1, 0.2, -0.3, 0.4]]},
                timeout=120, headers={"X-Moose-Tenant": tenant},
            )
            with outcomes_lock:
                outcomes.append({
                    "i": i, "tenant": tenant, "status": status,
                    "latency_s": time.perf_counter() - t,
                    "body": body[:120].decode(errors="replace"),
                })

        def open_loop():
            i = 0
            period = 1.0 / REQUESTS_PER_SECOND
            next_t = time.perf_counter()
            while not stop_load.is_set():
                threading.Thread(
                    target=one_request,
                    args=(i, tenants[i % len(tenants)]), daemon=True,
                ).start()
                i += 1
                next_t = max(next_t + period, time.perf_counter())
                time.sleep(max(0.0, next_t - time.perf_counter()))

        loader = threading.Thread(target=open_loop, daemon=True)
        loader.start()

        # ---- phase 2: train generation 1 -> canary -> PROMOTE
        t0 = time.perf_counter()
        report1 = plane_good.run_loop(producer, generations=1)[0]
        summary["generation1_s"] = time.perf_counter() - t0
        assert report1["promoted"], report1
        assert report1["generation"] == "g0001", report1
        summary["promote_s"] = report1["promote_s"]
        assert session.last_report["final_epoch"] == 1

        # ---- phase 3: poison generation 2, train it -> AUTO-ROLLBACK
        for base_url in bases.values():
            status, body = http_post(
                base_url + "/admin/chaos",
                {"match": "@g0002", "delay_ms": CHAOS_DELAY_MS},
            )
            assert status == 200, (base_url, body)
        t0 = time.perf_counter()
        report2 = plane_strict.run_loop(producer, generations=1)[0]
        summary["generation2_s"] = time.perf_counter() - t0
        assert not report2["promoted"], report2
        assert report2["generation"] == "g0002", report2
        assert report2["reason"] == "latency", report2
        assert report2["observed"]["p99_s"] > P99_SLO_S, report2
        summary["rollback_s"] = report2["rollback_s"]
        assert session.last_report["final_epoch"] == 2

        # ---- phase 4: stop the load, settle, judge
        stop_load.set()
        loader.join(timeout=10)

        def settled():
            with outcomes_lock:
                count = len(outcomes)
            time.sleep(2.0)
            with outcomes_lock:
                if len(outcomes) != count:
                    return False
            fleet = json.loads(http_get(donner + "/fleet")[1])
            return all(
                r["in_flight"] == 0 for r in fleet["replicas"]
            )

        wait_until(settled, 180, "open-loop stragglers to land")

        with outcomes_lock:
            done = list(outcomes)
        total = len(done)
        non_2xx = [o for o in done if o["status"] != 200]
        assert total >= 10, f"open loop under-delivered: {total}"
        assert not non_2xx, (
            f"{len(non_2xx)}/{total} requests dropped "
            f"(first: {non_2xx[:5]})"
        )

        # last-good is bit-identical on every replica: the fleet serves
        # the PROMOTED generation-1 weights, not the seed, not g0002
        y_good = probe(bases["a"])
        assert probe(bases["b"]) == y_good, "fleet disagrees after loop"
        assert y_good != y_seed, "generation 1 never actually served"

        # route table clean, staging names retired
        fleet_view = json.loads(http_get(donner + "/fleet")[1])
        assert not fleet_view["routes"].get("m", {}).get("weights")
        for base_url in bases.values():
            status, body = http_post(
                base_url + "/v1/models/m@g0002:predict",
                {"x": [[0.0, 0.0, 0.0, 0.0]]},
            )
            assert status == 404, (base_url, status, body)
            assert json.loads(body)["error"] == "ModelNotFoundError"

        # the WHY, from the flight recorder and a Prometheus scrape of
        # the control-plane process
        events = flight.get_recorder().events(party="controlplane")
        kinds = {
            (e["kind"], e.get("generation")) for e in events
        }
        assert ("generation_promoted", "g0001") in kinds, kinds
        assert ("generation_rolled_back", "g0002") in kinds, kinds
        rolled = [
            e for e in events
            if e["kind"] == "generation_rolled_back"
        ][-1]
        assert rolled["reason"] == "latency", rolled
        scrape = metrics_mod.render_prometheus()
        assert prom_value(
            scrape,
            'moose_tpu_controlplane_generations_total{'
            'outcome="promoted"}',
        ) == 1.0, "promoted counter missing from scrape"
        assert prom_value(
            scrape,
            'moose_tpu_controlplane_generations_total{'
            'outcome="rolled_back"}',
        ) == 1.0, "rolled_back counter missing from scrape"
        assert prom_value(
            scrape,
            'moose_tpu_controlplane_slo_breaches_total{'
            'reason="latency"}',
        ) == 1.0, "breach counter missing from scrape"
        # ... and donner's per-generation accounting on ITS scrape
        donner_prom = http_get(donner + "/metrics")[1].decode()
        assert "moose_tpu_donner_generation_requests_total" in (
            donner_prom
        ), "per-generation request counter missing from donner scrape"

        latencies = sorted(o["latency_s"] for o in done)
        summary.update({
            "requests": total,
            "dropped": 0,
            "generations": 2,
            "promoted": 1,
            "rolled_back": 1,
            "resumes": session.last_report["resumes"],
            "p50_s": latencies[len(latencies) // 2],
            "p99_s": latencies[min(
                len(latencies) - 1, int(len(latencies) * 0.99)
            )],
            "elapsed_s": time.perf_counter() - t_all,
        })
        print("LOOP_SMOKE_OK " + json.dumps(summary))
    except BaseException:
        for name, proc in procs.items():
            print(f"---- {name} tail ----\n{proc.tail()}", flush=True)
        raise
    finally:
        stop_load.set()
        for proc in procs.values():
            if proc.popen.poll() is None:
                proc.popen.kill()


if __name__ == "__main__":
    main()
