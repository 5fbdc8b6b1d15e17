"""CI fleet smoke (ISSUE 11): 3 blitzen replicas + the donner router,
open-loop clients, one chaos-kill + restart-from-snapshot and one
graceful rolling restart mid-run.

What it proves (the acceptance gates):

1. **Warm snapshots work end-to-end**: replica A registers fresh and
   writes the durable snapshot; replicas B and C cold-start FROM it
   (their stdout reports the restore and its duration, bounded below);
   after the chaos kill, B restarts from the snapshot again and its
   very first served request does not re-trace or re-validate —
   asserted from its /metrics Prometheus scrape
   (``retraces_after_warm_total == 0``,
   ``validating_after_warm_total == 0``) and /v1/metrics JSON.
2. **Zero dropped requests**: an open-loop client stream runs through
   donner for the whole scenario — SIGKILL of replica B mid-traffic,
   ejection, restart, readmission, then a SIGTERM rolling restart of
   replica C — and EVERY request ends 2xx (donner resolves all
   retryable failures on other replicas).
3. **Routing state machine**: donner's metrics show >= 1 ejection and
   >= 1 readmission; its /fleet view tracks the kill and the recovery.
4. **Bit-exactness across the fleet**: under MOOSE_TPU_FIXED_KEYS a
   canned single request answers bit-identically on every replica,
   fresh or snapshot-restored (quiet-phase probes: batching position
   affects share noise, so the probe never races open-loop traffic).
5. **Graceful drain**: the SIGTERM'd replica answers 503+Retry-After
   during its drain, exits 0, and leaves a refreshed snapshot behind.
6. **AOT-execute knob**: a final restore with
   ``MOOSE_TPU_SNAPSHOT_AOT_EXEC=0`` re-warms bit-identically, reports
   zero executed artifacts, and the summary carries the re-warm delta
   between the exec and no-exec paths.

Run time is dominated by replica A's fresh registration; B/C restore
from the snapshot in seconds (MOOSE_TPU_JIT=0 here, like
serve_smoke.py: this validates fleet SEMANTICS; compiled-path re-warm
time has no chip measurement yet, ROADMAP S7).

    JAX_PLATFORMS=cpu python scripts/fleet_smoke.py
"""

import json
import os
import re
import signal
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

FEATURES = 12
REWARM_BOUND_S = 300.0  # generous CI bound, not a measurement
LOAD_SECONDS = 30.0
# an eager logreg batch costs ~1 CPU-second: the open-loop rate must
# stay sustainable on a small CI box (3 replica processes share its
# cores) or the smoke measures scheduler thrash, not fleet semantics
REQUESTS_PER_SECOND = 1.0

ENV = {
    **os.environ,
    "JAX_PLATFORMS": "cpu",
    "MOOSE_TPU_JIT": "0",
    "MOOSE_TPU_FIXED_KEYS": "fleet-smoke",
    "MOOSE_TPU_ALLOW_WEAK_PRF": "1",
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

    def __init__(self, name, argv, extra_env=None):
        self.name = name
        self.lines = []
        self._lock = threading.Lock()
        self.popen = subprocess.Popen(
            argv, env={**ENV, **(extra_env or {})}, cwd=ROOT, text=True,
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

    def kill(self):
        self.popen.kill()
        self.popen.wait(timeout=30)

    def sigterm(self):
        self.popen.send_signal(signal.SIGTERM)


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


def http_post(url, payload, timeout=60):
    request = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"}, method="POST",
    )
    try:
        with urllib.request.urlopen(request, timeout=timeout) as resp:
            return resp.status, resp.read()
    except urllib.error.HTTPError as e:
        return e.code, e.read()
    except Exception as e:
        return None, type(e).__name__.encode()


def wait_ready(base, timeout_s=600):
    wait_until(
        lambda: http_get(base + "/readyz")[0] == 200,
        timeout_s, f"{base}/readyz == 200",
    )


def start_replica(name, port, onnx_path, snapshot_dir, extra_env=None):
    return Proc(name, [
        sys.executable, "-m", "moose_tpu.bin.blitzen",
        f"logreg={onnx_path}", "--features", f"logreg={FEATURES}",
        "--host", "127.0.0.1", "--port", str(port),
        "--snapshot-dir", str(snapshot_dir),
        "--drain-timeout-s", "60",
    ], extra_env=extra_env)


def prom_value(text, name):
    """Last sample of ``name`` in a Prometheus exposition (None when
    the series is absent — an absent counter means zero events)."""
    value = None
    for line in text.splitlines():
        if line.startswith(name) and not line.startswith("#"):
            value = float(line.rsplit(" ", 1)[1])
    return value


def main():
    from sklearn.linear_model import LogisticRegression

    from moose_tpu.predictors.sklearn_export import (
        logistic_regression_onnx,
    )

    rng = np.random.default_rng(3)
    x_train = rng.normal(size=(96, FEATURES))
    y_train = (rng.uniform(size=96) > 0.5).astype(int)
    sk = LogisticRegression().fit(x_train, y_train)

    workdir = Path(tempfile.mkdtemp(prefix="fleet_smoke_"))
    onnx_path = workdir / "logreg.onnx"
    onnx_path.write_bytes(
        logistic_regression_onnx(sk, FEATURES).encode()
    )
    snapshot_dir = workdir / "snapshots"

    ports = {"a": free_port(), "b": free_port(), "c": free_port()}
    bases = {k: f"http://127.0.0.1:{p}" for k, p in ports.items()}
    procs = {}
    summary = {}
    stop_load = threading.Event()
    t_all = time.perf_counter()
    try:
        # ---- phase 1: replica A registers fresh, writes the snapshot
        t0 = time.perf_counter()
        procs["a"] = start_replica(
            "a", ports["a"], onnx_path, snapshot_dir
        )
        wait_ready(bases["a"])
        summary["fresh_register_s"] = time.perf_counter() - t0
        assert (snapshot_dir / "CURRENT").exists(), (
            "replica A never wrote the warm-state snapshot"
        )

        # ---- phase 2: B and C cold-start FROM the snapshot
        t0 = time.perf_counter()
        for key in ("b", "c"):
            procs[key] = start_replica(
                key, ports[key], onnx_path, snapshot_dir
            )
        for key in ("b", "c"):
            wait_ready(bases[key])
            m = wait_until(
                lambda k=key: procs[k].grep(
                    r"restored warm state from .* in ([0-9.]+)s"
                ),
                30, f"replica {key} restore banner",
            )
            rewarm_s = float(m.group(1))
            assert rewarm_s < REWARM_BOUND_S, (
                f"replica {key} re-warm {rewarm_s}s "
                f"exceeds {REWARM_BOUND_S}s"
            )
            summary[f"rewarm_{key}_s"] = rewarm_s

        # ---- phase 3: quiet-phase bit-exactness probe across replicas
        probe_x = rng.normal(size=(1, FEATURES)).tolist()
        probe_bytes = {}
        for key, base in bases.items():
            status, body = http_post(
                base + "/v1/models/logreg:predict", {"x": probe_x}
            )
            assert status == 200, (key, status, body)
            probe_bytes[key] = body
        assert len(set(probe_bytes.values())) == 1, (
            "replicas disagree bitwise under MOOSE_TPU_FIXED_KEYS: "
            f"{probe_bytes}"
        )
        want = sk.predict_proba(np.asarray(probe_x))
        got = np.asarray(json.loads(probe_bytes["a"])["y"])
        assert float(np.abs(got - want).max()) < 5e-3

        # ---- phase 4: donner up, open-loop load through it
        procs["donner"] = Proc("donner", [
            sys.executable, "-m", "moose_tpu.bin.donner",
            "--replica", bases["a"], "--replica", bases["b"],
            "--replica", bases["c"],
            "--host", "127.0.0.1", "--port", "0",
            "--probe-interval-ms", "200", "--eject-after", "2",
            "--readmit-after", "2", "--retries", "6",
        ])
        m = wait_until(
            lambda: procs["donner"].grep(
                r"donner: routing .* on http://127\.0\.0\.1:(\d+)"
            ),
            30, "donner startup banner",
        )
        donner = f"http://127.0.0.1:{m.group(1)}"
        wait_ready(donner, timeout_s=30)

        outcomes = []
        outcomes_lock = threading.Lock()

        def one_request(i):
            x = rng.normal(size=(1, FEATURES)).tolist()
            t = time.perf_counter()
            status, body = http_post(
                donner + "/v1/models/logreg:predict", {"x": x},
                timeout=90,
            )
            with outcomes_lock:
                outcomes.append({
                    "i": i, "status": status,
                    "latency_s": time.perf_counter() - t,
                    "body": body[:120].decode(errors="replace"),
                })

        def open_loop():
            # OPEN loop: requests fire on the clock, never gated on
            # earlier completions — exactly the traffic shape that
            # exposes dropped requests during kill/eject windows.
            # Missed ticks are DROPPED, not replayed: on a slow CI box
            # a replay burst after a long phase would turn the open
            # loop into a thundering herd of catch-up threads
            i = 0
            period = 1.0 / REQUESTS_PER_SECOND
            next_t = time.perf_counter()
            while not stop_load.is_set():
                threading.Thread(
                    target=one_request, args=(i,), daemon=True
                ).start()
                i += 1
                next_t = max(
                    next_t + period, time.perf_counter()
                )
                time.sleep(max(0.0, next_t - time.perf_counter()))

        loader = threading.Thread(target=open_loop, daemon=True)
        t_load = time.perf_counter()
        loader.start()

        # ---- phase 5: chaos-kill replica B mid-traffic
        time.sleep(6)
        procs["b"].kill()
        wait_until(
            lambda: any(
                r["url"] == bases["b"] and r["ejected"]
                for r in json.loads(
                    http_get(donner + "/fleet")[1]
                )["replicas"]
            ),
            20, "donner ejecting the killed replica",
        )

        # ---- phase 6: restart B from the snapshot, wait readmission
        time.sleep(2)
        t0 = time.perf_counter()
        procs["b2"] = start_replica(
            "b2", ports["b"], onnx_path, snapshot_dir
        )
        wait_ready(bases["b"])
        summary["restart_to_ready_s"] = time.perf_counter() - t0
        m = wait_until(
            lambda: procs["b2"].grep(
                r"restored warm state from .* in ([0-9.]+)s"
            ),
            30, "restarted replica restore banner",
        )
        summary["rewarm_after_kill_s"] = float(m.group(1))
        assert summary["rewarm_after_kill_s"] < REWARM_BOUND_S
        wait_until(
            lambda: all(
                not r["ejected"]
                for r in json.loads(
                    http_get(donner + "/fleet")[1]
                )["replicas"]
            ),
            30, "donner readmitting the restarted replica",
        )

        # the restarted replica must actually serve from warm state:
        # wait until it has taken traffic, then hold its after-warm
        # counters to zero — scraped from /metrics, not in-process
        wait_until(
            lambda: (
                prom_value(
                    http_get(bases["b"] + "/metrics")[1].decode(),
                    "moose_tpu_serving_rows_total",
                ) or 0
            ) > 0,
            60, "restarted replica serving traffic",
        )
        prom = http_get(bases["b"] + "/metrics")[1].decode()
        assert not prom_value(
            prom, "moose_tpu_serving_retraces_after_warm_total"
        ), "restarted replica re-traced after its snapshot restore"
        assert not prom_value(
            prom, "moose_tpu_serving_validating_after_warm_total"
        ), "restarted replica re-validated after its snapshot restore"
        rewarm_gauge = prom_value(
            prom, "moose_tpu_serving_rewarm_seconds"
        )
        assert rewarm_gauge is not None and rewarm_gauge < REWARM_BOUND_S
        snap_json = json.loads(
            http_get(bases["b"] + "/v1/metrics")[1]
        )
        assert snap_json["retraces_after_warm"] == 0, snap_json
        assert snap_json["validating_after_warm"] == 0, snap_json

        # ---- phase 7: rolling restart — SIGTERM replica C (graceful)
        procs["c"].sigterm()
        # during the drain the replica answers 503 + Retry-After on
        # predicts and 503 on readiness; donner routes around it
        status, body = http_post(
            bases["c"] + "/v1/models/logreg:predict", {"x": probe_x},
            timeout=30,
        )
        if status is not None:  # it may already have exited
            assert status in (200, 503), (status, body)
            if status == 503:
                assert json.loads(body)["retryable"] is True
        assert procs["c"].popen.wait(timeout=300) == 0, (
            "graceful drain must exit 0"
        )
        assert procs["c"].grep(r"blitzen: drained \(clean\)"), (
            procs["c"].tail()
        )
        procs["c2"] = start_replica(
            "c2", ports["c"], onnx_path, snapshot_dir
        )
        wait_ready(bases["c"])

        # ---- phase 8: stop the load, settle, judge
        remaining = LOAD_SECONDS - (time.perf_counter() - t_load)
        if remaining > 0:
            time.sleep(remaining)
        stop_load.set()
        loader.join(timeout=10)

        # wait for REAL quiet: no outcome recorded for 2 consecutive
        # seconds AND the router reports zero in-flight forwards —
        # a straggler still bouncing through retries would co-batch
        # with the bit-exactness probe below and shift its share noise
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

        wait_until(settled, 120, "open-loop stragglers to land")

        # quiet-phase bit-exactness, again: with the open loop stopped
        # (co-batched rows shift batch positions, and share noise is
        # position-dependent), the snapshot-restored replica must still
        # answer the canned probe with the exact bytes the fleet agreed
        # on before the kill
        status, body = http_post(
            bases["b"] + "/v1/models/logreg:predict", {"x": probe_x}
        )
        assert status == 200 and body == probe_bytes["a"], (
            "snapshot-restored replica diverged bitwise: "
            f"{body!r} != {probe_bytes['a']!r}"
        )

        with outcomes_lock:
            done = list(outcomes)
        total = len(done)
        non_2xx = [o for o in done if o["status"] != 200]
        assert total >= LOAD_SECONDS * REQUESTS_PER_SECOND * 0.5, (
            f"open loop under-delivered: {total} requests"
        )
        assert not non_2xx, (
            f"{len(non_2xx)}/{total} requests dropped "
            f"(first: {non_2xx[:5]})"
        )

        donner_prom = http_get(donner + "/metrics")[1].decode()
        ejections = prom_value(
            donner_prom, "moose_tpu_donner_ejections_total"
        )
        readmissions = prom_value(
            donner_prom, "moose_tpu_donner_readmissions_total"
        )
        assert ejections and ejections >= 1, donner_prom
        assert readmissions and readmissions >= 1, donner_prom

        # ---- phase 9: AOT-execute re-warm delta — restart replica B
        # once more with the restored-artifact execution path disabled
        # (MOOSE_TPU_SNAPSHOT_AOT_EXEC=0) and compare re-warm times.
        # Under MOOSE_TPU_JIT=0 both restores are compile-free and the
        # delta is noise; on the compiled path the exec'd artifact
        # skips even the cached compile (tests/test_fleet.py proves the
        # "executed" verdict + bit-exactness).  Either way the knob and both restore paths
        # are exercised end-to-end here.
        procs["b2"].sigterm()
        procs["b2"].popen.wait(timeout=300)
        procs["b3"] = start_replica(
            "b3", ports["b"], onnx_path, snapshot_dir,
            extra_env={"MOOSE_TPU_SNAPSHOT_AOT_EXEC": "0"},
        )
        wait_ready(bases["b"])
        m = wait_until(
            lambda: procs["b3"].grep(
                r"restored warm state from .* in ([0-9.]+)s "
                r"\((\d+) probe digest\(s\) verified, (\d+) AOT "
                r"bucket\(s\) executed\)"
            ),
            60, "aot-exec-disabled restore banner",
        )
        summary["rewarm_aot_exec_s"] = summary["rewarm_after_kill_s"]
        summary["rewarm_aot_noexec_s"] = float(m.group(1))
        summary["rewarm_aot_delta_s"] = (
            summary["rewarm_aot_noexec_s"]
            - summary["rewarm_aot_exec_s"]
        )
        assert int(m.group(3)) == 0, (
            "MOOSE_TPU_SNAPSHOT_AOT_EXEC=0 must disable artifact "
            "execution"
        )
        status, body = http_post(
            bases["b"] + "/v1/models/logreg:predict", {"x": probe_x}
        )
        assert status == 200 and body == probe_bytes["a"], (
            "aot-exec-disabled restore diverged bitwise"
        )

        latencies = sorted(o["latency_s"] for o in done)
        summary.update({
            "requests": total,
            "dropped": 0,
            "ejections": ejections,
            "readmissions": readmissions,
            "p50_s": latencies[len(latencies) // 2],
            "p99_s": latencies[min(
                len(latencies) - 1, int(len(latencies) * 0.99)
            )],
            "elapsed_s": time.perf_counter() - t_all,
        })
        print("FLEET_SMOKE_OK " + json.dumps(summary))
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
