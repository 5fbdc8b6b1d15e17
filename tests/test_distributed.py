"""Distributed execution tests: role-filtered workers over the networking
backends — the reference's AsyncTestRuntime-style coverage (one worker per
identity in a single process, real Send/Recv code paths, fake or real
wire)."""

import os
import threading

import numpy as np
import pytest

# the test "cluster" lives in one process/trust domain, so the
# non-cryptographic default PRF is acceptable here; real deployments
# must set MOOSE_TPU_PRF=threefry (worker.execute_role enforces this)
os.environ.setdefault("MOOSE_TPU_ALLOW_WEAK_PRF", "1")

import moose_tpu as pm
from moose_tpu.compilation import DEFAULT_PASSES, compile_computation
from moose_tpu.compilation.lowering import arg_specs_from_arguments
from moose_tpu.distributed.networking import LocalNetworking
from moose_tpu.distributed.worker import execute_role
from moose_tpu.edsl import tracer


def _cpu_subprocess_env() -> dict:
    """Env for worker subprocesses, pinned to the CPU backend.

    A chip belongs to one process at a time, so worker subprocesses
    never ask for one.  The virtual-device XLA flag the conftest
    exports is also stripped — three workers × 12 device thread pools
    oversubscribes the host."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = ""
    return env


def _players():
    alice = pm.host_placement("alice")
    bob = pm.host_placement("bob")
    carole = pm.host_placement("carole")
    rep = pm.replicated_placement("rep", players=[alice, bob, carole])
    return alice, bob, carole, rep


def _secure_dot_comp():
    alice, bob, carole, rep = _players()

    @pm.computation
    def comp(
        x: pm.Argument(placement=alice, dtype=pm.float64),
        w: pm.Argument(placement=bob, dtype=pm.float64),
    ):
        with alice:
            xf = pm.cast(x, dtype=pm.fixed(14, 23))
        with bob:
            wf = pm.cast(w, dtype=pm.fixed(14, 23))
        with rep:
            y = pm.dot(xf, wf)
        with carole:
            out = pm.cast(y, dtype=pm.float64)
        return out

    return comp


def _run_workers(comp, identities, arguments, networking_factory,
                 storages=None):
    results = {}
    errors = {}

    def work(identity):
        try:
            net = networking_factory(identity)
            results[identity] = execute_role(
                comp,
                identity,
                (storages or {}).get(identity, {}),
                arguments,
                net,
                session_id="sess-1",
                timeout=60.0,
            )
        except Exception as e:  # pragma: no cover - surfaced in assert
            errors[identity] = e

    threads = [
        threading.Thread(target=work, args=(i,), daemon=True)
        for i in identities
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not errors, errors
    return results


def test_three_workers_secure_dot_local_networking():
    rng = np.random.default_rng(0)
    x = rng.normal(size=(4, 3))
    w = rng.normal(size=(3, 2))
    args = {"x": x, "w": w}
    traced = tracer.trace(_secure_dot_comp())
    compiled = compile_computation(
        traced, DEFAULT_PASSES, arg_specs=arg_specs_from_arguments(args)
    )

    net = LocalNetworking()
    results = _run_workers(
        compiled, ["alice", "bob", "carole"], args, lambda i: net
    )
    # output lands on carole
    outs = {
        k: v
        for r in results.values()
        for k, v in r["outputs"].items()
    }
    assert len(outs) == 1
    (val,) = outs.values()
    np.testing.assert_allclose(val, x @ w, atol=1e-5)
    # every worker reports a timing (telemetry parity,
    # choreography/grpc.rs:186-192)
    for r in results.values():
        assert r["elapsed_time_micros"] > 0


def test_worker_save_hits_own_storage_only():
    alice, bob, carole, rep = _players()

    @pm.computation
    def comp(x: pm.Argument(placement=alice, dtype=pm.float64)):
        with alice:
            y = x + x
        with bob:
            res = pm.save("y", y)
        return res

    x = np.array([1.0, 2.0])
    traced = tracer.trace(comp)
    compiled = compile_computation(
        traced, DEFAULT_PASSES,
        arg_specs=arg_specs_from_arguments({"x": x}),
    )
    net = LocalNetworking()
    storages = {"alice": {}, "bob": {}, "carole": {}}
    _run_workers(
        compiled, ["alice", "bob", "carole"], {"x": x},
        lambda i: net, storages,
    )
    np.testing.assert_allclose(storages["bob"]["y"], [2.0, 4.0])
    assert "y" not in storages["alice"]


def test_three_workers_over_native_tcp():
    """Secure dot across 3 workers over the C++ TCP transport
    (vixen-equivalent, networking/tcpstream.rs)."""
    from moose_tpu.distributed.networking import TcpNetworking

    rng = np.random.default_rng(1)
    x = rng.normal(size=(3, 4))
    w = rng.normal(size=(4, 2))
    args = {"x": x, "w": w}
    traced = tracer.trace(_secure_dot_comp())
    compiled = compile_computation(
        traced, DEFAULT_PASSES, arg_specs=arg_specs_from_arguments(args)
    )
    base = 21300
    endpoints = {
        "alice": f"127.0.0.1:{base}",
        "bob": f"127.0.0.1:{base + 1}",
        "carole": f"127.0.0.1:{base + 2}",
    }
    nets = {
        i: TcpNetworking(i, endpoints).start() for i in endpoints
    }
    try:
        results = _run_workers(
            compiled, list(endpoints), args, lambda i: nets[i]
        )
        outs = {
            k: v for r in results.values() for k, v in r["outputs"].items()
        }
        (val,) = outs.values()
        np.testing.assert_allclose(val, x @ w, atol=1e-5)
    finally:
        for net in nets.values():
            net.stop()


def test_grpc_cluster_end_to_end():
    """3 gRPC worker servers in-process + client runtime: the reference's
    comet/GrpcMooseRuntime path (choreography/grpc.rs, execution/grpc.rs)."""
    from moose_tpu.distributed.choreography import WorkerServer
    from moose_tpu.distributed.client import GrpcClientRuntime

    identities = ["alice", "bob", "carole"]
    # bind on port 0 -> server picks free ports; then share the table
    servers = {}
    endpoints = {}
    try:
        for i in identities:
            srv = WorkerServer(i, 0, {}).start()
            servers[i] = srv
            endpoints[i] = f"127.0.0.1:{srv.port}"
        for srv in servers.values():
            srv.endpoints.update(endpoints)
            srv.networking._endpoints.update(endpoints)

        rng = np.random.default_rng(2)
        x = rng.normal(size=(5, 3))
        w = rng.normal(size=(3, 1))
        traced = tracer.trace(_secure_dot_comp())
        runtime = GrpcClientRuntime(endpoints)
        outputs, timings = runtime.run_computation(
            traced, {"x": x, "w": w}
        )
        (val,) = outputs.values()
        np.testing.assert_allclose(val, x @ w, atol=1e-5)
        assert set(timings) == set(identities)
        assert all(t > 0 for t in timings.values())

        # duplicate session protection
        # (execution/asynchronous.rs:571-576)
        from moose_tpu.serde import serialize_computation
        from moose_tpu.compilation import compile_computation as cc
        compiled = cc(
            traced, DEFAULT_PASSES,
            arg_specs=arg_specs_from_arguments({"x": x, "w": w}),
        )
        blob = serialize_computation(compiled)
        client = servers["alice"]
        client._launch(
            __import__("msgpack").packb(
                {"session_id": "dup", "computation": blob,
                 "arguments": {}},
                use_bin_type=True,
            )
        )
        with pytest.raises(Exception):
            client._launch(
                __import__("msgpack").packb(
                    {"session_id": "dup", "computation": blob,
                     "arguments": {}},
                    use_bin_type=True,
                )
            )
    finally:
        for srv in servers.values():
            srv.stop()


def test_filesystem_storage(tmp_path):
    from moose_tpu.storage import FilesystemStorage

    store = FilesystemStorage(tmp_path)
    arr = np.arange(6, dtype=np.float64).reshape(2, 3)
    store.save("weights", arr)
    assert "weights" in store
    np.testing.assert_array_equal(store.load("weights"), arr)

    (tmp_path / "data.csv").write_text("x,y,z\n1,2,3\n4,5,6\n")
    full = store.load("data")
    np.testing.assert_array_equal(full, [[1, 2, 3], [4, 5, 6]])
    sel = store.load("data", '{"select_columns": ["z", "x"]}')
    np.testing.assert_array_equal(sel, [[3, 1], [6, 4]])

    with pytest.raises(Exception):
        store.load("missing")


def test_dasher_cli(tmp_path):
    import subprocess
    import sys
    import json

    from moose_tpu.textual import to_textual

    traced = tracer.trace(_secure_dot_comp())
    src = tmp_path / "comp.moose"
    src.write_text(to_textual(traced))
    rng = np.random.default_rng(3)
    x = rng.normal(size=(2, 3)).tolist()
    w = rng.normal(size=(3, 1)).tolist()
    args_file = tmp_path / "args.json"
    args_file.write_text(json.dumps({"x": x, "w": w}))
    out = subprocess.run(
        [sys.executable, "-m", "moose_tpu.bin.dasher", str(src),
         "--args", str(args_file)],
        capture_output=True, text=True, timeout=300,
        env=_cpu_subprocess_env(),
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert "us" in out.stdout
    assert "output" in out.stdout


@pytest.mark.slow
def test_comet_cluster_multiprocess(tmp_path):
    """3 comet worker PROCESSES + cometctl run: the reference's
    deployment shape (bin/comet, the reference's benchmark README)."""
    import json
    import os
    import signal
    import subprocess
    import sys
    import time

    from moose_tpu.textual import to_textual

    base = 21500
    endpoints = {
        "alice": f"127.0.0.1:{base}",
        "bob": f"127.0.0.1:{base + 1}",
        "carole": f"127.0.0.1:{base + 2}",
    }
    ep_spec = ",".join(f"{k}={v}" for k, v in endpoints.items())
    env = _cpu_subprocess_env()
    procs = [
        subprocess.Popen(
            [sys.executable, "-m", "moose_tpu.bin.comet",
             "--identity", name, "--port", str(base + i),
             "--endpoints", ep_spec],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, env=env,
        )
        for i, (name, _) in enumerate(endpoints.items())
    ]
    try:
        traced = tracer.trace(_secure_dot_comp())
        comp_file = tmp_path / "comp.moose"
        comp_file.write_text(to_textual(traced))
        rng = np.random.default_rng(4)
        x = rng.normal(size=(2, 3))
        w = rng.normal(size=(3, 1))
        (tmp_path / "args.json").write_text(
            json.dumps({"x": x.tolist(), "w": w.tolist()})
        )
        session = tmp_path / "run.session"
        session.write_text(
            'session_id = "t1"\n'
            "[computation]\n"
            f'path = "{comp_file}"\n'
            "[roles]\n"
            + "".join(
                f'{k} = "{v}"\n' for k, v in endpoints.items()
            )
        )
        # wait for workers to come up
        deadline = time.time() + 60
        import grpc

        for ep in endpoints.values():
            while True:
                try:
                    grpc.channel_ready_future(
                        grpc.insecure_channel(ep)
                    ).result(timeout=5)
                    break
                except Exception:
                    if time.time() > deadline:
                        raise
        out = subprocess.run(
            [sys.executable, "-m", "moose_tpu.bin.cometctl", "run",
             str(session), "--args", str(tmp_path / "args.json"),
             "--json"],
            capture_output=True, text=True, timeout=240, env=env,
        )
        if out.returncode != 0:
            # surface worker-side logs: the client error alone (usually a
            # receive timeout) doesn't say which worker failed or why
            logs = []
            for p, name in zip(procs, endpoints):
                p.send_signal(signal.SIGTERM)
                try:
                    _, err = p.communicate(timeout=10)
                except subprocess.TimeoutExpired:
                    p.kill()
                    _, err = p.communicate()
                logs.append(f"--- {name} ---\n{err.decode()[-2000:]}")
            raise AssertionError(
                out.stderr[-3000:] + "\n" + "\n".join(logs)
            )
        outputs = json.loads(out.stdout.strip().splitlines()[-1])
        (got,) = (np.asarray(v) for v in outputs.values())
        assert got.shape == (2, 1)
        np.testing.assert_allclose(got, x @ w, atol=1e-4)
        # per-role timings surfaced on stderr
        assert "us" in out.stderr
    finally:
        for p in procs:
            p.send_signal(signal.SIGTERM)
        for p in procs:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()


def test_rudolph_filesystem_choreography(tmp_path):
    """rudolph's launch-from-file path (reference
    choreography/filesystem.rs): a .session TOML names a textual
    computation + role table; every worker launches its role and the
    results are retrieved over choreography."""
    import json

    from moose_tpu.bin.rudolph import _launch_from_file
    from moose_tpu.distributed.choreography import (
        ChoreographyClient,
        WorkerServer,
    )
    from moose_tpu.textual import to_textual

    rng = np.random.default_rng(6)
    x = rng.normal(size=(3, 2))
    w = rng.normal(size=(2, 1))
    compiled = compile_computation(
        tracer.trace(_secure_dot_comp()), DEFAULT_PASSES,
        arg_specs=arg_specs_from_arguments({"x": x, "w": w}),
    )
    (tmp_path / "comp.moose").write_text(to_textual(compiled))
    (tmp_path / "args.json").write_text(
        json.dumps({"x": x.tolist(), "w": w.tolist()})
    )

    servers, endpoints = {}, {}
    try:
        for i in ("alice", "bob", "carole"):
            srv = WorkerServer(i, 0, {}).start()
            servers[i] = srv
            endpoints[i] = f"127.0.0.1:{srv.port}"

        session = tmp_path / "run.session"
        session.write_text(
            'session_id = "rudolph-1"\n'
            'arguments = "args.json"\n'
            "[computation]\n"
            'path = "comp.moose"\n'
            "[roles]\n"
            + "".join(f'{k} = "{v}"\n' for k, v in endpoints.items())
        )

        import logging

        log = logging.getLogger("test-rudolph")
        for srv in servers.values():
            _launch_from_file(srv, session, log)

        outputs = {}
        for name, endpoint in endpoints.items():
            result = ChoreographyClient(endpoint).retrieve(
                "rudolph-1", timeout=60.0
            )
            assert "error" not in result, (name, result)
            from moose_tpu.serde import deserialize_value

            for out_name, blob in (result.get("outputs") or {}).items():
                outputs[out_name] = deserialize_value(blob)
        (val,) = outputs.values()
        np.testing.assert_allclose(np.asarray(val), x @ w, atol=1e-4)
    finally:
        for srv in servers.values():
            srv.stop()


def test_worker_rejects_uncompiled_and_unnetworked_graphs():
    from moose_tpu.compilation import compile_computation
    from moose_tpu.distributed.networking import LocalNetworking
    from moose_tpu.errors import KernelError

    traced = tracer.trace(_secure_dot_comp())
    with pytest.raises(KernelError, match="uncompiled"):
        execute_role(traced, "alice", {}, {}, LocalNetworking(), "s-x")

    x = np.ones((2, 2))
    w = np.ones((2, 1))
    lowered = compile_computation(
        traced, ["typing", "lowering", "prune", "toposort"],  # no networking
        arg_specs=arg_specs_from_arguments({"x": x, "w": w}),
    )
    with pytest.raises(KernelError, match="networking"):
        execute_role(
            lowered, "alice", {}, {"x": x, "w": w},
            LocalNetworking(), "s-y",
        )


def test_abort_cancels_running_session():
    """AbortComputation stops a running session: retrievers unblock with
    an 'aborted' error and the execute thread exits at the next op
    boundary (the reference's abort handler is unimplemented)."""
    import msgpack

    from moose_tpu.distributed.choreography import WorkerServer
    from moose_tpu.errors import SessionAbortedError
    from moose_tpu.serde import serialize_computation

    # cooperative cancel at the worker level: a pre-set event aborts
    # before the first op executes
    x = np.ones((2, 2))
    compiled = compile_computation(
        tracer.trace(_secure_dot_comp()), DEFAULT_PASSES,
        arg_specs=arg_specs_from_arguments({"x": x, "w": x[:, :1]}),
    )
    ev = threading.Event()
    ev.set()
    with pytest.raises(SessionAbortedError, match="aborted"):
        execute_role(
            compiled, "alice", {}, {"x": x, "w": x[:, :1]},
            LocalNetworking(), "s-abort", cancel=ev,
        )

    # end-to-end: launch on one worker WITH its argument so it advances
    # into a blocked Receive (the other parties never launch), abort,
    # and both the retriever and the blocked execute thread unwind fast
    from moose_tpu.serde import serialize_value

    srv = WorkerServer("alice", 0, {}).start()
    try:
        srv.endpoints["alice"] = f"127.0.0.1:{srv.port}"
        srv.networking._endpoints.update(srv.endpoints)
        blob = serialize_computation(compiled)
        srv._launch(msgpack.packb(
            {"session_id": "ab-1", "computation": blob,
             "arguments": {"x": serialize_value(x)}},
            use_bin_type=True,
        ))
        import time as _t

        _t.sleep(1.0)  # let the thread reach its blocked Receive
        srv._abort(msgpack.packb({"session_id": "ab-1"},
                                 use_bin_type=True))
        t0 = _t.monotonic()
        result = msgpack.unpackb(
            srv._results.get("ab-1", timeout=10.0), raw=False
        )
        assert "error" in result and "abort" in result["error"], result
        assert _t.monotonic() - t0 < 5.0
    finally:
        srv.stop()


def _start_cluster(identities, **kwargs):
    """In-process WorkerServers on free ports with a shared endpoint
    table; returns (servers, endpoints)."""
    from moose_tpu.distributed.choreography import WorkerServer

    servers, endpoints = {}, {}
    for i in identities:
        srv = WorkerServer(i, 0, {}, **kwargs).start()
        servers[i] = srv
        endpoints[i] = f"127.0.0.1:{srv.port}"
    for srv in servers.values():
        srv.endpoints.update(endpoints)
        srv.networking._endpoints.update(endpoints)
    return servers, endpoints


def test_worker_error_fans_out_abort_to_peers():
    """First root-cause error on one worker aborts the session on every
    peer fast — the cross-worker extension of the reference's
    join_on_first_error (execution/asynchronous.rs:27-74): peers must
    not sit in blocked receives until the cell-store timeout."""
    import time

    import msgpack

    from moose_tpu.serde import serialize_computation, serialize_value

    alice, bob, carole, rep = _players()

    @pm.computation
    def comp(
        x: pm.Argument(placement=alice, dtype=pm.float64),
        w: pm.Argument(placement=bob, dtype=pm.float64),
        b: pm.Argument(placement=carole, dtype=pm.float64),
    ):
        with alice:
            xf = pm.cast(x, dtype=pm.fixed(14, 23))
        with bob:
            wf = pm.cast(w, dtype=pm.fixed(14, 23))
        with rep:
            y = pm.dot(xf, wf)
        with carole:
            out = pm.cast(y, dtype=pm.float64) + b
        return out

    rng = np.random.default_rng(7)
    x = rng.normal(size=(2, 3))
    w = rng.normal(size=(3, 1))
    b = rng.normal(size=(2, 1))
    all_args = {"x": x, "w": w, "b": b}
    compiled = compile_computation(
        tracer.trace(comp), DEFAULT_PASSES,
        arg_specs=arg_specs_from_arguments(all_args),
    )
    blob = serialize_computation(compiled)

    servers, _ = _start_cluster(["alice", "bob", "carole"])
    try:
        # launch everywhere but WITHOUT carole's argument: her Input op
        # raises immediately — the root cause that must fan out
        sent = {
            k: serialize_value(v) for k, v in all_args.items() if k != "b"
        }
        for srv in servers.values():
            srv._launch_inner(msgpack.packb(
                {"session_id": "fo-1", "computation": blob,
                 "arguments": sent},
                use_bin_type=True,
            ))
        t0 = time.monotonic()
        results = {
            name: msgpack.unpackb(
                srv._results.get("fo-1", timeout=10.0), raw=False
            )
            for name, srv in servers.items()
        }
        elapsed = time.monotonic() - t0
        assert elapsed < 5.0, f"abort fanout took {elapsed:.1f}s"
        assert "missing argument" in results["carole"]["error"]
        for peer in ("alice", "bob"):
            assert "aborted by carole" in results[peer]["error"], results
    finally:
        for srv in servers.values():
            srv.stop()


def test_dead_peer_trips_failure_detector():
    """A worker that is unreachable while a session runs fails the
    session on the live workers within the detector budget — a killed
    party must not leave the others blocked until the receive timeout."""
    import time

    import msgpack

    from moose_tpu.serde import serialize_computation, serialize_value

    x = np.ones((2, 2))
    w = x[:, :1]
    compiled = compile_computation(
        tracer.trace(_secure_dot_comp()), DEFAULT_PASSES,
        arg_specs=arg_specs_from_arguments({"x": x, "w": w}),
    )
    blob = serialize_computation(compiled)

    fd = dict(ping_interval=0.25, ping_misses=3, startup_grace=1.5)
    servers, endpoints = _start_cluster(["alice", "bob"], **fd)
    try:
        # carole is dead from the start: a reserved port nothing listens on
        for srv in servers.values():
            srv.endpoints["carole"] = "127.0.0.1:9"
            srv.networking._endpoints["carole"] = "127.0.0.1:9"
        args = {"x": serialize_value(x), "w": serialize_value(w)}
        t0 = time.monotonic()
        for srv in servers.values():
            srv._launch_inner(msgpack.packb(
                {"session_id": "fd-1", "computation": blob,
                 "arguments": args},
                use_bin_type=True,
            ))
        results = {
            name: msgpack.unpackb(
                srv._results.get("fd-1", timeout=15.0), raw=False
            )
            for name, srv in servers.items()
        }
        elapsed = time.monotonic() - t0
        assert elapsed < 5.0, f"failure detection took {elapsed:.1f}s"
        for name, result in results.items():
            assert "error" in result, (name, result)
            assert (
                "unreachable" in result["error"]
                or "aborted by" in result["error"]
                or "aborted on peer" in result["error"]
            ), (name, result)
    finally:
        for srv in servers.values():
            srv.stop()


@pytest.mark.slow
def test_sigkilled_comet_worker_fails_session_everywhere(tmp_path):
    """The done-criterion for distributed failure handling: SIGKILL a
    real comet worker PROCESS mid-session; the surviving workers' failure
    detectors must fail the session in well under the receive timeout."""
    import signal
    import subprocess
    import sys
    import time

    from moose_tpu.distributed.choreography import ChoreographyClient
    from moose_tpu.serde import serialize_computation

    base = 21700
    endpoints = {
        "alice": f"127.0.0.1:{base}",
        "bob": f"127.0.0.1:{base + 1}",
        "carole": f"127.0.0.1:{base + 2}",
    }
    ep_spec = ",".join(f"{k}={v}" for k, v in endpoints.items())
    env = _cpu_subprocess_env()
    procs = {
        name: subprocess.Popen(
            [sys.executable, "-m", "moose_tpu.bin.comet",
             "--identity", name, "--port", str(base + i),
             "--endpoints", ep_spec],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, env=env,
        )
        for i, name in enumerate(endpoints)
    }
    try:
        import grpc

        deadline = time.time() + 60
        for ep in endpoints.values():
            while True:
                try:
                    grpc.channel_ready_future(
                        grpc.insecure_channel(ep)
                    ).result(timeout=5)
                    break
                except Exception:
                    if time.time() > deadline:
                        raise
        # big enough that the session is still in flight when the kill
        # lands (u128 ring matmul on CPU takes seconds at this size)
        rng = np.random.default_rng(11)
        x = rng.normal(size=(800, 800))
        w = rng.normal(size=(800, 2))
        args = {"x": x, "w": w}
        compiled = compile_computation(
            tracer.trace(_secure_dot_comp()), DEFAULT_PASSES,
            arg_specs=arg_specs_from_arguments(args),
        )
        blob = serialize_computation(compiled)
        clients = {
            name: ChoreographyClient(ep) for name, ep in endpoints.items()
        }
        for client in clients.values():
            resp = client.launch("kill-1", blob, args)
            assert resp.get("ok")
        procs["carole"].send_signal(signal.SIGKILL)
        t0 = time.monotonic()
        result = clients["alice"].retrieve("kill-1", timeout=120.0)
        elapsed = time.monotonic() - t0
        assert "error" in result, result
        # the guarantee under test: failure surfaces in seconds, far
        # below the 120 s receive-timeout regime it replaces.  The bound
        # is load-tolerant (this 1-core rig runs benches concurrently);
        # unloaded the detection takes ~2-4 s.
        assert elapsed < 60.0, f"failure took {elapsed:.1f}s to surface"
        # any of the three valid propagation paths may win the race:
        # direct unreachability detection, abort fanout from the peer
        # that detected it, or abort status learned via liveness ping
        assert (
            "unreachable" in result["error"]
            or "aborted by" in result["error"]
            or "aborted on peer" in result["error"]
        ), result
    finally:
        for p in procs.values():
            try:
                p.send_signal(signal.SIGTERM)
            except Exception:
                pass
        for p in procs.values():
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()


# ---------------------------------------------------------------------------
# compiled worker fast path (worker_plan): per-role validated jit
# ---------------------------------------------------------------------------


def _stats_delta(before, after):
    return {k: after[k] - before[k] for k in after}


def test_worker_jit_plan_validates_promotes_and_caches(monkeypatch):
    """The tentpole contract: the first session validates every compute
    segment (jit candidate vs eager reference, bit-exact), the plan
    promotes to segmented/full-jit with ZERO pins on a clean graph, and
    a repeat session of the same computation performs ZERO validating
    evaluations — the warm plan cache (weak-keyed on (computation,
    role)) serves the resolved plan."""
    monkeypatch.setenv("MOOSE_TPU_WORKER_JIT", "1")
    monkeypatch.setenv("MOOSE_TPU_JIT_SELFCHECK", "1")
    from moose_tpu.distributed import worker_plan

    rng = np.random.default_rng(0)
    args = {"x": rng.normal(size=(4, 3)), "w": rng.normal(size=(3, 2))}
    compiled = compile_computation(
        tracer.trace(_secure_dot_comp()), DEFAULT_PASSES,
        arg_specs=arg_specs_from_arguments(args),
    )

    before = worker_plan.plan_stats()
    net1 = LocalNetworking()
    r1 = _run_workers(
        compiled, ["alice", "bob", "carole"], args, lambda i: net1,
    )
    d1 = _stats_delta(before, worker_plan.plan_stats())
    assert d1["plans_built"] == 3
    assert d1["validating_evaluations"] == 3
    for r in r1.values():
        assert r["plan_mode"] in ("segmented", "full-jit"), r
        assert r["pinned_segments"] == []

    # repeat session, same computation object: warm plans, no validation
    mid = worker_plan.plan_stats()
    net2 = LocalNetworking()
    r2 = _run_workers(
        compiled, ["alice", "bob", "carole"], args, lambda i: net2,
    )
    d2 = _stats_delta(mid, worker_plan.plan_stats())
    assert d2["plans_built"] == 0
    assert d2["cache_hits"] == 3
    assert d2["validating_evaluations"] == 0, d2
    outs = {
        k: v for r in r2.values() for k, v in r["outputs"].items()
    }
    (val,) = outs.values()
    np.testing.assert_allclose(val, args["x"] @ args["w"], atol=1e-5)


def test_worker_jit_pins_only_divergent_segments(monkeypatch):
    """MOOSE_TPU_SELFCHECK_FAULT corrupts jit CANDIDATES of the listed
    kinds: the segments carrying a Dot must pin eager while every other
    segment stays jitted, and the session result (always continued from
    the eager reference) stays correct."""
    monkeypatch.setenv("MOOSE_TPU_WORKER_JIT", "1")
    monkeypatch.setenv("MOOSE_TPU_JIT_SELFCHECK", "1")
    monkeypatch.setenv("MOOSE_TPU_SELFCHECK_FAULT", "Dot")
    from moose_tpu.distributed import worker_plan

    rng = np.random.default_rng(1)
    args = {"x": rng.normal(size=(4, 3)), "w": rng.normal(size=(3, 2))}
    compiled = compile_computation(
        tracer.trace(_secure_dot_comp()), DEFAULT_PASSES,
        arg_specs=arg_specs_from_arguments(args),
    )
    before = worker_plan.plan_stats()
    results = None
    for sid in ("pin-1", "pin-2"):
        net = LocalNetworking()
        results = _run_workers(
            compiled, ["alice", "bob", "carole"], args, lambda i: net,
        )
    delta = _stats_delta(before, worker_plan.plan_stats())
    assert delta["segments_pinned"] > 0
    pinned = {i: r["pinned_segments"] for i, r in results.items()}
    assert any(pinned.values()), pinned
    # selective: pinning one divergent segment must not demote the plan
    for r in results.values():
        assert r["plan_mode"] in ("segmented", "full-jit"), r
    outs = {
        k: v for r in results.values() for k, v in r["outputs"].items()
    }
    (val,) = outs.values()
    np.testing.assert_allclose(val, args["x"] @ args["w"], atol=1e-5)


def test_worker_jit_handles_unseeded_sample(monkeypatch):
    """Sample is a hard plan boundary (an entropy draw must stay eager,
    never baked into a compiled segment) but NOT one of the
    Input/Load/Save/Output/PrfKeyGen host kinds — the orchestrator must
    route it through the legacy eager kernel dispatch instead of
    crashing the session (regression: KernelError 'not a host-boundary
    op')."""
    monkeypatch.setenv("MOOSE_TPU_WORKER_JIT", "1")
    monkeypatch.setenv("MOOSE_TPU_JIT_SELFCHECK", "1")
    from moose_tpu.computation import Operation, Signature, Ty

    rng = np.random.default_rng(3)
    args = {"x": rng.normal(size=(4, 3)), "w": rng.normal(size=(3, 2))}
    compiled = compile_computation(
        tracer.trace(_secure_dot_comp()), DEFAULT_PASSES,
        arg_specs=arg_specs_from_arguments(args),
    )
    # graft an unseeded draw onto alice's role (the reference SampleOp
    # shape: Constant HostShape -> Sample ring tensor); the standard
    # predictor pipeline emits SampleSeeded, so wire graphs carrying
    # plain Sample come from hand-written / interop computations
    compiled.add_operation(Operation(
        "smp_shape", "Constant", [], "alice",
        Signature((), Ty("HostShape")),
        attributes={"value": np.asarray([2, 3])},
    ))
    compiled.add_operation(Operation(
        "smp_draw", "Sample", ["smp_shape"], "alice",
        Signature((Ty("HostShape"),), Ty("HostRing64Tensor")),
    ))
    net = LocalNetworking()
    results = _run_workers(
        compiled, ["alice", "bob", "carole"], args, lambda i: net,
    )
    outs = {
        k: v for r in results.values() for k, v in r["outputs"].items()
    }
    (val,) = outs.values()
    np.testing.assert_allclose(val, args["x"] @ args["w"], atol=1e-5)


def test_worker_jit_off_keeps_legacy_eager_scheduler(monkeypatch):
    monkeypatch.setenv("MOOSE_TPU_WORKER_JIT", "0")
    from moose_tpu.distributed import worker_plan

    rng = np.random.default_rng(2)
    args = {"x": rng.normal(size=(3, 3)), "w": rng.normal(size=(3, 1))}
    compiled = compile_computation(
        tracer.trace(_secure_dot_comp()), DEFAULT_PASSES,
        arg_specs=arg_specs_from_arguments(args),
    )
    before = worker_plan.plan_stats()
    net = LocalNetworking()
    results = _run_workers(
        compiled, ["alice", "bob", "carole"], args, lambda i: net,
    )
    assert _stats_delta(before, worker_plan.plan_stats()) == {
        k: 0 for k in before
    }
    for r in results.values():
        assert r["plan_mode"] == "eager"


def test_send_many_envelope_posts_every_payload():
    """The coalesced send_many frame (worker fast path batching
    same-destination sends at a segment boundary) delivers every
    rendezvous payload through one SendValue rpc."""
    import msgpack

    from moose_tpu.distributed.networking import (
        GrpcNetworking,
        transfer_key,
    )
    from moose_tpu.serde import serialize_value
    from moose_tpu.values import host_tensor_from_numpy

    net = GrpcNetworking("bob", {})
    a = host_tensor_from_numpy(np.arange(4.0), "alice")
    b = host_tensor_from_numpy(np.arange(6.0) * 2, "alice")
    frame = msgpack.packb(
        {
            "sender": "alice",
            "batch": [
                {"key": transfer_key("s-1", "k-a"),
                 "value": serialize_value(a)},
                {"key": transfer_key("s-1", "k-b"),
                 "value": serialize_value(b)},
            ],
        },
        use_bin_type=True,
    )
    net.handle_send_value(frame)
    ok_a, got_a = net.try_receive("alice", "k-a", "s-1", plc="bob")
    ok_b, got_b = net.try_receive("alice", "k-b", "s-1", plc="bob")
    assert ok_a and ok_b
    np.testing.assert_array_equal(np.asarray(got_a.value), np.arange(4.0))
    np.testing.assert_array_equal(
        np.asarray(got_b.value), np.arange(6.0) * 2
    )


@pytest.mark.slow
def test_aes_decrypt_across_grpc_workers():
    """Encrypted-input inference deployed to real workers: the AES
    ciphertext lowers through the explicit pipeline (Input -> bit slices
    -> MPC decrypt circuit) and executes role-filtered over gRPC — the
    deployment the fused local path cannot provide (reference lowers
    Decrypt like any op, encrypted/mod.rs:14-40)."""
    import time

    from moose_tpu.dialects import aes
    from moose_tpu.distributed.client import GrpcClientRuntime

    alice, bob, carole, rep = _players()
    F = pm.fixed(14, 23)

    @pm.computation
    def comp(
        aes_data: pm.Argument(placement=alice,
                              vtype=pm.AesTensorType(dtype=F)),
        aes_key: pm.Argument(placement=rep, vtype=pm.AesKeyType()),
        w: pm.Argument(placement=bob, dtype=pm.float64),
    ):
        with rep:
            x = pm.decrypt(aes_key, aes_data)
        with bob:
            wf = pm.cast(w, dtype=F)
        with rep:
            score = pm.dot(x, wf)
        with carole:
            out = pm.cast(score, dtype=pm.float64)
        return out

    rng = np.random.default_rng(2)
    features = rng.normal(size=(1, 2))
    w = rng.normal(size=(2, 1))
    key = bytes(range(16))
    wire = aes.encrypt_fixed_array(
        key, bytes([7] * 12), features, frac_precision=23
    )
    args = {
        "aes_data": np.asarray(wire),
        "aes_key": np.asarray(aes.bytes_to_bits_be(key)),
        "w": w,
    }

    servers, endpoints = _start_cluster(["alice", "bob", "carole"])
    try:
        runtime = GrpcClientRuntime(endpoints)
        t0 = time.monotonic()
        outputs, timings = runtime.run_computation(
            tracer.trace(comp), args, timeout=600.0,
        )
        elapsed = time.monotonic() - t0
        (got,) = outputs.values()
        np.testing.assert_allclose(got, features @ w, atol=5e-4)
        assert set(timings) == {"alice", "bob", "carole"}
        print(f"aes-over-grpc: {elapsed:.1f}s")
    finally:
        for srv in servers.values():
            srv.stop()


@pytest.mark.slow
def test_full_predictor_softmax_across_grpc_workers():
    """A complete ONNX predictor — linear classifier with a SOFTMAX head
    (max tournament, exp, Goldschmidt normalization: ~10k host ops) —
    compiled and executed role-filtered across 3 gRPC workers, checked
    against sklearn.  This is the op-count scale the reference's
    rust_integration_tests run under its multi-identity runtime; the
    wall-clock budget guards against head-of-line regressions in the
    parallel worker scheduler."""
    import time

    from sklearn.linear_model import LogisticRegression

    from moose_tpu import predictors
    from moose_tpu.distributed.client import GrpcClientRuntime
    from moose_tpu.predictors.sklearn_export import (
        logistic_regression_onnx,
    )

    rng = np.random.default_rng(3)
    features = 8
    x_train = rng.normal(size=(128, features))
    y_train = rng.integers(0, 3, size=128)  # 3 classes -> softmax head
    sk = LogisticRegression().fit(x_train, y_train)
    model = predictors.from_onnx(
        logistic_regression_onnx(sk, features).encode()
    )
    comp = model.predictor_factory()
    x = rng.normal(size=(4, features))

    servers, endpoints = _start_cluster(["alice", "bob", "carole"])
    try:
        runtime = GrpcClientRuntime(endpoints)
        t0 = time.monotonic()
        outputs, timings = runtime.run_computation(
            tracer.trace(comp), {"x": x}, timeout=600.0,
        )
        elapsed = time.monotonic() - t0
        (got,) = outputs.values()
        np.testing.assert_allclose(
            got, sk.predict_proba(x), atol=5e-3
        )
        assert set(timings) == {"alice", "bob", "carole"}
        # budget: the sequential pre-round-3 walk would put every op of
        # a ~10k-op graph behind every blocked receive; the parallel
        # scheduler keeps this in tens of seconds even on 1 core
        assert elapsed < 300, f"distributed predictor took {elapsed:.0f}s"
        print(f"predictor-over-grpc: {elapsed:.1f}s")
    finally:
        for srv in servers.values():
            srv.stop()


# ---------------------------------------------------------------------------
# ISSUE 7: static schedule/cost analysis wired into the worker plan
# ---------------------------------------------------------------------------


def _oversubscribed_comp():
    """Rendezvous key consumed by two Receives but sent once: a
    would-hang plan that toposorts cleanly (only the MSA5xx plan-level
    analysis rejects it before execution)."""
    from moose_tpu.computation import (
        Computation,
        HostFloat64TensorTy,
        HostPlacement,
        Operation,
        Signature,
        UnitTy,
    )

    f64 = HostFloat64TensorTy
    comp = Computation()
    for name in ("alice", "bob"):
        comp.add_placement(HostPlacement(name))
    comp.add_operation(Operation(
        "c", "Constant", [], "bob", Signature((), f64),
        {"value": np.zeros((2,))},
    ))
    comp.add_operation(Operation(
        "s", "Send", ["c"], "bob", Signature((f64,), UnitTy),
        {"rendezvous_key": "dup", "receiver": "alice"},
    ))
    for i in (1, 2):
        comp.add_operation(Operation(
            f"r{i}", "Receive", [], "alice", Signature((), f64),
            {"rendezvous_key": "dup", "sender": "bob"},
        ))
    comp.add_operation(Operation(
        "out", "Output", ["r2"], "alice", Signature((f64,), f64),
    ))
    return comp


def test_would_deadlock_plan_rejected_at_build_time(monkeypatch):
    """get_plan must reject the plan BEFORE anything executes: typed
    PlanRejectedError carrying MSA501 diagnostics, a plans_rejected
    stat, and a flight plan_rejected event."""
    monkeypatch.setenv("MOOSE_TPU_WORKER_JIT", "1")
    monkeypatch.setenv("MOOSE_TPU_JIT_SELFCHECK", "1")
    from moose_tpu import flight
    from moose_tpu.distributed import worker_plan
    from moose_tpu.errors import PlanRejectedError

    comp = _oversubscribed_comp()
    before = worker_plan.plan_stats()
    with pytest.raises(PlanRejectedError) as exc_info:
        worker_plan.get_plan(comp, "alice", session_id="rej-1")
    err = exc_info.value
    assert any(d.rule == "MSA501" for d in err.diagnostics), (
        err.diagnostics
    )
    assert "MSA501" in str(err)
    delta = _stats_delta(before, worker_plan.plan_stats())
    assert delta["plans_rejected"] == 1
    assert delta["plans_built"] == 0
    events = flight.get_recorder().events(session="rej-1")
    assert any(e["kind"] == "plan_rejected" for e in events), events
    # rejection is not retryable: resubmitting the same computation
    # deterministically re-fails
    from moose_tpu.errors import is_retryable

    assert not is_retryable(err)


def test_rejected_plan_falls_back_to_legacy_scheduler(monkeypatch):
    """execute_role with the fast path on must demote to the legacy
    eager scheduler on rejection (typed timeout in seconds — never a
    hang, and never a crash on the rejection itself)."""
    monkeypatch.setenv("MOOSE_TPU_WORKER_JIT", "1")
    monkeypatch.setenv("MOOSE_TPU_JIT_SELFCHECK", "1")
    import time

    from moose_tpu.distributed.networking import (
        LocalNetworking,
        ProgressClock,
    )
    from moose_tpu.errors import ReceiveTimeoutError

    comp = _oversubscribed_comp()
    net = LocalNetworking()
    t0 = time.monotonic()
    with pytest.raises(ReceiveTimeoutError):
        execute_role(
            comp, "alice", {}, {}, net, "rej-2", timeout=1.0,
            progress=ProgressClock(),
        )
    assert time.monotonic() - t0 < 20.0


def test_cost_model_matches_measured_counters_exactly(monkeypatch):
    """The ISSUE 7 tentpole contract at test granularity: the static
    cost model's predictions for the secure-dot session equal the
    metrics-registry deltas EXACTLY on the local transport — bytes,
    singles, coalesced envelopes/payloads, receives."""
    monkeypatch.setenv("MOOSE_TPU_WORKER_JIT", "1")
    monkeypatch.setenv("MOOSE_TPU_JIT_SELFCHECK", "1")
    from moose_tpu import metrics
    from moose_tpu.compilation.analysis import cost_report

    rng = np.random.default_rng(4)
    args = {"x": rng.normal(size=(4, 3)), "w": rng.normal(size=(3, 2))}
    compiled = compile_computation(
        tracer.trace(_secure_dot_comp()), DEFAULT_PASSES,
        arg_specs=arg_specs_from_arguments(args),
    )

    names = {
        "tx_bytes": "moose_tpu_net_tx_bytes_total",
        "rx_bytes": "moose_tpu_net_rx_bytes_total",
        "sends": "moose_tpu_net_sends_total",
        "send_many_envelopes": "moose_tpu_net_send_many_total",
        "send_many_payloads": "moose_tpu_net_send_many_payloads_total",
        "receives": "moose_tpu_net_receives_total",
    }

    def snap():
        return {
            k: metrics.REGISTRY.value(v, transport="local")
            for k, v in names.items()
        }

    net = LocalNetworking()
    before = snap()
    _run_workers(compiled, ["alice", "bob", "carole"], args,
                 lambda i: net)
    measured = {k: int(v - before[k]) for k, v in snap().items()}
    report = cost_report(compiled, session_id="sess-1",
                         transport="local")
    assert report["resolved"], report
    predicted = {k: int(report["totals"][k]) for k in names}
    assert predicted == measured
    # per-party numbers are self-consistent with the totals
    for key in names:
        assert sum(
            report["per_party"][p][key] for p in report["per_party"]
        ) == predicted[key]


@pytest.mark.slow
def test_fabric_logreg_warm_counters_match_cost_model_exactly(
    monkeypatch,
):
    """The fabric acceptance pin: a WARM (second-session) 3-party
    logreg SGD run inside one FabricDomain moves ZERO payloads over the
    wire transport, and every fabric counter delta — permutes, batched
    permutes, permute payloads, device bytes, singleton sends — equals
    the MSA6xx cost model's fabric prediction EXACTLY.  Worker jit is
    ON so coalesced flush groups lower to batched permutes (the eager
    singleton path is pinned by test_fabric.py)."""
    monkeypatch.setenv("MOOSE_TPU_JIT", "1")
    monkeypatch.setenv("MOOSE_TPU_FIXED_KEYS", "fabric-logreg")
    from moose_tpu import metrics
    from moose_tpu.compilation.analysis.cost import cost_report
    from moose_tpu.distributed.fabric import (
        FabricDomain,
        FabricNetworking,
    )
    from moose_tpu.predictors.trainers import LogregSGDTrainer

    trainer = LogregSGDTrainer(n_features=2, steps_per_epoch=1)
    rng = np.random.default_rng(7)
    args = {
        "x": rng.normal(size=(4, 2)),
        "y": (rng.random(size=(4, 1)) > 0.5).astype(np.float64),
        "w": np.zeros((2, 1)),
    }
    compiled = compile_computation(
        trainer.step_computation(4), DEFAULT_PASSES,
        arg_specs=arg_specs_from_arguments(args),
    )

    identities = ["alice", "bob", "carole"]
    domain = FabricDomain.default(identities, trust_model="simulation")
    inner = LocalNetworking()
    nets = {
        i: FabricNetworking(domain, i, inner) for i in identities
    }

    def run(session_id):
        results, errors = {}, {}

        def work(identity):
            try:
                results[identity] = execute_role(
                    compiled, identity, {}, args, nets[identity],
                    session_id=session_id, timeout=120.0,
                )
            except Exception as e:  # pragma: no cover
                errors[identity] = e

        threads = [
            threading.Thread(target=work, args=(i,), daemon=True)
            for i in identities
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=240)
        assert not errors, errors
        return {
            k: np.asarray(v)
            for r in results.values() for k, v in r["outputs"].items()
        }

    run("fab-lr-cold")  # jits every (edge, shape-set) permute program

    names = {
        "fabric_permutes": "moose_tpu_fabric_permutes_total",
        "fabric_batched_permutes":
            "moose_tpu_fabric_batched_permutes_total",
        "fabric_permute_payloads":
            "moose_tpu_fabric_permute_payloads_total",
        "fabric_tx_bytes": "moose_tpu_fabric_tx_bytes_total",
    }

    def snap():
        out = {k: metrics.REGISTRY.value(v) for k, v in names.items()}
        out["sends"] = metrics.REGISTRY.value(
            "moose_tpu_net_sends_total", transport="fabric"
        )
        out["wire"] = metrics.REGISTRY.value(
            "moose_tpu_net_sends_total", transport="local"
        )
        return out

    before = snap()
    out_warm = run("fab-lr-warm")
    after = snap()
    measured = {k: int(after[k] - before[k]) for k in names}
    measured["sends"] = int(after["sends"] - before["sends"])

    # zero wire sends on intra-fabric edges
    assert after["wire"] == before["wire"]
    # warm weights well-formed (one revealed (2, 1) update at bob)
    (w_out,) = out_warm.values()
    assert w_out.shape == (2, 1) and np.isfinite(w_out).all()

    report = cost_report(
        compiled, session_id="fab-lr-warm", transport="fabric",
        fabric_parties=tuple(identities),
    )
    assert report["resolved"], report
    predicted = {
        k: int(report["totals"][k]) for k in list(names) + ["sends"]
    }
    assert measured == predicted
    assert report["totals"]["fallback_sends"] == 0
    assert report["totals"]["fabric_batched_permutes"] > 0
