"""Tracing/profiling spans (reference aux subsystem: tracing crate spans,
reindeer.rs:7-30; per-role elapsed time, pymoose/src/bindings.rs:320-328)."""

import json

import numpy as np
import pytest

import moose_tpu as pm
from moose_tpu import telemetry
from moose_tpu.runtime import LocalMooseRuntime


def test_span_nesting_and_timings():
    with telemetry.span("outer", kind="test") as outer:
        with telemetry.span("inner"):
            pass
        with telemetry.span("inner2"):
            pass
    assert outer.name == "outer"
    assert [c.name for c in outer.children] == ["inner", "inner2"]
    assert outer.duration_s >= 0
    assert telemetry.last_trace() is outer
    assert outer.find("inner2") is not None

    timings = telemetry.phase_timings()
    assert set(timings) == {"outer", "inner", "inner2"}

    blob = json.loads(telemetry.to_json())
    assert blob["name"] == "outer"
    assert blob["attrs"] == {"kind": "test"}
    assert len(blob["children"]) == 2


def test_find_attr_searches_span_tree():
    with telemetry.span("outer") as outer:
        with telemetry.span("mid"):
            with telemetry.span("execute", plan_mode="per-op",
                                pinned_ops=1):
                pass
    assert telemetry.find_attr(outer, "plan_mode") == "per-op"
    assert telemetry.find_attr(outer, "pinned_ops") == 1
    assert telemetry.find_attr(outer, "absent", "dflt") == "dflt"
    assert telemetry.find_attr(None, "plan_mode", 7) == 7


def test_runtime_surfaces_plan_mode():
    """Resolved plan shape rides along with the phase timings: the
    execute span's plan attributes are lifted into last_timings and
    last_plan (ISSUE 2 tentpole c)."""
    alice = pm.host_placement("alice")

    @pm.computation
    def comp(x: pm.Argument(placement=alice, vtype=pm.TensorType(pm.float64))):
        with alice:
            y = pm.add(x, x)
        return y

    runtime = LocalMooseRuntime(["alice"], use_jit=False)
    runtime.evaluate_computation(comp, arguments={"x": np.ones((4,))})
    assert runtime.last_plan["plan_mode"] == "eager"
    assert runtime.last_plan["pinned_ops"] == []
    assert runtime.last_plan["layout"] == "per-host"

    jit_rt = LocalMooseRuntime(["alice"], use_jit=True)
    jit_rt.evaluate_computation(comp, arguments={"x": np.ones((4,))})
    assert jit_rt.last_plan["plan_mode"] == "whole-graph"


def test_runtime_records_phase_timings():
    alice = pm.host_placement("alice")

    @pm.computation
    def comp(x: pm.Argument(placement=alice, vtype=pm.TensorType(pm.float64))):
        with alice:
            y = pm.add(x, x)
        return y

    runtime = LocalMooseRuntime(["alice"])
    x = np.ones((4,))
    runtime.evaluate_computation(comp, arguments={"x": x})
    t = runtime.last_timings
    # trace/build happen on the first call; execute on every call
    for phase in ("evaluate_computation", "trace", "build_plan", "execute"):
        assert phase in t, f"missing phase {phase}: {t}"
        assert t[phase] >= 0

    # second call: cached trace/plan, execute still present
    runtime.evaluate_computation(comp, arguments={"x": x})
    t2 = runtime.last_timings
    assert "execute" in t2
    assert "trace" not in t2
    assert "build_plan" not in t2


def test_compile_path_records_pass_spans():
    alice = pm.host_placement("alice")

    @pm.computation
    def comp(x: pm.Argument(placement=alice, vtype=pm.TensorType(pm.float64))):
        with alice:
            y = pm.mul(x, x)
        return y

    runtime = LocalMooseRuntime(["alice"])
    runtime.evaluate_computation(
        comp,
        arguments={"x": np.ones((3,))},
        compiler_passes=["typing", "lowering", "prune", "toposort"],
    )
    t = runtime.last_timings
    assert "compile" in t
    assert "pass:lowering" in t
    assert "pass:prune" in t


def test_report_renders_tree(capsys):
    with telemetry.span("root"):
        with telemetry.span("child"):
            pass
    import io

    buf = io.StringIO()
    telemetry.report(file=buf)
    text = buf.getvalue()
    assert "root:" in text
    assert "  child:" in text


def test_eager_per_op_spans(monkeypatch):
    """MOOSE_TPU_TRACE_OPS=1 records per-kind op spans in eager mode
    (reference: one tracing span per async op task)."""
    monkeypatch.setenv("MOOSE_TPU_TRACE_OPS", "1")
    alice = pm.host_placement("alice")

    @pm.computation
    def comp(x: pm.Argument(placement=alice, vtype=pm.TensorType(pm.float64))):
        with alice:
            y = pm.mul(pm.add(x, x), x)
        return y

    runtime = LocalMooseRuntime(["alice"], use_jit=False)
    runtime.evaluate_computation(comp, arguments={"x": np.ones((3,))})
    t = runtime.last_timings
    assert "op:Add" in t and "op:Mul" in t, t


def test_eager_per_op_spans_compiled_path(monkeypatch):
    """The physical executor's eager loop records per-op spans too."""
    monkeypatch.setenv("MOOSE_TPU_TRACE_OPS", "1")
    alice = pm.host_placement("alice")

    @pm.computation
    def comp(x: pm.Argument(placement=alice, vtype=pm.TensorType(pm.float64))):
        with alice:
            y = pm.add(x, x)
        return y

    runtime = LocalMooseRuntime(["alice"], use_jit=False)
    runtime.evaluate_computation(
        comp, arguments={"x": np.ones((2,))},
        compiler_passes=["typing", "lowering", "prune", "toposort"],
    )
    assert "op:Add" in runtime.last_timings, runtime.last_timings


# ---------------------------------------------------------------------------
# OTLP/HTTP export (reference: comet --telemetry ships spans to Jaeger,
# comet.rs:30-41 + reindeer.rs:7-30)
# ---------------------------------------------------------------------------


class _Collector:
    """Minimal in-process OTLP/HTTP collector capturing POSTed payloads."""

    def __init__(self):
        import http.server
        import threading

        collector = self

        class Handler(http.server.BaseHTTPRequestHandler):
            def do_POST(self):
                length = int(self.headers["Content-Length"])
                collector.requests.append(
                    (self.path, json.loads(self.rfile.read(length)))
                )
                self.send_response(200)
                self.send_header("Content-Type", "application/json")
                self.end_headers()
                self.wfile.write(b"{}")

            def log_message(self, *args):
                pass

        self.requests = []
        self.server = http.server.HTTPServer(("127.0.0.1", 0), Handler)
        self.endpoint = f"http://127.0.0.1:{self.server.server_port}"
        threading.Thread(target=self.server.serve_forever, daemon=True).start()

    def close(self):
        self.server.shutdown()
        self.server.server_close()


def test_otlp_export_ships_root_trees():
    collector = _Collector()
    try:
        exporter = telemetry.configure_otlp(
            collector.endpoint, service_name="test-svc"
        )
        with telemetry.span("root", session_id="s1"):
            with telemetry.span("child", n_ops=7):
                pass
            with telemetry.span("child2"):
                pass
        assert exporter.flush(timeout_s=10.0)
        assert exporter.exported == 1 and exporter.dropped == 0
    finally:
        telemetry.disable_otlp()
        collector.close()

    (path, payload), = collector.requests
    assert path == "/v1/traces"
    resource = payload["resourceSpans"][0]
    svc = {
        a["key"]: a["value"] for a in resource["resource"]["attributes"]
    }
    assert svc["service.name"] == {"stringValue": "test-svc"}
    spans = resource["scopeSpans"][0]["spans"]
    by_name = {s["name"]: s for s in spans}
    assert set(by_name) == {"root", "child", "child2"}
    root = by_name["root"]
    assert "parentSpanId" not in root
    # children share the root's trace and point at its spanId
    for name in ("child", "child2"):
        assert by_name[name]["traceId"] == root["traceId"]
        assert by_name[name]["parentSpanId"] == root["spanId"]
    # OTLP JSON nano timestamps are strings and ordered
    assert int(root["startTimeUnixNano"]) <= int(
        by_name["child"]["startTimeUnixNano"]
    )
    assert int(root["endTimeUnixNano"]) >= int(
        by_name["child2"]["endTimeUnixNano"]
    )
    # attribute typing: ints ride intValue (as strings, per the mapping)
    child_attrs = {
        a["key"]: a["value"] for a in by_name["child"]["attributes"]
    }
    assert child_attrs["n_ops"] == {"intValue": "7"}


def test_otlp_export_runtime_spans_end_to_end():
    """A real evaluate_computation exports its span tree."""
    collector = _Collector()
    try:
        exporter = telemetry.configure_otlp(collector.endpoint)
        alice = pm.host_placement("alice")

        @pm.computation
        def comp(
            x: pm.Argument(placement=alice, vtype=pm.TensorType(pm.float64))
        ):
            with alice:
                y = pm.add(x, x)
            return y

        runtime = LocalMooseRuntime(["alice"], use_jit=False)
        runtime.evaluate_computation(comp, arguments={"x": np.ones((2,))})
        assert exporter.flush(timeout_s=10.0)
        assert exporter.exported >= 1
    finally:
        telemetry.disable_otlp()
        collector.close()

    names = set()
    for _, payload in collector.requests:
        for rs in payload["resourceSpans"]:
            for ss in rs["scopeSpans"]:
                names.update(s["name"] for s in ss["spans"])
    assert "evaluate_computation" in names
    # the runtime's phase children ride along in the same tree
    assert {"trace", "execute"} <= names


def test_otlp_collector_down_never_raises():
    """An unreachable collector drops batches without breaking spans."""
    try:
        exporter = telemetry.configure_otlp("http://127.0.0.1:9")  # discard
        with telemetry.span("root"):
            pass
        exporter.flush(timeout_s=10.0)
        assert exporter.dropped >= 1
        assert exporter.last_error
        assert telemetry.last_trace().name == "root"
    finally:
        telemetry.disable_otlp()


def test_trace_context_ids_and_adoption():
    """Spans carry stable ids; roots under an ambient TraceContext join
    its trace instead of minting an orphan one."""
    with telemetry.span("orphan") as orphan:
        pass
    assert len(orphan.trace_id) == 32 and len(orphan.span_id) == 16
    assert orphan.parent_span_id is None

    ctx = telemetry.TraceContext.new()
    with telemetry.use_context(ctx):
        assert telemetry.current_context() == ctx
        with telemetry.span("root") as root:
            inner = telemetry.current_context()
            assert inner.trace_id == ctx.trace_id
            assert inner.span_id == root.span_id
            with telemetry.span("child") as child:
                pass
    # restored after the context manager
    assert telemetry.current_context() is None
    assert root.trace_id == ctx.trace_id
    assert root.parent_span_id == ctx.span_id
    assert child.trace_id == ctx.trace_id
    assert child.parent_span_id == root.span_id
    assert child.span_id != root.span_id

    # wire round-trip
    assert telemetry.TraceContext.from_dict(ctx.to_dict()) == ctx
    assert telemetry.TraceContext.from_dict(None) is None
    assert telemetry.TraceContext.from_dict({"trace_id": ""}) is None


def test_background_thread_inherits_context():
    import threading

    ctx = telemetry.TraceContext.new()
    captured = {}

    def worker():
        with telemetry.use_context(ctx):
            with telemetry.span("bg-root") as s:
                pass
            captured["span"] = s

    t = threading.Thread(target=worker)
    t.start()
    t.join()
    assert captured["span"].trace_id == ctx.trace_id
    assert captured["span"].parent_span_id == ctx.span_id


def test_otlp_encode_uses_propagated_ids():
    """The exporter ships the spans' own (propagated) ids — not fresh
    random ones per encode — so two processes exporting halves of one
    session produce ONE stitched trace."""
    ctx = telemetry.TraceContext.new()
    with telemetry.use_context(ctx):
        with telemetry.span("root") as root:
            with telemetry.span("child"):
                pass
    exporter = telemetry.OtlpExporter.__new__(telemetry.OtlpExporter)
    exporter.service_name = "svc"
    payload = exporter.encode(root)
    spans = payload["resourceSpans"][0]["scopeSpans"][0]["spans"]
    by_name = {s["name"]: s for s in spans}
    assert by_name["root"]["traceId"] == ctx.trace_id
    assert by_name["root"]["spanId"] == root.span_id
    # the remote parent (the propagated context's span) is preserved
    assert by_name["root"]["parentSpanId"] == ctx.span_id
    assert by_name["child"]["traceId"] == ctx.trace_id
    # a second encode of the same tree yields the SAME ids
    payload2 = exporter.encode(root)
    spans2 = payload2["resourceSpans"][0]["scopeSpans"][0]["spans"]
    assert {s["spanId"] for s in spans} == {s["spanId"] for s in spans2}


def test_otlp_flush_never_blocks_on_full_queue():
    """Satellite: flush() on a wedged full queue must time out and
    return False — a blocking put would park the caller forever."""
    import threading
    import time

    release = threading.Event()

    class _Wedged(telemetry.OtlpExporter):
        def _post(self, payload):
            release.wait(30.0)

    exporter = _Wedged("http://127.0.0.1:9", max_queue=2)
    try:
        for _ in range(4):  # 1 in-flight (blocked in _post) + 2 queued
            with telemetry.span("r"):
                pass
            exporter.export(telemetry.last_trace())
        t0 = time.monotonic()
        ok = exporter.flush(timeout_s=0.5)
        elapsed = time.monotonic() - t0
        assert ok is False
        assert elapsed < 5.0, f"flush blocked for {elapsed:.1f}s"
        assert exporter.dropped >= 1  # the overflow export was dropped
    finally:
        release.set()
        exporter.shutdown()


def test_distributed_session_exports_one_stitched_trace(monkeypatch):
    """ISSUE 6 acceptance: a 3-party gRPC session with OTLP configured
    exports exactly ONE trace id shared by the client spans and every
    worker's execute_role span, with parent/child span ids lining up
    across the rpc boundary."""
    monkeypatch.setenv("MOOSE_TPU_ALLOW_WEAK_PRF", "1")
    from moose_tpu.distributed.choreography import start_local_cluster
    from moose_tpu.distributed.client import GrpcClientRuntime

    alice = pm.host_placement("alice")
    bob = pm.host_placement("bob")
    carole = pm.host_placement("carole")
    rep = pm.replicated_placement("rep", players=[alice, bob, carole])

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

    from moose_tpu.edsl import tracer

    rng = np.random.default_rng(0)
    args = {"x": rng.normal(size=(4, 3)), "w": rng.normal(size=(3, 2))}

    collector = _Collector()
    servers = {}
    try:
        exporter = telemetry.configure_otlp(collector.endpoint)
        servers, endpoints = start_local_cluster(
            ("alice", "bob", "carole"), ping_interval=0.25,
            receive_timeout=30.0,
        )
        runtime = GrpcClientRuntime(endpoints, max_attempts=1)
        runtime.run_computation(
            tracer.trace(comp), args, timeout=60.0
        )
        assert exporter.flush(timeout_s=10.0)
    finally:
        telemetry.disable_otlp()
        for srv in servers.values():
            srv.stop()
        collector.close()

    spans = []
    for _, payload in collector.requests:
        for rs in payload["resourceSpans"]:
            for ss in rs["scopeSpans"]:
                spans.extend(ss["spans"])
    by_name: dict = {}
    for s in spans:
        by_name.setdefault(s["name"], []).append(s)
    roots = by_name["run_computation"]
    assert len(roots) == 1
    trace_id = roots[0]["traceId"]
    workers = by_name.get("execute_role", [])
    parties = set()
    for s in workers:
        attrs = {a["key"]: a["value"] for a in s["attributes"]}
        parties.add(attrs["party"]["stringValue"])
    assert parties == {"alice", "bob", "carole"}, parties
    # ONE stitched trace: every span of client AND workers shares it
    session_span_names = {
        "run_computation", "attempt", "launch", "retrieve",
        "execute_role", "worker_segment",
    }
    for s in spans:
        if s["name"] in session_span_names:
            assert s["traceId"] == trace_id, (s["name"], s["traceId"])
    # parent/child line up across the rpc: each worker root hangs off
    # the client's attempt span
    (attempt,) = by_name["attempt"]
    assert attempt["parentSpanId"] == roots[0]["spanId"]
    for s in workers:
        assert s["parentSpanId"] == attempt["spanId"], s
    # exporter book-keeping
    assert exporter.exported >= 4  # client root + 3 worker roots
    assert exporter.dropped == 0


def test_comet_telemetry_flag_wires_exporter(monkeypatch):
    """comet --telemetry ENDPOINT installs the OTLP exporter before the
    worker starts (reference comet.rs:30-41)."""
    from moose_tpu.bin import comet

    installed = {}

    def fake_configure(endpoint, service_name="moose_tpu"):
        installed["endpoint"] = endpoint
        installed["service"] = service_name
        raise SystemExit(0)  # stop before the server binds

    monkeypatch.setattr(telemetry, "configure_otlp", fake_configure)
    try:
        comet.main([
            "--identity", "alice", "--port", "50901",
            "--endpoints", "alice=localhost:50901",
            "--telemetry", "http://collector:4318",
        ])
    except SystemExit:
        pass
    assert installed == {
        "endpoint": "http://collector:4318",
        "service": "comet:alice",
    }


def test_accumulate_sums_on_the_innermost_open_span():
    """``telemetry.accumulate`` adds to an attribute of the innermost
    open span (traced bit-mask draws sum their PRF output there as
    ``bank_draw_mb``); with no span open it does nothing."""
    from moose_tpu import metrics
    from moose_tpu.parallel import spmd

    telemetry.accumulate(bank_draw_mb=1.0)  # no span: no error
    before = metrics.REGISTRY.value(
        "moose_tpu_bit_bank_draw_bytes_total", form="bytes"
    )
    with telemetry.span("outer") as outer:
        with telemetry.span("dispatch") as inner:
            sess = spmd.SpmdSession(np.arange(4, dtype=np.uint32))
            sess.sample_bit_bank((5, 100))
            sess.sample_bit_words(2, (2, 8, 128))
    assert "bank_draw_mb" not in outer.attrs
    words = 4 * 2 * 3 * 2 * 8 * 128
    assert inner.attrs["bank_draw_mb"] == pytest.approx(
        (3 * 500 + words) / 1e6
    )
    assert metrics.REGISTRY.value(
        "moose_tpu_bit_bank_draw_bytes_total", form="bytes"
    ) == before + 1500
