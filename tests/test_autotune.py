"""Cost-driven plan autotuner (compilation/autotune.py, ISSUE 20).

The decision engine must be a pure function of (computation,
measurements, env): same measurements give the same plan in any
process; an explicitly-set env knob always wins verbatim; and a
measured-faster-but-divergent Pallas kernel is still pinned to the XLA
path by the first-use bit-exactness check — the autotuner picks among
exact plans, it never trades exactness for speed.  The resolved
decision table must surface through ``runtime.last_plan["autotune"]``,
the ``plan_autotuned`` flight event, and ``moose_tpu_autotune_*``
metrics.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import moose_tpu as pm
from moose_tpu import flight, metrics
from moose_tpu.compilation import autotune
from moose_tpu.edsl import tracer
from moose_tpu.native import ring128_kernels as rk

KNOBS = (
    "MOOSE_TPU_JIT_SEGMENT",
    "MOOSE_TPU_WORKER_MIN_SEG",
    "MOOSE_TPU_PALLAS",
    "MOOSE_TPU_PALLAS_DOT",
    "MOOSE_TPU_FABRIC",
    "MOOSE_TPU_AUTOTUNE",
)


@pytest.fixture(autouse=True)
def clean_autotune(monkeypatch):
    """Each test sees unset knobs, an empty measurement store, and no
    cached decisions; whatever was there before is restored."""
    for knob in KNOBS:
        monkeypatch.delenv(knob, raising=False)
    saved = autotune.measurements().snapshot()
    autotune.measurements().clear()
    autotune.reset_dot_decisions()
    autotune.reset_cache()
    yield
    autotune.measurements().clear()
    autotune.measurements().load(saved)
    autotune.reset_dot_decisions()
    autotune.reset_cache()


def _dot_comp():
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

    return tracer.trace(comp)


# ---------------------------------------------------------------------------
# Individual decision functions
# ---------------------------------------------------------------------------


def test_segment_limit_balanced_beats_default_plus_tail():
    d = autotune.segment_limit_for(2100)
    assert d.source == "predicted"
    # 2100 ops as 2 balanced segments of <=1050, not 2000 + 100
    assert d.choice == 1050
    small = autotune.segment_limit_for(500)
    assert small.source == "default" and small.choice == 2000


def test_segment_limit_override_wins(monkeypatch):
    monkeypatch.setenv("MOOSE_TPU_JIT_SEGMENT", "123")
    d = autotune.segment_limit_for(100_000)
    assert d.source == "override" and d.choice == 123
    # 0 means "one fused program" (the established knob semantics)
    monkeypatch.setenv("MOOSE_TPU_JIT_SEGMENT", "0")
    assert autotune.segment_limit_for(100_000).choice == 1 << 62


def test_worker_min_seg_decision():
    # majority-tiny schedule: floor lifts to median tiny size + 1
    sizes = [2, 2, 3, 3, 5, 40, 900]
    d = autotune.worker_min_seg_for(sizes)
    assert d.source == "predicted" and d.choice == 4  # median(2,2,3,3,5)+1
    # compile-bound schedule: default floor stands
    d2 = autotune.worker_min_seg_for([100, 200, 300])
    assert d2.choice == 4
    # no signal
    assert autotune.worker_min_seg_for([]).source == "default"


def test_worker_min_seg_override_wins(monkeypatch):
    monkeypatch.setenv("MOOSE_TPU_WORKER_MIN_SEG", "9")
    d = autotune.worker_min_seg_for([2, 2, 2])
    assert d.source == "override" and d.choice == 9


def test_dot_shape_classes():
    assert autotune.dot_shape_class(512, 512, 128) == "mxu"
    assert autotune.dot_shape_class(1000, 1000, 1000) == "mxu"
    assert autotune.dot_shape_class(1024, 128, 8) == "tall"
    assert autotune.dot_shape_class(1024, 100, 1) == "tall"
    assert autotune.dot_shape_class(128, 100, 2) == "small"
    assert autotune.dot_shape_class(3, 4, 2) == "small"


def test_dot_kernel_decision_follows_measurements():
    shape = (1024, 128, 8)  # tall
    # no measurement: honest default off
    d0 = autotune.dot_kernel_decision(128, shape)
    assert d0.choice is False and d0.source == "default"
    # measured faster: on
    autotune.measurements().record(
        "dot_cross_terms", 128, "tall", pallas_s=1e-4, xla_s=1e-2,
    )
    d1 = autotune.dot_kernel_decision(128, shape)
    assert d1.choice is True and d1.source == "measured"
    # measured slower: off — and the small class is untouched (no
    # global default flip)
    autotune.measurements().record(
        "dot_cross_terms", 128, "small", pallas_s=1e-2, xla_s=1e-4,
    )
    assert autotune.dot_kernel_decision(128, (128, 100, 2)).choice is False
    assert autotune.dot_kernel_decision(128, shape).choice is True


def test_dot_kernel_override_wins(monkeypatch):
    autotune.measurements().record(
        "dot_cross_terms", 128, "tall", pallas_s=1e-4, xla_s=1e-2,
    )
    monkeypatch.setenv("MOOSE_TPU_PALLAS_DOT", "0")
    d = autotune.dot_kernel_decision(128, (1024, 128, 8))
    assert d.choice is False and d.source == "override"
    monkeypatch.setenv("MOOSE_TPU_PALLAS_DOT", "1")
    d = autotune.dot_kernel_decision(128, (128, 100, 2))
    assert d.choice is True and d.source == "override"


def test_autotune_disabled_restores_fixed_defaults(monkeypatch):
    autotune.measurements().record(
        "dot_cross_terms", 128, "tall", pallas_s=1e-4, xla_s=1e-2,
    )
    monkeypatch.setenv("MOOSE_TPU_AUTOTUNE", "0")
    assert autotune.segment_limit_for(100_000).choice == 2000
    assert autotune.worker_min_seg_for([2, 2, 2]).choice == 4
    assert autotune.dot_kernel_decision(128, (1024, 128, 8)).choice is False


def test_serving_bucket_plan_prunes_flat_latencies():
    # default ladder when no measurements
    d0 = autotune.serving_bucket_plan(32)
    assert d0.source == "default" and d0.choice[-1] == 32
    # flat 8-vs-16: 8 pruned; 16-vs-32 scales: 16 kept
    for bucket, lat in ((8, 0.010), (16, 0.0101), (32, 0.020)):
        autotune.measurements().record(
            "bucket_latency", 0, str(bucket), eval_s=lat,
        )
    d1 = autotune.serving_bucket_plan(32)
    assert d1.source == "measured"
    assert 8 not in d1.choice and 16 in d1.choice and 32 in d1.choice


def test_transport_choice():
    # no attestation: grpc, regardless of pricing
    d = autotune.transport_choice((), ("alice", "bob"))
    assert d.choice == "grpc" and d.source == "default"
    # attested + no pricing: fabric (strips serde framing)
    d = autotune.transport_choice(
        ("alice", "bob", "carole"), ("alice", "bob"),
    )
    assert d.choice == "fabric" and d.source == "predicted"
    # attested + MSA6xx prices grpc cheaper: grpc
    d = autotune.transport_choice(
        ("alice", "bob"), ("alice", "bob"),
        predicted={"fabric_bytes": 100.0, "grpc_bytes": 10.0},
    )
    assert d.choice == "grpc" and d.source == "predicted"


def test_transport_override_wins(monkeypatch):
    monkeypatch.setenv("MOOSE_TPU_FABRIC", "0")
    d = autotune.transport_choice(
        ("alice", "bob"), ("alice", "bob"),
    )
    assert d.choice == "grpc" and d.source == "override"


def test_pallas_family_measured_votes(monkeypatch):
    for kern in ("fx_mul", "msb", "fx_sigmoid"):
        autotune.measurements().record(
            kern, 128, "default", pallas_s=1e-4, xla_s=1e-2,
        )
    d = autotune.pallas_family_decision(128)
    assert d.choice is True and d.source == "measured"
    monkeypatch.setenv("MOOSE_TPU_PALLAS", "0")
    d = autotune.pallas_family_decision(128)
    assert d.choice is False and d.source == "override"


# ---------------------------------------------------------------------------
# Determinism: same measurements -> same plan, across processes
# ---------------------------------------------------------------------------


def test_measurements_snapshot_roundtrip():
    autotune.measurements().record(
        "dot_cross_terms", 128, "mxu", pallas_s=1.5, xla_s=2.5,
    )
    snap = autotune.measurements().snapshot()
    autotune.measurements().clear()
    assert autotune.measurements().get("dot_cross_terms", 128, "mxu") is None
    autotune.measurements().load(snap)
    row = autotune.measurements().get("dot_cross_terms", 128, "mxu")
    assert row == {"pallas_s": 1.5, "xla_s": 2.5}


def test_same_measurements_same_plan_same_process():
    comp = _dot_comp()
    plan1 = autotune.autotune_plan(comp, est_ops=4321)
    plan2 = autotune.autotune_plan(comp, est_ops=4321)
    assert plan2 is plan1  # weak cache
    autotune.reset_cache()
    plan3 = autotune.autotune_plan(comp, est_ops=4321)
    assert plan3.as_dict() == plan1.as_dict()


_SUBPROCESS_SCRIPT = """
import json, sys
sys.path.insert(0, {root!r})
from moose_tpu.compilation import autotune
autotune.measurements().load_file(sys.argv[1])
print(json.dumps({{
    "seg": autotune.segment_limit_for(4321).as_dict(),
    "minseg": autotune.worker_min_seg_for([2, 2, 3, 3, 5, 40]).as_dict(),
    "dot_tall": autotune.dot_kernel_decision(128, (1024, 128, 8)).as_dict(),
    "dot_small": autotune.dot_kernel_decision(128, (128, 100, 2)).as_dict(),
    "buckets": autotune.serving_bucket_plan(32).as_dict(),
    "family": autotune.pallas_family_decision(128).as_dict(),
}}))
"""


def test_decisions_deterministic_across_processes(tmp_path):
    """Feed the identical measurement snapshot to a fresh interpreter:
    every decision (choice, source, why) must come back verbatim."""
    rows = {
        ("dot_cross_terms", 128, "tall"): dict(pallas_s=1e-4, xla_s=1e-2),
        ("dot_cross_terms", 128, "small"): dict(pallas_s=1e-2, xla_s=1e-4),
        ("fx_mul", 128, "default"): dict(pallas_s=1e-4, xla_s=1e-2),
        ("bucket_latency", 0, "8"): dict(eval_s=0.010),
        ("bucket_latency", 0, "16"): dict(eval_s=0.0101),
        ("bucket_latency", 0, "32"): dict(eval_s=0.020),
    }
    for (kind, width, detail), vals in rows.items():
        autotune.measurements().record(kind, width, detail, **vals)
    snap_path = tmp_path / "measurements.json"
    snap_path.write_text(json.dumps(autotune.measurements().snapshot()))

    here = {
        "seg": autotune.segment_limit_for(4321).as_dict(),
        "minseg": autotune.worker_min_seg_for([2, 2, 3, 3, 5, 40]).as_dict(),
        "dot_tall": autotune.dot_kernel_decision(128, (1024, 128, 8)).as_dict(),
        "dot_small": autotune.dot_kernel_decision(128, (128, 100, 2)).as_dict(),
        "buckets": autotune.serving_bucket_plan(32).as_dict(),
        "family": autotune.pallas_family_decision(128).as_dict(),
    }

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    for knob in KNOBS:
        env.pop(knob, None)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "-c", _SUBPROCESS_SCRIPT.format(root=root),
         str(snap_path)],
        capture_output=True, text=True, timeout=300, env=env, cwd=root,
    )
    assert proc.returncode == 0, proc.stderr
    there = json.loads(proc.stdout.strip().splitlines()[-1])
    assert there == here


# ---------------------------------------------------------------------------
# Exactness discipline: the ladder outranks the autotuner
# ---------------------------------------------------------------------------


def test_divergent_dot_kernel_still_pinned_to_xla(monkeypatch):
    """A measurement that says the kernel is faster does NOT exempt it
    from the first-use bit-exactness check: a divergent kernel is
    pinned to the XLA path no matter what the measurements prefer."""
    autotune.measurements().record(
        "dot_cross_terms", 128, "tall", pallas_s=1e-6, xla_s=1.0,
    )
    shape = (1024, 128, 8)
    # the measured policy WANTS the kernel...
    assert autotune.dot_kernel_wanted(128, shape) is True

    def diverge(width):
        raise AssertionError("forced divergence (test)")

    monkeypatch.setitem(rk._CHECKS, "dot_cross_terms", diverge)
    saved_state = dict(rk._STATE)
    rk.set_enabled(True)
    try:
        rk._STATE.pop(("dot_cross_terms", 128), None)
        # ...but dispatch refuses it: the self-check diverged
        assert rk.dispatch("dot_cross_terms", 128, shape=shape) is False
        verdict = rk.report()["kernels"]["dot_cross_terms/128"]
        assert verdict == "fallback:diverged"
        # and stays refused on the next dispatch (pinned per process)
        assert rk.dispatch("dot_cross_terms", 128, shape=shape) is False
    finally:
        rk.set_enabled(None)
        with rk._STATE_LOCK:
            rk._STATE.clear()
            rk._STATE.update(saved_state)


def test_dot_micro_kernel_failure_is_a_recorded_fallback(monkeypatch):
    """Only ``ShapeUnsupported`` is "no measurement".  A kernel the
    backend refuses to compile or run while the A/B micro times it is a
    fallback like any other: verdict, exception text and counter."""
    monkeypatch.setitem(autotune._DOT_CLASS_SHAPES, "small", (4, 8, 2))
    saved_state = dict(rk._STATE)

    def fallbacks():
        return metrics.REGISTRY.value(
            "moose_tpu_pallas_fallback_total",
            kernel="dot_cross_terms", reason="error",
        )

    def kernel_raising(exc):
        def kernel(*a, **k):
            raise exc

        return kernel

    try:
        rk._STATE.pop(("dot_cross_terms", 64), None)
        before = fallbacks()
        monkeypatch.setattr(
            rk, "dot_cross_terms",
            kernel_raising(rk.ShapeUnsupported("too small (test)")),
        )
        assert autotune.measure_dot_micro(64, "small", iters=1) is None
        assert "dot_cross_terms/64" not in rk.report()["kernels"]
        assert fallbacks() == before

        monkeypatch.setattr(
            rk, "dot_cross_terms",
            kernel_raising(RuntimeError("Mosaic refused (test)")),
        )
        assert autotune.measure_dot_micro(64, "small", iters=1) is None
        report = rk.report()
        assert report["kernels"]["dot_cross_terms/64"] == "fallback:error"
        assert report["errors"]["dot_cross_terms/64"] == (
            "RuntimeError: Mosaic refused (test)"
        )
        assert fallbacks() == before + 1
    finally:
        rk.reset_state()
        rk._STATE.update(saved_state)


def test_dispatch_without_shape_keeps_xla():
    """Calls that cannot present a shape never get the dot kernel from
    the autotuner (the absolute knob is the only way in)."""
    autotune.measurements().record(
        "dot_cross_terms", 128, "tall", pallas_s=1e-6, xla_s=1.0,
    )
    rk.set_enabled(True)
    try:
        assert rk.dispatch("dot_cross_terms", 128) is False
    finally:
        rk.set_enabled(None)


def test_dot_kernel_bit_exact_with_forced_tiling():
    """The tiled kernel (multi m/n tiles + k segmentation with ring
    accumulation) agrees bit-for-bit with the limb_int8 XLA twin on an
    un-aligned shape, via the tile_plan override that forces 2 m-tiles
    x 2 k-segments cheaply in interpret mode."""
    import jax.numpy as jnp

    from moose_tpu.dialects import ring
    from moose_tpu.parallel import spmd

    rng = np.random.default_rng(0xD07)
    width = 64
    m, k, n = 10, 300, 3

    def rand(shape):
        return jnp.asarray(
            rng.integers(0, 1 << 64, size=shape, dtype=np.uint64)
        ), None

    x0, x1 = rand((3, m, k)), rand((3, m, k))
    y0, y1 = rand((3, k, n)), rand((3, k, n))
    ysum = ring.add(*y0, *y1)

    want = ring.add(
        *spmd._dot_contract(*x0, *ysum), *spmd._dot_contract(*x1, *y0)
    )
    got = rk.dot_cross_terms(
        x0, x1, y0, ysum, width, tile_plan=(8, 128, 256),
    )
    assert np.array_equal(np.asarray(got[0]), np.asarray(want[0]))


# ---------------------------------------------------------------------------
# Decision surface: last_plan / flight / metrics
# ---------------------------------------------------------------------------


def test_decision_surface_in_last_plan_flight_metrics():
    from moose_tpu.runtime import LocalMooseRuntime

    comp = _dot_comp()
    rng = np.random.default_rng(21)
    args = {"x": rng.normal(size=(3, 4)), "w": rng.normal(size=(4, 2))}

    plans_before = metrics.REGISTRY.value("moose_tpu_autotune_plans_total")
    rt = LocalMooseRuntime(["alice", "bob", "carole"])
    out = next(iter(
        rt.evaluate_computation(comp, arguments=args).values()
    ))
    np.testing.assert_allclose(
        np.asarray(out), args["x"] @ args["w"], atol=1e-4,
    )

    # last_plan carries the full decision table + the per-class dot
    # verdicts the trace-time dispatch made
    table = rt.last_plan["autotune"]
    assert set(table["decisions"]) >= {
        "segment_limit", "worker_min_seg", "coalesce",
        "pallas", "pallas_dot", "transport",
    }
    for entry in table["decisions"].values():
        assert entry["source"] in (
            "override", "measured", "predicted", "default",
        )
        assert isinstance(entry["why"], str) and entry["why"]
    assert isinstance(table["pallas_dot_classes"], dict)

    # metrics counted the fresh decision set
    plans_after = metrics.REGISTRY.value("moose_tpu_autotune_plans_total")
    assert plans_after >= plans_before + 1
    assert metrics.REGISTRY.value(
        "moose_tpu_autotune_decisions_total",
        knob="segment_limit",
        source=rt.last_plan["autotune"]["decisions"]["segment_limit"][
            "source"
        ],
    ) >= 1

    # the flight recorder carries the plan_autotuned event
    events = [
        e for e in flight.get_recorder().events()
        if e["kind"] == "plan_autotuned"
    ]
    assert events, "no plan_autotuned flight event recorded"
    assert "decisions" in events[-1] and "est_ops" in events[-1]


def test_override_threads_through_autotune_plan(monkeypatch):
    monkeypatch.setenv("MOOSE_TPU_JIT_SEGMENT", "777")
    comp = _dot_comp()
    plan = autotune.autotune_plan(comp, est_ops=100_000)
    seg = plan["segment_limit"]
    assert seg.source == "override" and seg.choice == 777


def test_schedule_uses_autotuned_min_seg(monkeypatch):
    """reconstruct_schedules' default path resolves the worker eager
    floor through the autotuner, so the worker plan, the MSA5xx/6xx
    analyzers, and the cost watchdog all see ONE schedule."""
    from moose_tpu.compilation.analysis.schedule import (
        reconstruct_schedules,
        worker_min_seg_decision,
    )

    comp = _dot_comp()
    decision = worker_min_seg_decision(comp)
    assert decision.knob == "worker_min_seg"
    scheds = reconstruct_schedules(comp)
    assert {"alice", "bob", "carole"} <= set(scheds)
    # explicit floor equal to the decision reproduces the default path
    explicit = reconstruct_schedules(comp, min_seg=decision.choice)
    for party in scheds:
        assert [
            seg.names for seg in scheds[party].segments
        ] == [seg.names for seg in explicit[party].segments]
