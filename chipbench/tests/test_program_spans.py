"""The program's own span trees as the per-layer readers see them: real
roots from CPU evaluations through the cell's driver, laid against a
constructed device trace (``view.trace["evaluations"]``)."""

import types

import pytest

from chipbench import program_spans, run
from chipbench.drivers import eval_loop

EVALS = 3
READERS = {
    "input_fingerprint_ms": "input_fingerprint",
    "input_upload_ms": "input_upload",
    "dispatch_ms": "dispatch",
    "device_wait_ms": "device_wait",
    "host_transfer_ms": "host_transfer",
    "runtime_self_ms": program_spans.SELF,
}


def _modules():
    return {m.NAME: m for m in run.layer_metric_modules()}


def _view(size=None):
    """``EVALS`` evaluations of dot-2048's computation at a small size
    (the mix's ``rehearse_size`` unless given), and a view whose device
    trace says the chip was busy for half of each ``device_wait`` and
    the benchmark's span was 1 ms longer than the program's root."""
    from moose_tpu import telemetry

    ns = run.read_cell("dot-2048")
    size = size or ns.traffic["rehearse_size"]
    ctx = types.SimpleNamespace(
        config=ns.config, traffic=ns.traffic, seed=2147483659, size=size,
    )
    state = eval_loop.setup(ctx)
    rec = eval_loop.window(state, 60.0, max_evals=EVALS)
    assert not rec.errors
    roots = telemetry.recent_roots(program_spans.ROOT)[-EVALS:]
    trace = {"evaluations": [
        {
            "span_s": root.duration_s + 1e-3,
            "busy_s": root.find("device_wait").duration_s / 2,
        }
        for root in roots
    ]}
    view = types.SimpleNamespace(
        cell=ns.cell, config=ns.config, size=size, trace=trace,
        evals=len(rec.ends),
    )
    return view, roots


@pytest.fixture(scope="module")
def rehearsed():
    return _view()


def test_rows_add_up_to_the_root(rehearsed):
    view, roots = rehearsed
    rows = program_spans.rows_ms(view)
    root_ms = 1e3 * sum(r.duration_s for r in roots) / len(roots)
    assert rows["root"] == pytest.approx(root_ms)
    covered = sum(rows[n] for n in program_spans.LEAVES + (program_spans.SELF,))
    assert covered == pytest.approx(root_ms, rel=0.01)
    assert rows["dispatch"] > 0 and rows["host_transfer"] > 0
    assert rows[program_spans.SELF] > 0


def test_each_reader_reads_its_row(rehearsed):
    view, roots = rehearsed
    rows = program_spans.rows_ms(view)
    modules = _modules()
    for name, row in READERS.items():
        assert modules[name].read(view) == pytest.approx(rows[row]), name
    # gap = root + 1 ms - wait / 2, and the host rows are root - wait
    wait_ms = rows["device_wait"]
    assert modules["host_unexplained_ms"].read(view) == pytest.approx(
        1.0 + wait_ms / 2, rel=0.01, abs=1e-6,
    )


def test_only_the_windows_evaluations_are_read(rehearsed):
    view, roots = rehearsed
    one = types.SimpleNamespace(**{**vars(view), "evals": 1})
    assert program_spans.rows_ms(one)["root"] == pytest.approx(
        1e3 * roots[-1].duration_s
    )


def test_large_arguments_are_fingerprinted_and_resident():
    """Over the device cache's 64 KiB floor (96 x 96 float64): a hash
    per argument on every call, and after set-up's first pass over the
    cycled inputs no upload."""
    view, roots = _view({"n": 96})
    rows = program_spans.rows_ms(view)
    assert rows["input_fingerprint"] > 0
    for root in roots:
        names = [s.name for s in root.find("bind_arguments").children]
        assert names.count("input_fingerprint") == 2
    covered = sum(rows[n] for n in program_spans.LEAVES + (program_spans.SELF,))
    assert covered == pytest.approx(rows["root"], rel=0.01)


def test_readers_are_silent_without_a_device_trace(rehearsed):
    view, _ = rehearsed
    blind = types.SimpleNamespace(**{**vars(view), "trace": None})
    modules = _modules()
    for name in list(READERS) + ["host_unexplained_ms"]:
        assert modules[name].read(blind) is None, name


def test_readers_are_silent_on_a_program_without_recent_roots(
    rehearsed, monkeypatch
):
    """The parent commit's ``telemetry`` keeps one tree a thread and has
    no ``recent_roots``: nothing to read, and nothing raised."""
    from moose_tpu import telemetry

    monkeypatch.delattr(telemetry, "recent_roots")
    view, _ = rehearsed
    modules = _modules()
    for name in list(READERS) + ["host_unexplained_ms"]:
        assert modules[name].read(view) is None, name
