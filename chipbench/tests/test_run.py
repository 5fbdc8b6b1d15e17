"""The harness: the last line's shape under ``--rehearse``, and cells
whose files are missing failing by name."""

import json
import os
import shutil

import pytest

from chipbench import run

ROOT = run.ROOT
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def _last_line(capsys):
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("cell", ["dot-2048"])
@pytest.mark.parametrize("trace", [0, 1])
def test_last_line_under_rehearse(cell, trace, capsys):
    code = run.main([
        "--workload", cell, "--seed", "2147483659", "--seconds", "2",
        "--trace", str(trace), "--rehearse",
    ])
    assert code == 0
    last = _last_line(capsys)
    assert RESULT_KEYS <= set(last)
    assert last["rehearsal"] is True  # never a device number
    assert list(last)[-1] == "compared"
    assert last["correct"] is True and last["failed"] == 0
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(last["device"])
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    if trace == 0:
        assert set(last["metrics"]) == {m["name"] for m in bench["end_to_end"]}
    else:
        # the CPU's trace has no device plane: only the readers that
        # count from the program report
        assert set(last["metrics"]) == {"pinned_ops", "compiles_in_window"}
    for metric in last["metrics"].values():
        assert set(metric) == {"value", "unit"}
    for number in last["compared"].values():
        assert number["value"] <= number["limit"]


def test_benchmark_json_lists_every_reader_as_it_describes_itself():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    listed = {m["name"]: m for m in bench["per_layer"]}
    modules = {m.NAME: m for m in run.layer_metric_modules()}
    assert set(listed) == set(modules)
    cells = [w["name"] for w in bench["workloads"]]
    for name, module in modules.items():
        entry = listed[name]
        assert entry["unit"] == module.UNIT and entry["layer"] == module.LAYER
        assert entry["moves"] == module.MOVES and entry["source"] == module.SOURCE
        assert entry["better"] == module.BETTER
        assert entry["workloads"] == (getattr(module, "WORKLOADS", None) or cells)


@pytest.fixture
def copy_of_the_benchmark(tmp_path, monkeypatch):
    """BENCHMARK.json and chipbench/'s data files in a directory of
    their own, for a test to break."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for kind in ("configs", "traffic"):
        shutil.copytree(
            os.path.join(ROOT, "chipbench", kind), tmp_path / "chipbench" / kind
        )
    monkeypatch.setattr(run, "ROOT", str(tmp_path))
    monkeypatch.setattr(run, "HERE", str(tmp_path / "chipbench"))
    return tmp_path


ARGS = ["--seed", "1", "--seconds", "1", "--trace", "0", "--rehearse"]


def test_unknown_cell_fails_by_name(copy_of_the_benchmark):
    with pytest.raises(SystemExit, match="no cell 'dot-4096'"):
        run.main(["--workload", "dot-4096"] + ARGS)


def test_missing_configuration_file_fails_by_name(copy_of_the_benchmark):
    os.remove(copy_of_the_benchmark / "chipbench/configs/secure-dot-r128.json")
    with pytest.raises(SystemExit, match="chipbench/configs/secure-dot-r128.json"):
        run.main(["--workload", "dot-2048"] + ARGS)


def test_missing_traffic_file_fails_by_name(copy_of_the_benchmark):
    os.remove(copy_of_the_benchmark / "chipbench/traffic/closed1-square-2048.json")
    with pytest.raises(SystemExit, match="chipbench/traffic/closed1-square-2048.json"):
        run.main(["--workload", "dot-2048"] + ARGS)


def test_missing_driver_file_fails_by_name(copy_of_the_benchmark):
    path = copy_of_the_benchmark / "chipbench/configs/secure-dot-r128.json"
    config = json.loads(path.read_text())
    config["driver"] = "served_loop"
    path.write_text(json.dumps(config))
    with pytest.raises(SystemExit, match="chipbench/drivers/served_loop.py"):
        run.main(["--workload", "dot-2048"] + ARGS)


def test_no_tpu_is_a_failure_and_prints_no_result(capsys):
    code = run.main([
        "--workload", "dot-2048", "--seed", "1", "--seconds", "1", "--trace", "0",
    ])
    assert code != 0
    assert capsys.readouterr().out.strip() == ""
