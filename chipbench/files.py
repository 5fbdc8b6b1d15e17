"""Finding the benchmark's files by the names in ``BENCHMARK.json``: a
missing one fails by name."""

import importlib
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def read_json(path: str, what: str, root: str = ROOT) -> dict:
    if not os.path.exists(path):
        raise SystemExit(
            f"chipbench: no {what} file {os.path.relpath(path, root)}"
        )
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """``chipbench/<kind>/<name>.py`` as a module."""
    qualified = f"chipbench.{kind}.{name}"
    try:
        return importlib.import_module(qualified)
    except ModuleNotFoundError as e:
        if e.name != qualified:
            raise
        raise SystemExit(
            f"chipbench: no {kind[:-1]} file chipbench/{kind}/{name}.py"
        ) from e
