"""Plain reference of the secure dot, and the data of its cells.

Float64 NumPy and nothing else: no import from ``moose_tpu``, no value
the program has made.  The program rounds both operands to 2^-frac,
multiplies exactly on the ring and truncates once; against float64 the
error of an output is a sum of K rounding errors, so it grows as
sqrt(K) * 2^-frac.  The numbers compared are that error in those units,
which makes one limit hold at every size (the tests run at a small one).
"""

import math

import numpy as np


def make_case(config: dict, size: dict, distinct: int, seed: int) -> dict:
    """``distinct`` (x, y) pairs, N(0, 1), from the seed alone."""
    n = size["n"]
    rng = np.random.default_rng(seed)
    inputs = [
        {"x": rng.normal(size=(n, n)), "y": rng.normal(size=(n, n))}
        for _ in range(distinct)
    ]
    return {"inputs": inputs, "model": None}


def expected(config: dict, case: dict, i: int) -> np.ndarray:
    arguments = case["inputs"][i]
    return arguments["x"] @ arguments["y"]


def degraded(config: dict, case: dict, i: int) -> np.ndarray:
    """The control: this reference at the next precision below the
    configuration's, in the program's place."""
    frac = config["control"]["fixed"][1]
    arguments = case["inputs"][i]

    def q(a):
        return np.round(a * 2.0 ** frac) / 2.0 ** frac

    return q(q(arguments["x"]) @ q(arguments["y"]))


def numbers(config: dict, case: dict, i: int, got, want) -> dict:
    k = case["inputs"][i]["x"].shape[1]
    unit = math.sqrt(k) * 2.0 ** -config["fixed"][1]
    err = np.asarray(got, dtype=np.float64) - want
    return {
        "max_err_ulp_sqrt_k": float(np.abs(err).max() / unit),
        "rms_err_ulp_sqrt_k": float(np.sqrt(np.mean(err * err)) / unit),
    }
