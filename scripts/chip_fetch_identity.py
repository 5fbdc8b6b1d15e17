"""On the chip: a float64 result fetched as its two float32 halves is
the float64 ``np.asarray`` of the same device array gives, bit for bit.

    chiprun --chips 1 -- python3 scripts/chip_fetch_identity.py [--keys 32]

Three parts, one process, one line of JSON each (and all of it in
``chiprun_out/fetch_identity.json``):

``plain``   float64 arrays that are no fixed-point decode (a wide
            exponent spread, lows that are subnormal in float32, +-0,
            inf, nan, values outside float32's range), uploaded and
            also recomputed on the device, staged and fetched through
            ``_stage_user_value`` / ``_fetch_user_value`` and compared
            with ``np.asarray`` on the ``uint64`` views, class by class,
            with the form each class took (``halves`` or ``direct``).
``cell``    ``dot-2048``'s four input pairs (chipbench's own set-up and
            data from ``--seed``), ``--keys`` evaluations of each pair,
            every one under the fresh master key the runtime draws: the
            array the user receives against ``np.asarray`` of the device
            array it was staged from.
``events``  three evaluations under ``jax.profiler``: the runtime's
            ``X64FromTuple`` / ``Delinearize`` events of the host plane,
            by name, with their milliseconds, and the host-clock
            milliseconds of either fetch of one 2048 x 2048 result.

Exit code 0 only if every comparison is identical.  ``--rehearse`` runs
the same code in the sandbox (CPU, the mix's tiny size, the platform
test stood in for): it finds wrong paths, and its numbers mean nothing.
"""

import argparse
import glob
import json
import os
import shutil
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
OUT = os.path.join(ROOT, "chiprun_out", "fetch_identity.json")
TRACE_DIR = os.path.join(ROOT, ".chipbench_trace", "fetch_identity")


def same_bits(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    return a.view(np.uint64) == b.view(np.uint64)


def form_of(interp, staged) -> str:
    return "halves" if interp._joined(staged) else "direct"


def plain_classes(rng, n: int) -> dict:
    """Float64 arrays of ``n`` elements, one per class of value."""
    def scaled(lo_exp, hi_exp):
        return rng.normal(size=n) * 2.0 ** rng.integers(lo_exp, hi_exp, size=n)

    def cycle(*members):
        return np.resize(np.array(members), n)

    return {
        # these the halves carry
        "normal": rng.normal(size=n),
        "wide_exponents": scaled(-40, 120),
        "infs_nans_plus_zero": cycle(0.0, np.inf, -np.inf, np.nan, -np.nan, 1.0, -1.0),
        "beyond_float32": rng.normal(size=n) * 2.0 ** 200,
        "fixed_14_23": np.round(rng.normal(size=n) * 2.0 ** 30) / 2.0 ** 23,
        "fixed_24_40": np.round(rng.normal(size=n) * 2.0 ** 60) / 2.0 ** 40,
        # these the device says they may not, and the float64 is fetched:
        # |x| under 2^-74, where a low half can be subnormal in float32
        "float32_subnormal_lows": scaled(-110, -80),
        "float32_subnormal_highs": rng.normal(size=n) * 2.0 ** -140,
        "minus_zero": cycle(-0.0, 0.0, 1.0, -1.0),
    }


def part_plain(interp, values, seed: int, n: int) -> dict:
    import jax

    from moose_tpu import dtypes as dt

    def both(device_array):
        staged = interp._stage_user_value(
            values.HostTensor(device_array, "carole", dt.float64)
        )
        jax.block_until_ready(staged)
        return (
            interp._fetch_user_value(staged), np.asarray(device_array),
            form_of(interp, staged),
        )

    recompute = jax.jit(lambda x: x * 3.0 + x)
    report = {}
    for name, host in plain_classes(np.random.default_rng(seed), n).items():
        uploaded = jax.device_put(host)
        for how, device_array in (
            ("uploaded", uploaded), ("recomputed", recompute(uploaded)),
        ):
            joined, direct, form = both(device_array)
            same = same_bits(joined, direct)
            worst = np.flatnonzero(~same)[:3]
            report[f"{name}.{how}"] = {
                "form": form, "n": int(same.size),
                "identical": int(same.sum()),
                # what the chip cannot hold of the host's float64, either way
                "direct_equals_host_input": int(same_bits(direct, host).sum())
                if how == "uploaded" else None,
                "examples": [
                    [float(direct[i]).hex(), float(joined[i]).hex()] for i in worst
                ],
            }
    return report


def part_cell(interp, values, seed: int, keys: int, rehearse: bool) -> dict:
    from chipbench import files, run as bench

    ns = bench.read_cell("dot-2048")
    driver = files.load_module("drivers", ns.config["driver"])
    state = driver.setup(bench.context(ns, seed, rehearse))

    raw, drawn = [], set()
    stage, draw = interp._stage_user_value, interp.master_key_words

    def recording_stage(value):
        staged = stage(value)
        raw.append((value, form_of(interp, staged)))
        return staged

    def recording_draw(*a, **kw):
        key = draw(*a, **kw)
        drawn.add(bytes(np.asarray(key)))
        return key

    interp._stage_user_value = recording_stage
    interp.master_key_words = recording_draw
    pairs = len(state.case["inputs"])
    per_pair = [{"evaluations": 0, "identical": 0, "elements_differ": 0}
                for _ in range(pairs)]
    forms = {}
    try:
        for i in range(pairs * keys):
            out = state.evaluate(i)
            (value, form), = raw
            raw.clear()
            forms[form] = forms.get(form, 0) + 1
            direct = np.asarray(value.value)
            same = same_bits(np.asarray(out), direct)
            row = per_pair[i % pairs]
            row["evaluations"] += 1
            row["identical"] += int(same.all())
            row["elements_differ"] += int((~same).sum())
    finally:
        interp._stage_user_value, interp.master_key_words = stage, draw
    return {
        "seed": seed, "n": int(out.shape[0]), "per_pair": per_pair,
        "distinct_master_keys": len(drawn), "forms": forms,
        "plan": state.runtime.last_plan.get("plan_state"),
    }, state


def part_events(interp, values, state, n: int) -> dict:
    import jax
    from jax.profiler import ProfileData

    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    jax.profiler.start_trace(TRACE_DIR)
    try:
        for i in range(3):
            state.evaluate(i)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(
        os.path.join(TRACE_DIR, "plugins", "profile", "*", "*.xplane.pb")
    )
    events = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if any(w in ev.name for w in ("X64", "Delinearize", "host_transfer")):
                    events.setdefault(ev.name, []).append(
                        round(ev.duration_ns / 1e6, 3)
                    )
    shutil.rmtree(TRACE_DIR, ignore_errors=True)

    from moose_tpu import dtypes as dt

    # host-clock ms of either fetch of one fresh n x n float64 result
    fresh = jax.jit(lambda x, k: x + k)
    base = jax.device_put(np.random.default_rng(0).normal(size=(n, n)))
    ms = {"direct": [], "halves": []}
    for k in range(6):
        device_array = jax.block_until_ready(fresh(base, float(k)))
        t0 = time.perf_counter()
        np.asarray(device_array)
        ms["direct"].append(round((time.perf_counter() - t0) * 1e3, 2))
        device_array = jax.block_until_ready(fresh(base, float(k) + 0.5))
        t0 = time.perf_counter()
        staged = interp._stage_user_value(
            values.HostTensor(device_array, "carole", dt.float64)
        )
        interp.prefetch_to_host(staged)
        jax.block_until_ready(staged)
        t1 = time.perf_counter()
        interp._fetch_user_value(staged)
        t2 = time.perf_counter()
        ms["halves"].append(
            [round((t1 - t0) * 1e3, 2), round((t2 - t1) * 1e3, 2)]
        )
    return {"host_plane_events_ms": events, "fetch_ms": ms,
            "fetch_ms_halves_is": "[stage and wait, fetch and join]"}


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--seed", type=int, default=2718281828)
    parser.add_argument("--keys", type=int, default=32)
    parser.add_argument("--rehearse", action="store_true")
    args = parser.parse_args(argv)

    import jax

    from moose_tpu import compile_cache, metrics, values
    from moose_tpu.execution import interpreter as interp

    platform = jax.devices()[0].platform
    if args.rehearse:
        interp._lives_on_tpu = lambda arr: isinstance(arr, jax.Array)
        from moose_tpu.native import ring128_kernels as rk

        rk.set_enabled(True)
    elif platform != "tpu":
        print(f"needs a TPU; JAX found {platform}", file=sys.stderr)
        return 1
    compile_cache.enable()

    report = {"device": jax.devices()[0].device_kind, "platform": platform,
              "rehearsal": args.rehearse}

    def say(name, part):
        report[name] = part
        print(json.dumps({name: part}), flush=True)

    say("plain", part_plain(interp, values, args.seed, 1 << 15))
    cell, state = part_cell(interp, values, args.seed, args.keys, args.rehearse)
    say("cell", cell)
    say("events", part_events(interp, values, state, cell["n"]))
    say("result_fetch_total", metrics.REGISTRY.snapshot().get(
        "moose_tpu_result_fetch_total", {}).get("values", {}))

    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    with open(OUT, "w") as f:
        json.dump(report, f, indent=1)
    plain_ok = all(
        row["identical"] == row["n"] for row in report["plain"].values()
    )
    cell_ok = all(
        row["identical"] == row["evaluations"] for row in cell["per_pair"]
    )
    print(json.dumps({"plain_identical": plain_ok, "cell_identical": cell_ok}))
    return 0 if plain_ok and cell_ok else 2


if __name__ == "__main__":
    sys.exit(main())
