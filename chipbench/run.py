"""One cell, once: ``python3 -m chipbench.run --workload <cell> --seed <n>
--seconds <s> --trace <0|1>`` from the root of a checkout.

One process, no child.  The cell's configuration, traffic mix, driver
and per-layer metrics are files found by the names in ``BENCHMARK.json``
(``chipbench/README.md``); this file lists none of them.  Anything but a
TPU with the cell's number of chips is a non-zero exit and no result
line; ``--rehearse`` (for the sandbox only) runs the same control flow at
the mix's tiny size on whatever JAX finds, and says so in its last line:
nothing it prints is a device number.

Order: read the cell -> the driver's set-up (data from ``--seed``, the
computation, evaluations until the plan has settled) -> ``setup_s``
stops -> the window -> the device's peak memory is read -> the driver
drops the program's state -> the plain reference over the kept answers
decides ``correct`` -> with ``--trace 1`` the trace is reduced and each
per-layer reader is asked -> earlier lines on stdout (plan, kernels,
counters), the numbers compared on stderr, and as the last line of
stdout the result object.
"""

import time

T_START = time.perf_counter()  # before anything heavy is imported

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pkgutil  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

from chipbench import files  # noqa: E402

ROOT = files.ROOT
HERE = os.path.join(ROOT, "chipbench")
TRACE_DIR = os.path.join(ROOT, ".chipbench_trace")
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def say(record: dict) -> None:
    print(json.dumps(record, default=str), flush=True)


def read_cell(name: str) -> types.SimpleNamespace:
    """The cell's entry in ``BENCHMARK.json`` and the files it names."""
    bench = files.read_json(os.path.join(ROOT, "BENCHMARK.json"), "benchmark", ROOT)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(
            f"chipbench: no cell {name!r} in BENCHMARK.json "
            f"(cells: {sorted(cells)})"
        )
    cell = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    if cell["config"] not in configs:
        raise SystemExit(
            f"chipbench: cell {name!r} names configuration "
            f"{cell['config']!r}, which BENCHMARK.json does not list"
        )
    config = files.read_json(
        os.path.join(ROOT, configs[cell["config"]]["file"]), "configuration", ROOT
    )
    traffic = files.read_json(
        os.path.join(HERE, "traffic", cell["traffic"] + ".json"), "traffic", ROOT
    )
    return types.SimpleNamespace(
        cell=cell, config=config, traffic=traffic, bench=bench
    )


def layer_metric_modules() -> list:
    """Every module under ``chipbench/layer_metrics/``: one per-layer
    metric each, found by file, listed nowhere."""
    package = importlib.import_module("chipbench.layer_metrics")
    return [
        importlib.import_module(f"chipbench.layer_metrics.{m.name}")
        for m in sorted(pkgutil.iter_modules(package.__path__), key=lambda m: m.name)
    ]


def device_record(devices) -> dict:
    """The device as JAX reports it.  ``memory_peak_bytes`` is the peak
    on the fullest chip of what the allocator handed out
    (``peak_bytes_in_use``: arguments, results, compiled code, an eager
    run's intermediates) plus what it reserved for compiled programs'
    temporaries (``peak_bytes_reserved``): on a v5e the first does not
    count the second (PERF.md, PR 25).  The two peaks need not coincide,
    so the sum bounds the true peak from above; both parts are printed."""
    stats = [d.memory_stats() or {} for d in devices]
    say({"phase": "memory", "stats": stats})
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": int(max(
            s.get("peak_bytes_in_use", 0) + s.get("peak_bytes_reserved", 0)
            for s in stats
        )),
    }


def counters() -> dict:
    """The program's own counts and plan facts (sound today, PERF.md)."""
    from moose_tpu import metrics
    from moose_tpu.native import ring128_kernels as rk

    snap = metrics.REGISTRY.snapshot()
    return {
        "pallas_dispatch_total": snap.get(
            "moose_tpu_pallas_dispatch_total", {}
        ).get("values", {}),
        "pallas_fallback_total": snap.get(
            "moose_tpu_pallas_fallback_total", {}
        ).get("values", {}),
        "pallas": rk.report(),
    }


def context(ns, seed: int, rehearse: bool) -> types.SimpleNamespace:
    """What a driver is given: the cell's files, the seed and the size."""
    return types.SimpleNamespace(
        config=ns.config, traffic=ns.traffic, seed=seed,
        size=ns.traffic["rehearse_size" if rehearse else "size"],
    )


def per_layer(view) -> dict:
    """Ask every reader that applies to this cell; one that finds
    nothing to read returns None and is left out of the line."""
    out = {}
    for module in layer_metric_modules():
        cells = getattr(module, "WORKLOADS", None)
        if cells is not None and view.cell["name"] not in cells:
            continue
        value = module.read(view)
        if value is not None:
            out[module.NAME] = {"value": value, "unit": module.UNIT}
    return out


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument(
        "--rehearse", action="store_true",
        help="sandbox only: any platform, the mix's tiny size; the last "
        "line says so and no number in it is a device number",
    )
    parser.add_argument(
        "--keep-plain", metavar="PATH",
        help="with --trace 1: also write the trace in trace_reduce's "
        "plain form (how the tests' recorded trace was made)",
    )
    args = parser.parse_args(argv)

    ns = read_cell(args.workload)
    driver = files.load_module("drivers", ns.config["driver"])

    import jax

    devices = jax.devices()
    devices_s = time.perf_counter() - T_START
    if not args.rehearse and (
        devices[0].platform != "tpu" or len(devices) < ns.cell["chips"]
    ):
        print(
            f"chipbench: cell {args.workload!r} needs {ns.cell['chips']} TPU "
            f"chip(s); JAX found {len(devices)} x {devices[0].platform}",
            file=sys.stderr,
        )
        return 1
    devices = devices[: ns.cell["chips"]]

    from moose_tpu import compile_cache

    cache_dir = compile_cache.enable()
    if args.rehearse:
        from moose_tpu.native import ring128_kernels as rk

        rk.set_enabled(True)  # the chip's default; interpret mode here

    compiles = []  # (perf_counter at the end, seconds) of every compile
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **kw: compiles.append((time.perf_counter(), secs))
        if event == COMPILE_EVENT else None
    )

    ctx = context(ns, args.seed, args.rehearse)
    state = driver.setup(ctx)
    setup_plan = dict(state.runtime.last_plan)
    setup_s = time.perf_counter() - T_START

    if args.trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        jax.profiler.start_trace(TRACE_DIR)
        try:
            rec = driver.window(
                state, args.seconds, max_evals=ns.traffic["trace_evals"],
                annotate=jax.profiler.TraceAnnotation,
            )
        finally:
            jax.profiler.stop_trace()
    else:
        rec = driver.window(state, args.seconds)

    device = device_record(devices)
    plan = dict(state.runtime.last_plan)
    program = counters()
    driver.release(state)
    t0 = time.perf_counter()
    verdict = driver.check(state, rec)
    check_s = time.perf_counter() - t0

    in_window = [s for t, s in compiles if rec.t_open <= t <= rec.t_close]
    metrics = dict(driver.end_to_end(rec))
    metrics["setup_s"] = setup_s
    units = {m["name"]: m["unit"] for m in ns.bench["end_to_end"]}
    result = {
        "correct": verdict["correct"],
        "attempted": verdict["attempted"],
        "failed": verdict["failed"],
        "metrics": {
            k: {"value": v, "unit": units.get(k, "")} for k, v in metrics.items()
        },
        "device": device,
    }

    if args.trace:
        from chipbench import trace_reduce

        t0 = time.perf_counter()
        try:
            plain = trace_reduce.to_plain(trace_reduce.newest_xplane(TRACE_DIR))
            if args.keep_plain:
                with open(args.keep_plain, "w") as f:
                    json.dump(plain, f)
            reduced = trace_reduce.reduce(plain)
        except (ValueError, FileNotFoundError) as e:
            if not args.rehearse:
                raise
            reduced = None  # the CPU's trace has no device plane
            say({"phase": "trace", "rehearsal_has_no_device_trace": str(e)})
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        view = types.SimpleNamespace(
            cell=ns.cell, config=ns.config, size=ctx.size, trace=reduced,
            evals=len(rec.ends), window_s=rec.seconds, plan=plan,
            compiles_in_window=len(in_window), counters=program,
            device_kind=device["kind"], end_to_end=metrics,
        )
        result["metrics"] = per_layer(view)
        if reduced is not None:
            result["device"]["busy_s"] = reduced["busy_s"]
            result["device"]["window_s"] = reduced["window_s"]
            result["breakdown"] = {
                "device_ops": reduced["device_ops"],
                "idle_gaps": reduced["idle_gaps"],
            }
            say({
                "phase": "trace", "reduce_s": time.perf_counter() - t0,
                "group_s": reduced["group_s"],
                "n_op_names": reduced["n_op_names"],
                "evaluations": reduced["evaluations"],
            })

    say({
        "phase": "plan", "after_setup": setup_plan, "after_window": plan,
        "setup_evals": state.setup_evals,
        "setup_plan_states": state.setup_plan_states,
        "setup_phases": {"to_devices_s": devices_s, **state.setup_phases},
        "compile_cache_dir": cache_dir, "prf": ns.config["prf"],
        "compiles_in_setup": len(compiles) - len(in_window),
        "compiles_in_window": in_window,
    })
    say({"phase": "program", **program})
    say({
        "phase": "window", "seconds": rec.seconds, "evaluations": len(rec.ends),
        "compared": verdict["compared"], "check_s": check_s,
        "errors": rec.errors[:8], "outside": verdict["outside"],
        "eval_ms": [round((e - b) * 1e3) for b, e in zip(rec.starts, rec.ends)],
        "end_to_end": metrics,
    })
    # the numbers compared, each beside its limit: last on stderr, and
    # last in the result line
    print(
        "chipbench compared: " + json.dumps(verdict["numbers"]),
        file=sys.stderr, flush=True,
    )
    if args.rehearse:
        result["rehearsal"] = True
    result["compared"] = verdict["numbers"]
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
