"""The readings a cell's limits are set from, in one process and one
set-up: ``python3 -m chipbench.control --workload <cell> --seeds 12``.

For each seed: fresh inputs, a few evaluations through the driver's own
window, every answer against the plain reference (the program's
readings: the lower end of a limit), and on the first ``--control-seeds``
of them the control in the program's place: the reference computed at
the next precision below the configuration's (``control`` in its file),
compared the same way (the upper end).  Prints one JSON line per seed
and a summary; sets nothing.  The benchmark's own runs never run this.
"""

import argparse
import json
import sys

from chipbench import files, run


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", type=int, default=12)
    parser.add_argument("--first-seed", type=int, default=2500000001)
    parser.add_argument("--control-seeds", type=int, default=3)
    parser.add_argument("--evals", type=int, default=6)
    parser.add_argument("--rehearse", action="store_true")
    args = parser.parse_args(argv)

    ns = run.read_cell(args.workload)
    driver = files.load_module("drivers", ns.config["driver"])
    reference = files.load_module("reference", ns.config["reference"])

    import jax

    devices = jax.devices()
    if not args.rehearse and devices[0].platform != "tpu":
        print("chipbench.control: no TPU", file=sys.stderr)
        return 1
    from moose_tpu import compile_cache

    compile_cache.enable()
    state = driver.setup(run.context(ns, args.first_seed, args.rehearse))
    program, control = {}, {}
    for k in range(args.seeds):
        seed = args.first_seed + 7919 * k
        driver.reseed(state, seed)
        rec = driver.window(state, float("inf"), max_evals=args.evals)
        got = driver.check(state, rec)
        line = {"seed": seed, "program": got["numbers"],
                "failed": got["failed"], "plan": state.runtime.last_plan.get("plan_state")}
        for name, n in got["numbers"].items():
            program.setdefault(name, []).append(n["value"])
        if k < args.control_seeds:
            ctl = driver.check(state, rec, produce=reference.degraded)
            line["control"] = ctl["numbers"]
            line["control_correct"] = ctl["correct"]
            for name, n in ctl["numbers"].items():
                control.setdefault(name, []).append(n["value"])
        print(json.dumps(line), flush=True)
    print(json.dumps({
        "summary": args.workload, "device": devices[0].device_kind,
        "rehearsal": args.rehearse, "evals_per_seed": args.evals,
        "lower_max_of_program": {k: max(v) for k, v in program.items()},
        "program_all": program,
        "upper_min_of_control": {k: min(v) for k, v in control.items()},
        "control_all": control,
        "limits": ns.config["limits"],
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
