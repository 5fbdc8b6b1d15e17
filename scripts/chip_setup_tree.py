"""One ``chipbench`` run, then what the program kept of the evaluations
before its window: ``python3 scripts/chip_setup_tree.py <out.json>
<chipbench.run's arguments>`` from the root of a checkout (with
``--trace 1``: a window of 8 leaves set-up's trees among the 64 kept).

Nothing is hooked and nothing patched: ``chipbench.run.main`` runs as
the driver runs it and prints its lines; afterwards the set-up trees
(``chipbench/setup_spans.py``'s) are written to ``<out.json>`` and
printed in ``telemetry.report()``'s form (a parent commit's too, for a
span-by-span comparison), and the cost of the
``jax.monitoring`` listeners is measured on this machine (a region's
opening and closing under an open span, and the events the trees
count), for PERF.md's account of what the instrumentation costs.
"""

import json
import os
import sys
import time


def _plain(span) -> dict:
    return {
        "name": span.name, "s": span.duration_s, "attrs": span.attrs,
        "children": [_plain(c) for c in span.children],
    }


def _show(span, depth: int = 0) -> None:
    """``telemetry.report()``'s form (a parent of PR 37 prints only the
    thread's last tree)."""
    attrs = " ".join(f"{k}={v}" for k, v in span.attrs.items())
    print(
        f"{'  ' * depth}{span.name}: {span.duration_s * 1e3:.3f} ms {attrs}",
        file=sys.stderr,
    )
    for child in span.children:
        _show(child, depth + 1)


def _total(span, attr: str):
    return span.attrs.get(attr, 0) + sum(_total(c, attr) for c in span.children)


def _listener_cost_s(regions: int = 200_000) -> float:
    """Seconds a region costs the listeners, back to back (in a trace,
    with everything else between two events, the chip's host paid two
    to three times this: PERF.md, PR 37): what JAX reports at its
    close, with a span open, as a first call fires it."""
    import jax

    from moose_tpu import telemetry

    event = "/jax/core/compile/jaxpr_trace_duration"
    with telemetry.span("listener_cost"):
        t0 = time.perf_counter()
        for _ in range(regions):
            jax.monitoring.record_event_duration_secs(event, 0.0, fun_name="f")
        return (time.perf_counter() - t0) / regions


def main(argv) -> int:
    out, bench_args = argv[0], argv[1:]
    sys.path.insert(0, os.getcwd())
    from chipbench import run, setup_spans

    code = run.main(bench_args)
    from moose_tpu import telemetry

    # every first call of a binding and every validating evaluation (a
    # parent of PR 37 has the spans and none of JAX's seconds on them)
    first_calls = [
        r for r in telemetry.recent_roots(setup_spans.ROOT)
        if r.find("build_plan") or r.find("ladder_validate")
    ]
    per_region = _listener_cost_s()
    # a trace counts itself; a compile stands for its lowering too
    regions = sum(
        _total(r, "jax_traces") + 2 * _total(r, "compiles") for r in first_calls
    )
    record = {
        "argv": bench_args, "exit": code,
        "first_call_trees": [_plain(r) for r in first_calls],
        "listener": {
            "seconds_a_region": per_region, "regions": regions,
            "seconds": per_region * regions,
        },
    }
    os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
    with open(out, "w") as f:
        json.dump(record, f, default=str)
    for root in first_calls:
        _show(root)
    print(json.dumps({"phase": "listener", **record["listener"]}), file=sys.stderr)
    return code


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
