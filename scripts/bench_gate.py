"""SLO / regression gate over bench records (ISSUE 12 tentpole #3).

``bench.py`` recorded the repo's perf trajectory for five rounds — and
nothing failed when the headline slid 69x -> 51x between rounds 3 and 5
(``BENCH_r04``/``r05`` are the records still committed).  This gate is
the tripwire: it diffs a fresh bench record against the committed
``BENCH_r*.json`` trajectory with
per-metric thresholds and exits nonzero on regression.  Future BENCH
rounds must pass it (see DEVELOP.md "Profiling" / "Benchmarks").

    python scripts/bench_gate.py --record fresh.json          # gate it
    python scripts/bench_gate.py --self-test                  # CI step

Record inputs accepted, in order of preference:

- a driver-style ``BENCH_r*.json`` wrapper (``{"parsed": {...}}``);
- a raw bench JSON record (the dict ``bench.py`` prints);
- raw ``bench.py`` stdout (the LAST parseable JSON line wins — the
  progressive-emission convention).

Threshold file (``benchmarks/bench_thresholds.json``)::

    {
      "vs_baseline": {
        "direction": "higher",          # higher|lower is better
        "max_regression_frac": 0.20,    # tolerated fractional slide
        "reference": "latest",          # latest|best over the trajectory
        "required": false,              # fail when the fresh record
                                        # lacks the metric (only once the
                                        # trajectory has established it)
        "floor": 69.0                   # absolute bound EVERY fresh
      },                                # record must meet, regardless of
      ...                               # how far the trajectory slid
    }

Per metric: ``reference`` resolves against every committed BENCH round
(``latest`` = the newest record carrying the metric, ``best`` = the best
value ever recorded); the fresh value fails when it regresses past
``reference * (1 -/+ max_regression_frac)``.  Metrics the trajectory has
never carried pass vacuously — the fresh record establishes their
baseline.

``floor`` is the escape from ratchet decay: relative thresholds follow
the trajectory down (69x -> 51x passed five rounds of "within 20% of
latest"), a floor does not move.  Floors bind FRESH records only — they
are the target the next committed round must clear, not a retroactive
judgment of the trajectory (``--self-test`` evaluates the committed
trajectory with floors disabled, then separately proves a below-floor
record trips).  ``--self-test`` proves the gate's own teeth: the merged
latest trajectory record must PASS, a synthetically regressed copy
(every gated metric pushed to 2x its tolerated slide) must FAIL, and
every floored metric must FAIL a record pushed just past its floor.
"""

from __future__ import annotations

import argparse
import glob
import json
import re
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
DEFAULT_THRESHOLDS = ROOT / "benchmarks" / "bench_thresholds.json"


def load_thresholds(path) -> dict:
    with open(path, encoding="utf-8") as fh:
        thresholds = json.load(fh)
    for metric, spec in thresholds.items():
        if spec.get("direction") not in ("higher", "lower"):
            raise ValueError(
                f"{metric}: direction must be 'higher' or 'lower'"
            )
        frac = spec.get("max_regression_frac")
        if not isinstance(frac, (int, float)) or frac < 0:
            raise ValueError(
                f"{metric}: max_regression_frac must be a number >= 0"
            )
        if spec.get("reference", "latest") not in ("latest", "best"):
            raise ValueError(
                f"{metric}: reference must be 'latest' or 'best'"
            )
        floor = spec.get("floor")
        if floor is not None and not isinstance(floor, (int, float)):
            raise ValueError(f"{metric}: floor must be a number")
    return thresholds


def trajectory_records(root=ROOT) -> list:
    """(round_name, parsed_record) for every committed BENCH_r*.json,
    oldest first."""
    out = []
    for path in sorted(
        glob.glob(str(root / "BENCH_r*.json")),
        key=lambda p: [int(t) for t in re.findall(r"\d+", Path(p).name)],
    ):
        try:
            with open(path, encoding="utf-8") as fh:
                doc = json.load(fh)
        except (OSError, json.JSONDecodeError):
            continue
        parsed = doc.get("parsed") if isinstance(doc, dict) else None
        if isinstance(parsed, dict):
            out.append((Path(path).stem, parsed))
    return out


def load_record(path) -> dict:
    """One fresh bench record from a wrapper / raw record / stdout."""
    text = Path(path).read_text(encoding="utf-8")
    try:
        doc = json.loads(text)
    except json.JSONDecodeError:
        doc = None
    if isinstance(doc, dict):
        parsed = doc.get("parsed")
        if isinstance(parsed, dict):
            return parsed
        return doc
    # bench.py stdout: progressive emission re-prints supersets, so the
    # LAST parseable JSON line is the fullest record
    record = None
    for line in text.splitlines():
        line = line.strip()
        if not line.startswith("{"):
            continue
        try:
            candidate = json.loads(line)
        except json.JSONDecodeError:
            continue
        if isinstance(candidate, dict):
            record = candidate
    if record is None:
        raise ValueError(f"no bench record found in {path}")
    return record


def resolve_reference(metric: str, spec: dict, trajectory) -> tuple:
    """(reference_value, source_round) over the trajectory, or
    (None, None) when no committed round ever carried the metric."""
    carried = [
        (name, record[metric])
        for name, record in trajectory
        if isinstance(record.get(metric), (int, float))
    ]
    if not carried:
        return None, None
    if spec.get("reference", "latest") == "best":
        pick = (
            max(carried, key=lambda nv: nv[1])
            if spec["direction"] == "higher"
            else min(carried, key=lambda nv: nv[1])
        )
        return pick[1], pick[0]
    return carried[-1][1], carried[-1][0]


def bound_for(spec: dict, reference: float) -> float:
    frac = float(spec["max_regression_frac"])
    if spec["direction"] == "higher":
        return reference * (1.0 - frac)
    return reference * (1.0 + frac)


def gate(record: dict, thresholds: dict, trajectory,
         enforce_floors: bool = True) -> dict:
    """Evaluate every thresholded metric; returns the machine-readable
    verdict ({"ok": bool, "results": {metric: {...}}}).

    ``enforce_floors=False`` skips the absolute-floor checks — used by
    ``--self-test`` when judging the committed trajectory, where floors
    are forward-looking targets rather than retroactive failures."""
    results = {}
    ok = True
    for metric, spec in sorted(thresholds.items()):
        reference, source = resolve_reference(metric, spec, trajectory)
        fresh = record.get(metric)
        entry = {
            "direction": spec["direction"],
            "reference": reference,
            "reference_round": source,
            "fresh": fresh,
        }
        floor = spec.get("floor")
        if floor is not None:
            entry["floor"] = floor
        if (
            enforce_floors
            and floor is not None
            and isinstance(fresh, (int, float))
        ):
            below = (
                fresh < floor
                if spec["direction"] == "higher"
                else fresh > floor
            )
            if below:
                entry["verdict"] = "FAIL(floor)"
                ok = False
                results[metric] = entry
                continue
        if reference is None:
            # the trajectory never carried it: the fresh record (if it
            # has the metric) ESTABLISHES the baseline — by design a
            # brand-new metric cannot fail its first gate
            entry["verdict"] = (
                "baseline-established"
                if isinstance(fresh, (int, float))
                else "no-data"
            )
        elif not isinstance(fresh, (int, float)):
            if spec.get("required", False):
                entry["verdict"] = "FAIL(missing)"
                ok = False
            else:
                entry["verdict"] = "missing"
        else:
            bound = bound_for(spec, float(reference))
            entry["bound"] = bound
            regressed = (
                fresh < bound
                if spec["direction"] == "higher"
                else fresh > bound
            )
            if regressed:
                entry["verdict"] = "FAIL(regressed)"
                ok = False
            else:
                entry["verdict"] = "pass"
        results[metric] = entry
    return {"ok": ok, "results": results}


def _print_verdict(verdict: dict, file=sys.stdout) -> None:
    for metric, entry in verdict["results"].items():
        ref = entry["reference"]
        fresh = entry["fresh"]
        bound = entry.get("bound")
        parts = [
            f"{entry['verdict']:<22}",
            f"{metric:<44}",
            f"fresh={fresh if fresh is not None else '-'}",
            f"ref={ref if ref is not None else '-'}",
        ]
        if entry.get("reference_round"):
            parts.append(f"({entry['reference_round']})")
        if bound is not None:
            parts.append(f"bound={bound:.6g}")
        if entry.get("floor") is not None:
            parts.append(f"floor={entry['floor']:.6g}")
        print(" ".join(parts), file=file)
    print(
        ("BENCH GATE: PASS" if verdict["ok"] else "BENCH GATE: FAIL"),
        file=file,
    )


def self_test(thresholds: dict, trajectory) -> int:
    """The gate must pass the real trajectory, fail a synthetically
    regressed copy of it, and fail a below-floor record — proof it has
    teeth, runnable in CI with no fresh bench."""
    if not trajectory:
        print("bench_gate --self-test: no BENCH_r*.json trajectory found")
        return 1
    # merged latest record: per metric, the newest round's value — the
    # "real one" of the acceptance criterion.  Floors are disabled for
    # THIS check: a floor is the bar the next round must clear, and
    # raising one above the current trajectory (e.g. vs_baseline back
    # to the r03 69x) must not brick CI retroactively.
    merged: dict = {}
    for _, record in trajectory:
        for key, value in record.items():
            if isinstance(value, (int, float)):
                merged[key] = value
    verdict = gate(merged, thresholds, trajectory, enforce_floors=False)
    if not verdict["ok"]:
        print("self-test FAILED: the real trajectory record was rejected")
        _print_verdict(verdict)
        return 1

    regressed = dict(merged)
    gated = 0
    for metric, spec in thresholds.items():
        reference, _ = resolve_reference(metric, spec, trajectory)
        if reference is None:
            continue
        gated += 1
        frac = 2.0 * float(spec["max_regression_frac"]) + 0.01
        if spec["direction"] == "higher":
            regressed[metric] = reference * max(0.0, 1.0 - frac)
        else:
            regressed[metric] = reference * (1.0 + frac)
    if gated == 0:
        print("self-test FAILED: no metric had a trajectory reference")
        return 1
    verdict_bad = gate(regressed, thresholds, trajectory)
    failed = [
        m for m, e in verdict_bad["results"].items()
        if e["verdict"].startswith("FAIL")
    ]
    if verdict_bad["ok"] or len(failed) < gated:
        print(
            "self-test FAILED: the synthetically regressed record "
            f"passed ({len(failed)}/{gated} metrics tripped)"
        )
        _print_verdict(verdict_bad)
        return 1

    # floor teeth: for every floored metric, a record sitting just past
    # the floor (but otherwise healthy) must trip FAIL(floor)
    floored = {
        m: spec for m, spec in thresholds.items()
        if spec.get("floor") is not None
    }
    floor_trips = 0
    for metric, spec in floored.items():
        probe = dict(merged)
        nudge = 0.99 if spec["direction"] == "higher" else 1.01
        probe[metric] = float(spec["floor"]) * nudge
        entry = gate(probe, thresholds, trajectory)["results"][metric]
        if entry["verdict"] != "FAIL(floor)":
            print(
                f"self-test FAILED: {metric} below its floor "
                f"{spec['floor']} got verdict {entry['verdict']!r}"
            )
            return 1
        floor_trips += 1
    print(json.dumps({
        "self_test": "ok",
        "gated_metrics": gated,
        "tripped_on_synthetic_regression": len(failed),
        "floored_metrics": floor_trips,
        "passing_real_record_metrics": sorted(
            m for m, e in verdict["results"].items()
            if e["verdict"] == "pass"
        ),
    }))
    return 0


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        prog="bench_gate", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    parser.add_argument(
        "--record", default=None,
        help="fresh bench record to gate (wrapper / raw record / "
        "bench.py stdout)",
    )
    parser.add_argument(
        "--thresholds", default=str(DEFAULT_THRESHOLDS),
        help=f"threshold file (default {DEFAULT_THRESHOLDS})",
    )
    parser.add_argument(
        "--baseline-dir", default=str(ROOT),
        help="directory holding the committed BENCH_r*.json trajectory",
    )
    parser.add_argument(
        "--self-test", action="store_true",
        help="prove the gate passes the real trajectory and fails a "
        "synthetic regression (CI step; no fresh record needed)",
    )
    parser.add_argument(
        "--json", action="store_true",
        help="print the machine-readable verdict instead of the table",
    )
    args = parser.parse_args(argv)

    thresholds = load_thresholds(args.thresholds)
    trajectory = trajectory_records(Path(args.baseline_dir))
    if args.self_test:
        return self_test(thresholds, trajectory)
    if not args.record:
        parser.error("--record is required (or use --self-test)")
    record = load_record(args.record)
    verdict = gate(record, thresholds, trajectory)
    if args.json:
        print(json.dumps(verdict))
    else:
        _print_verdict(verdict)
    return 0 if verdict["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
