"""What the program recorded of the evaluations before the window, for
the per-layer readers that split ``setup_s``.

``moose_tpu.telemetry`` keeps the last 64 root span trees of the process
(``recent_roots``), one ``evaluate_computation`` root an evaluation; the
last ``view.evals`` are the traced window's (``program_spans``), and
those before them are set-up's: at most 12 of the driver's and 8 of the
window.  On their first call the program's spans carry JAX's own
seconds as attributes (``jax_trace_s``, ``jax_lower_s``,
``backend_compile_s``: each second once, on the innermost span open
when JAX reported it), and the first call's work has spans of its own
(``record_key``, ``pallas_selfcheck``, ``ladder_validate``, ...).

Every second of a tree goes to one row: JAX's seconds to ``jax_trace``,
``lower`` or ``compile`` whichever span carries them, and what a span
has left after its children and the seconds it carries to the row of
the nearest span above it (itself included) that ``OWNERS`` names, else
to ``unexplained``.  So the rows add up to ``evals``, the trees' whole
durations, by construction.

The spans are on ``time.perf_counter`` and need no device trace: the
readers report under ``--rehearse --trace 1`` too.  A program whose
spans carry none of the attributes (a parent commit) gives ``None``,
and the readers leave their metrics out.
"""

ROOT = "evaluate_computation"
# attribute -> the row its seconds go to
CARRIED = {
    "jax_trace_s": "jax_trace",
    "jax_lower_s": "lower",
    "backend_compile_s": "compile",
}
NAMED = "named"  # spans whose names say what they are: no row to split
UNEXPLAINED = "unexplained"
# span name -> the row that takes what the span, and every span under it
# that is not named here itself, has left
OWNERS = {
    # ``lower(...).as_text()``, the computation's serialization, digests
    "record_key": "lower",
    "pallas_selfcheck": "kernel_checks",
    # the candidate's and the twin's runs and their comparison
    "ladder_validate": "validate",
    **dict.fromkeys((
        "trace", "autotune", "build_plan", "bind_arguments",
        "candidate_build", "verdict_read", "autotune_measure",
        "device_wait", "host_transfer",
    ), NAMED),
}
ROWS = (
    "jax_trace", "lower", "compile", "kernel_checks", "validate", NAMED,
    UNEXPLAINED,
)


def _owner(span, inherited: str) -> str:
    if span.name == "plan_verdict":  # the lookup is its children's
        return NAMED if span.attrs.get("op") == "store" else inherited
    return OWNERS.get(span.name, inherited)


def _add(span, inherited: str, rows: dict) -> None:
    owner = _owner(span, inherited)
    left = span.duration_s - sum(c.duration_s for c in span.children)
    for attr, row in CARRIED.items():
        seconds = span.attrs.get(attr, 0.0)
        rows[row] += seconds
        left -= seconds
    rows[owner] += left
    rows["cache_misses"] += span.attrs.get("cache_misses", 0)
    for child in span.children:
        _add(child, owner, rows)


def setup_roots(view) -> list:
    """The ``evaluate_computation`` trees from before the window."""
    from moose_tpu import telemetry

    recent_roots = getattr(telemetry, "recent_roots", None)
    if recent_roots is None:
        return []
    roots = recent_roots(ROOT)
    return roots[: max(0, len(roots) - view.evals)]


def rows_s(view):
    """``{row: seconds}`` over set-up's evaluations: ``ROWS``, their sum
    ``"evals"``, and the count ``"cache_misses"``; None where the
    program kept no such tree or its spans carry none of JAX's seconds."""
    roots = setup_roots(view)
    rows = dict.fromkeys(ROWS + ("cache_misses",), 0)
    for root in roots:
        _add(root, UNEXPLAINED, rows)
    if not (rows["jax_trace"] or rows["compile"]):  # no first call speaks
        return None
    rows["evals"] = sum(r.duration_s for r in roots)
    return rows


def row_s(view, name: str):
    rows = rows_s(view)
    return None if rows is None else rows[name]
