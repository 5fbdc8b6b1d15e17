"""What the program recorded of its own evaluations, for the per-layer
readers that split the host's share of one.

``moose_tpu.telemetry`` keeps the last 64 root span trees of the process
(``recent_roots``); one evaluation is one root named
``evaluate_computation``, and nothing evaluates between the window and
the readers, so the last ``view.evals`` such roots are the window's.
The tree's five leaves are taken whole (``input_fingerprint`` and
``input_upload`` once per large argument, ``dispatch``, ``device_wait``,
``host_transfer``); what no leaf covers, the self time of every span
above them, is ``runtime_self``.  The six rows therefore add up to the
root's duration.

The spans are on ``time.perf_counter``; each is also a
``jax.profiler.TraceAnnotation`` (``moose_tpu.<name>``) in the xplane,
which ``trace_reduce.to_plain`` does not keep yet (PERF.md, Open
questions).  A program without ``recent_roots`` (a parent commit) gives
``None`` and the readers leave their metrics out.
"""

ROOT = "evaluate_computation"
LEAVES = (
    "input_fingerprint", "input_upload", "dispatch", "device_wait",
    "host_transfer",
)
SELF = "runtime_self"
# what the host does while the chip waits for it: every row but the wait
HOST_ROWS = tuple(n for n in LEAVES if n != "device_wait") + (SELF,)


def _add(span, rows: dict) -> None:
    if span.name in LEAVES:
        rows[span.name] += span.duration_s
        return
    rows[SELF] += span.duration_s - sum(c.duration_s for c in span.children)
    for child in span.children:
        _add(child, rows)


def rows_ms(view):
    """``{row: mean ms per evaluation}`` over the window's evaluations,
    ``"root"`` (the whole of ``evaluate_computation``) among them; None
    where there is no device trace to lay them against (a rehearsal) or
    the program kept no trees."""
    if view.trace is None:
        return None
    from moose_tpu import telemetry

    recent_roots = getattr(telemetry, "recent_roots", None)
    if recent_roots is None or not view.evals:
        return None
    roots = recent_roots(ROOT)[-view.evals:]
    if not roots:
        return None
    rows = dict.fromkeys(LEAVES + (SELF,), 0.0)
    for root in roots:
        _add(root, rows)
    rows["root"] = sum(r.duration_s for r in roots)
    return {name: 1e3 * s / len(roots) for name, s in rows.items()}


def row_ms(view, name: str):
    rows = rows_ms(view)
    return None if rows is None else rows[name]
