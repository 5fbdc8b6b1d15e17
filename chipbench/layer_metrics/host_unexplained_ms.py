"""Where the two clocks meet.  Per evaluation: the device trace's own
host gap (the benchmark's span round the call less device-busy time
inside it, what ``host_gap_ms`` reports) less the program's host-side
rows (every row of ``program_spans`` but ``device_wait``).  Near 0: the
program's spans account for the chip's idle time.  Above: the chip also
idles while the host waits for it, or something outside the spans holds
it.  Below: device work overlaps a host span."""

from chipbench import program_spans

NAME = "host_unexplained_ms"
UNIT = "ms"
BETTER = "lower"
SOURCE = "program_span"
LAYER = "eDSL + runtime"
MOVES = "evals_per_s"


def read(view):
    rows = program_spans.rows_ms(view)
    if rows is None:
        return None
    evaluations = view.trace["evaluations"]
    gap_s = sum(e["span_s"] - e["busy_s"] for e in evaluations)
    host_ms = sum(rows[name] for name in program_spans.HOST_ROWS)
    return 1e3 * gap_s / len(evaluations) - host_ms
