"""From the profiler's trace to what the per-layer metrics read.

``jax.profiler`` writes ``<dir>/plugins/profile/<time>/*.xplane.pb``;
``to_plain`` turns it into plain lists (the form the test's recorded
trace is kept in), ``reduce`` into: the traced window, the seconds in
which an operation ran on the device (union of the intervals of the
device's op line, averaged over the chips), device time by operation
name (self time: a ``while`` does not count its body twice) and by
layer group, the benchmark's own host spans on the same clock, the busy
time inside each evaluation, and the idle gaps by what the host was
doing.

On a v5e the op line's event names are whole HLO instructions
(``%fusion.31 = u32[..] fusion(..), kind=kOutput, calls=..``);
``parse_op`` takes the name, the opcode, the fusion kind and the custom
call's target out of one.  Groups (``classify``):

- ``mxu``: ``convolution`` and ``dot`` instructions and ``kOutput``
  fusions, which is how XLA:TPU fuses a convolution with what consumes
  it (dot-2048's 272 of them are its 272 limb convolutions): the ring
  matmul as XLA runs it;
- ``pallas``: custom calls to ``tpu_custom_call``: the Mosaic ring
  kernels.  They all carry the name ``%core`` today, so the tiled dot
  kernel, where the autotuner picks it, counts here and not under
  ``mxu`` until the program names its kernels (PERF.md, Open questions);
- ``xla_rest``: every other device operation: the stacked protocol as
  plain XLA (limb split and recombine, PRF draws, shares, reveal).
"""

import glob
import os
import re

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "chipbench."
EVALUATE_SPAN = SPAN_PREFIX + "evaluate"
MOSAIC_TARGET = "tpu_custom_call"


def newest_xplane(trace_dir: str) -> str:
    paths = glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    )
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def to_plain(xplane_path: str, keep_host_prefix: str = SPAN_PREFIX) -> dict:
    """``{"planes": [{"name", "lines": [{"name", "events": [[name,
    start_ns, duration_ns], ...]}]}]}``.  Of host planes only the
    benchmark's own spans are kept; of device planes everything."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(xplane_path)
    planes = []
    for plane in data.planes:
        is_device = plane.name.startswith(DEVICE_PLANE_PREFIX)
        lines = []
        for line in plane.lines:
            events = [
                [ev.name, float(ev.start_ns), float(ev.duration_ns)]
                for ev in line.events
                if is_device or ev.name.startswith(keep_host_prefix)
            ]
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


_AFTER_SHAPE = re.compile(r"\s*([a-z][\w\-]*)\(")
_KIND = re.compile(r"\bkind=(\w+)")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def parse_op(text: str) -> dict:
    """``name``, ``opcode``, ``kind`` and ``target`` of an op event's
    name.  A name that is not an HLO instruction is its own opcode."""
    name, sep, rest = text.partition(" = ")
    if not sep:
        return {"name": text, "opcode": text, "kind": "", "target": ""}
    if rest.startswith("("):  # a tuple shape: skip to its closing paren
        depth = 0
        for i, c in enumerate(rest):
            depth += c == "("
            depth -= c == ")"
            if depth == 0:
                break
        after = rest[i + 1:]
    else:
        after = rest.partition(" ")[2]
    opcode = _AFTER_SHAPE.match(" " + after)
    # operands are named after the opcode's paren; attributes follow the
    # operand list, so search kind= and the target from the end
    kind = _KIND.search(after)
    target = _TARGET.search(after)
    return {
        "name": name,
        "opcode": opcode.group(1) if opcode else "",
        "kind": kind.group(1) if kind else "",
        "target": target.group(1) if target else "",
    }


def classify(op: dict) -> str:
    if op["opcode"] in ("convolution", "dot"):
        return "mxu"
    if op["opcode"] == "fusion" and op["kind"] == "kOutput":
        return "mxu"
    if op["opcode"] == "custom-call" and op["target"] == MOSAIC_TARGET:
        return "pallas"
    return "xla_rest"


def label(op: dict) -> str:
    """The short name ``breakdown`` shows: instances of one kind of op
    together (``%fusion.31`` and ``%fusion.77`` as ``%fusion*``)."""
    base = re.sub(r"[.\d]+$", "", op["name"])
    parts = [base + "*", op["opcode"]]
    if op["kind"]:
        parts.append(op["kind"])
    if op["target"]:
        parts.append(op["target"])
    return " ".join(parts)


def _union(intervals):
    """Sorted, merged copies of (start, end) intervals."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1][1] = end
        else:
            merged.append([start, end])
    return merged


def _covered(merged, lo: float, hi: float) -> float:
    """Length of the part of [lo, hi] that ``merged`` covers."""
    total = 0.0
    for start, end in merged:
        if end <= lo:
            continue
        if start >= hi:
            break
        total += min(end, hi) - max(start, lo)
    return total


def _self_times(events):
    """``{name: self ns}`` of one line's events: an event that encloses
    others (a loop, a conditional) counts its own time less theirs."""
    out = {}
    stack = []  # (end, name)
    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        end = start + dur
        while stack and stack[-1][0] <= start:
            stack.pop()
        if stack:
            parent = stack[-1][1]
            out[parent] = out.get(parent, 0.0) - min(dur, stack[-1][0] - start)
        out[name] = out.get(name, 0.0) + dur
        stack.append((end, name))
    return out


def _clip(events, lo, hi):
    return [
        [n, max(s, lo), min(s + d, hi) - max(s, lo)]
        for n, s, d in events if s + d > lo and s < hi
    ]


def reduce(plain: dict) -> dict:
    """See the module's docstring.  Seconds throughout."""
    spans = []
    for plane in plain["planes"]:
        if plane["name"].startswith(DEVICE_PLANE_PREFIX):
            continue
        for line in plane["lines"]:
            spans += [e for e in line["events"] if e[0].startswith(SPAN_PREFIX)]
    spans.sort(key=lambda e: e[1])
    evaluates = [e for e in spans if e[0] == EVALUATE_SPAN]
    if not evaluates:
        raise ValueError(f"no {EVALUATE_SPAN} span in the trace")
    lo = evaluates[0][1]
    hi = max(s + d for _, s, d in evaluates)

    device_lines = []
    for plane in plain["planes"]:
        if not plane["name"].startswith(DEVICE_PLANE_PREFIX):
            continue
        ops = [l for l in plane["lines"] if l["name"] == OPS_LINE]
        if not ops:
            raise ValueError(f"no {OPS_LINE!r} line in plane {plane['name']}")
        device_lines.append(_clip(ops[0]["events"], lo, hi))
    if not device_lines:
        raise ValueError("no device plane in the trace")
    chips = len(device_lines)

    busy_ns = 0.0
    op_ns, group_ns = {}, {"mxu": 0.0, "pallas": 0.0, "xla_rest": 0.0}
    for events in device_lines:
        busy_ns += sum(e - s for s, e in _union(
            (s, s + d) for _, s, d in events
        ))
        for name, ns in _self_times(events).items():
            op_ns[name] = op_ns.get(name, 0.0) + ns
    by_label = {}
    for name, ns in op_ns.items():
        op = parse_op(name)
        group_ns[classify(op)] += ns
        by_label[label(op)] = by_label.get(label(op), 0.0) + ns

    # per evaluation and for the gaps: the first device's line (one chip
    # today; a cell across chips reads its own planes in its own reader)
    merged = _union((s, s + d) for _, s, d in device_lines[0])
    per_eval, edge_ops = [], {}
    for _, s, d in evaluates:
        inside = [m for m in merged if m[1] > s and m[0] < s + d]
        edge_ops[s] = (inside[0][0], inside[-1][1]) if inside else None
        per_eval.append({
            "span_s": d / 1e9,
            "busy_s": _covered(merged, s, s + d) / 1e9,
            "first_op_after_s": (inside[0][0] - s) / 1e9 if inside else None,
            "last_op_before_end_s": (
                (s + d - inside[-1][1]) / 1e9 if inside else None
            ),
        })

    # idle time by what the host was doing: each benchmark span is a
    # phase (an evaluation in three: before its first device op, between
    # its ops, after its last), and what no span covers is a phase too
    phases, cursor = [], lo
    for name, s, d in spans:
        short = name[len(SPAN_PREFIX):]
        s, e = max(s, cursor), min(s + d, hi)
        if e <= s:
            continue
        if s > cursor:
            phases.append(("between_spans", cursor, s))
        edges_of = edge_ops.get(s) if name == EVALUATE_SPAN else None
        if edges_of is None:
            phases.append((short, s, e))
        else:
            first, last = edges_of
            phases += [
                (short + ":before_first_op", s, first),
                (short + ":between_ops", first, last),
                (short + ":after_last_op", last, e),
            ]
        cursor = e
    if hi > cursor:
        phases.append(("between_spans", cursor, hi))
    gaps = {}
    for what, p_lo, p_hi in phases:
        idle = (p_hi - p_lo) - _covered(merged, p_lo, p_hi)
        if idle > 0:
            gaps[what] = gaps.get(what, 0.0) + idle

    def top(d, scale):
        return [
            [k, v / scale] for k, v in
            sorted(d.items(), key=lambda kv: -kv[1])[:10]
        ]

    return {
        "chips": chips,
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy_ns / chips / 1e9,
        "evaluations": per_eval,
        "group_s": {k: v / chips / 1e9 for k, v in group_ns.items()},
        "device_ops": top(by_label, chips * 1e9),
        "idle_gaps": top(gaps, 1e9),
        "n_op_names": len(op_ns),
    }
