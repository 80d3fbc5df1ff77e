"""From a profiler trace (``.xplane.pb``) to device busy time, per-operation
time, collectives and idle gaps.  Read with ``jax.profiler.ProfileData``.

What a v5e trace written by jax 0.9 holds (looked at by hand, PR 22): one
plane ``/device:TPU:<n>`` per chip with the lines ``XLA Modules`` (one event
per program execution) and ``XLA Ops`` (one event per executed HLO
instruction, flat and sequential, named by the instruction's whole text);
and the plane ``/host:CPU`` with one line per thread, where
``jax.profiler.TraceAnnotation`` spans appear under their own names.  Host
and device clocks agree to about a millisecond.  (``Async XLA Ops``, one
event per asynchronous operation from its start to its done, is filled on
the first chip only, so it is not read.)

The traced window of a chip runs from the start of the first to the end of
the last *whole* execution of the step program (the module with most time);
the executions at the two ends of the trace may be cut and are left out.
"""

import bisect
import collections
import re

_DEVICE = re.compile(r"^/device:TPU:(\d+)$")
_OPCODE = re.compile(r" ([a-z][a-z0-9_-]*)\(")
_LAYOUT = re.compile(r"\{[^{}]*\}")
_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute", "collective-broadcast")
_MOSAIC = 'custom_call_target="tpu_custom_call"'
_SPAN_PREFIX = "bench."
#: what ``reduce`` averages over the chips, besides the per-name tables
_SCALARS = ("window_s", "busy_s", "custom_call_s", "collectives",
            "collective_s", "collective_exposed_s", "between_programs_s")
IN_PROGRAM = "inside the step program"
NO_SPAN = "host outside the benchmark's spans"


def _union(intervals):
    """Total length and merged list of possibly overlapping intervals."""
    merged = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return sum(b - a for a, b in merged), merged


def _clip(intervals, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def describe(name):
    """``(label, opcode, is_mosaic)`` of an ``XLA Ops`` event.  The label
    groups the instructions that differ only by their number: name without
    the number, opcode (with the fusion kind), result shape without layouts."""
    head, _, rest = name.partition(" = ")
    m = _OPCODE.search(" " + rest)
    if m is None:
        return name[:100], "?", False
    opcode = m.group(1)
    shape = _LAYOUT.sub("", rest[:max(m.start() - 1, 0)]).strip()
    kind = re.search(r"kind=k(\w+)", rest)
    mosaic = _MOSAIC in rest
    what = opcode + (f"/{kind.group(1).lower()}" if kind else "") + (
        "/mosaic" if mosaic else "")
    prefix = re.sub(r"[.\d]+$", "", head.lstrip("%"))
    named = what if prefix == opcode else f"{prefix} {what}"
    return f"{named} -> {shape}"[:120], what, mosaic


def _is_collective(opcode):
    return opcode.split("/")[0].startswith(_COLLECTIVES)


def _reduce_device(plane, spans, k):
    lines = {line.name: line for line in plane.lines}
    if "XLA Modules" not in lines or "XLA Ops" not in lines:
        raise SystemExit(f"benchmark: no device operation in the trace of "
                         f"{plane.name}")
    by_name = collections.defaultdict(list)
    for ev in lines["XLA Modules"].events:
        by_name[ev.name].append((ev.start_ns, ev.end_ns))
    runs = sorted(max(by_name.values(), key=lambda r: sum(b - a for a, b in r)))
    runs = runs[1:-1]                       # the two ends may be cut
    if not runs:
        raise SystemExit(f"benchmark: the trace of {plane.name} holds no "
                         f"whole execution of the step program")
    lo, hi = runs[0][0], runs[-1][1]

    busy, exposed, in_flight = [], [], []
    started = collections.defaultdict(collections.deque)   # kind -> starts
    mosaic_ns = 0
    ops = collections.defaultdict(lambda: [0, 0])       # label -> count, ns
    categories = collections.defaultdict(int)
    for a, b, name in sorted((ev.start_ns, ev.end_ns, ev.name)
                             for ev in lines["XLA Ops"].events):
        if a < lo or b > hi:
            continue
        label, what, mosaic = describe(name)
        busy.append((a, b))
        ops[label][0] += 1
        ops[label][1] += b - a
        categories[what] += b - a
        if mosaic:
            mosaic_ns += b - a
        if not _is_collective(what):
            continue
        # an asynchronous collective is in flight from the start of its
        # start operation to the end of its done operation (first started,
        # first done); only the two operations themselves hold the core
        exposed.append((a, b))
        if what.endswith("-start"):
            started[what[:-len("-start")]].append(a)
        elif what.endswith("-done"):
            waiting = started[what[:-len("-done")]]
            in_flight.append((waiting.popleft() if waiting else a, b))
        else:
            in_flight.append((a, b))
    busy_ns, merged = _union(busy)

    run_starts = [a for a, _ in runs]
    span_starts = [s[0] for s in spans]
    idle = collections.defaultdict(int)
    edges = [lo] + [t for iv in merged for t in iv] + [hi]
    for a, b in zip(edges[0::2], edges[1::2]):          # the gaps
        i = max(bisect.bisect_right(run_starts, a) - 1, 0)
        inside = sum(max(0, min(b, r1) - max(a, r0)) for r0, r1 in runs[i:i + 2])
        if inside:
            idle[IN_PROGRAM] += inside
        if b - a > inside:
            mid = (a + b) / 2
            j = bisect.bisect_right(span_starts, mid) - 1
            covering = [s[2] for s in spans[max(j - 1, 0):j + 1]
                        if s[0] <= mid <= s[1]]
            idle[covering[-1] if covering else NO_SPAN] += b - a - inside
    n_steps = len(runs) * k
    return {
        "steps": n_steps, "window_s": (hi - lo) / 1e9, "busy_s": busy_ns / 1e9,
        "custom_call_s": mosaic_ns / 1e9,
        "collectives": len(in_flight) / len(runs),
        "collective_s": _union(in_flight)[0] / 1e9,
        "collective_exposed_s": _union(exposed)[0] / 1e9,
        "between_programs_s": sum(
            v for name, v in idle.items() if name != IN_PROGRAM) / 1e9,
        "op_counts": {label: n / len(runs) for label, (n, _) in ops.items()},
        "op_seconds": {label: ns / 1e9 for label, (_, ns) in ops.items()},
        "categories": {c: ns / 1e9 for c, ns in categories.items()},
        "idle": {name: ns / 1e9 for name, ns in idle.items()},
    }


def _mean_dicts(dicts):
    keys = set().union(*dicts)
    return {key: sum(d.get(key, 0.0) for d in dicts) / len(dicts)
            for key in keys}


def reduce(path, k):
    """The reduced trace, averaged over the chips.  ``k``: steps in one
    execution of the step program.  Exits where no operation ran on a
    device: a host time is never reported under a device metric's name."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path) if path else None
    planes = sorted((p for p in (data.planes if data else [])
                     if _DEVICE.match(p.name)), key=lambda p: p.name)
    if not planes:
        raise SystemExit("benchmark: the profiler's trace has no device "
                         "plane; no device operation was traced")
    spans = sorted(
        (ev.start_ns, ev.end_ns, ev.name)
        for plane in data.planes if plane.name == "/host:CPU"
        for line in plane.lines for ev in line.events
        if ev.name.startswith(_SPAN_PREFIX))
    per_chip = [_reduce_device(p, spans, k) for p in planes]
    out = {"devices": len(per_chip),
           "steps": min(d["steps"] for d in per_chip)}
    out.update(_mean_dicts([{key: d[key] for key in _SCALARS}
                            for d in per_chip]))
    counts = _mean_dicts([d["op_counts"] for d in per_chip])
    seconds = _mean_dicts([d["op_seconds"] for d in per_chip])
    out["ops"] = [[label, counts[label], seconds[label]] for label in sorted(
        seconds, key=seconds.get, reverse=True)[:60]]
    out["categories"] = dict(sorted(
        _mean_dicts([d["categories"] for d in per_chip]).items(),
        key=lambda kv: -kv[1]))
    out["idle"] = dict(sorted(
        _mean_dicts([d["idle"] for d in per_chip]).items(),
        key=lambda kv: -kv[1]))
    return out


def breakdown(trace):
    """The result line's ``breakdown``: the ten groups of device operations
    that took most of the traced window (name, with the executions per step,
    and seconds), and the idle time by what covered it."""
    return {
        "device_ops": [[f"{label} x{count:g}", seconds]
                       for label, count, seconds in trace["ops"][:10]],
        "idle_gaps": [[name, seconds]
                      for name, seconds in list(trace["idle"].items())[:10]],
    }
