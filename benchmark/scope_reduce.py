"""Device time per step under a ``jax.named_scope`` of the program's model
code, forward, backward and recomputed alike.

The same join as ``phase_reduce`` (whose parsing it reuses, unedited): the
compiled step's HLO text says which scopes an instruction was issued under
(the ``op_name`` of its metadata holds every enclosing scope), the profiler's
trace says how long the instruction ran.  A fusion counts whole for the scope
of its own metadata.  Where the program has no such scope (a checkout from
before the scope was written), a reader returns ``None`` and raises nothing.

Beside the readers' numbers, ``benchmark/out/<cell>/scopes.json`` holds the
milliseconds per step by the innermost ``apex.*`` scope of every instruction
(``apex.ssm.conv``, ``apex.ssm.scan``, ``apex.ssm.norm``, ``apex.ssm`` for what
is left of the mixer, and the phases'), for ``PERF.md``'s breakdown.
"""

import collections
import glob
import json
import os
import re

from benchmark import phase_reduce, trace_reduce

_memo = {}      # path of a trace -> (ns by op_name, steps of each chip)
_SCOPE = re.compile(r"apex\.[a-z_.]*[a-z_]")


def _by_op_name(path, k, hlo):
    """Nanoseconds of the device events inside whole executions of the step
    program, summed by the ``op_name`` of the instruction that ran."""
    from jax.profiler import ProfileData

    issued_under = {}
    for name, rest in phase_reduce._INSTRUCTION.findall(hlo):
        m = phase_reduce._OP_NAME.search(rest)
        issued_under[name] = m.group(1) if m else ""
    took, steps = collections.Counter(), []
    for plane in ProfileData.from_file(path).planes:
        lines = {line.name: line for line in plane.lines}
        if not (trace_reduce._DEVICE.match(plane.name)
                and "XLA Modules" in lines and "XLA Ops" in lines):
            continue
        runs = phase_reduce._step_runs(lines)
        steps.append((len(runs) - 2) * k)
        events = ((ev.start_ns, ev.end_ns, ev.name)
                  for ev in lines["XLA Ops"].events)
        for op_name, _, ns in phase_reduce._inside_runs(events, runs,
                                                        issued_under):
            took[op_name] += ns
    return took, steps


def ms_per_step(ctx, scope):
    """Milliseconds per step, averaged over the chips, of the device
    operations whose ``op_name`` contains ``scope``; ``None`` where no
    instruction of the step was issued under it, or there is no trace."""
    if not ctx.hlo:
        return None
    out_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)), "out",
                           ctx.workload)
    found = sorted(glob.glob(os.path.join(out_dir, "trace", "**",
                                          "*.xplane.pb"), recursive=True))
    if not found:
        return None
    if found[-1] not in _memo:
        _memo[found[-1]] = _by_op_name(found[-1], ctx.k, ctx.hlo)
        _write_by_scope(out_dir, *_memo[found[-1]])
    took, steps = _memo[found[-1]]
    under = [ns for op_name, ns in took.items() if scope in op_name]
    if not under or not steps or min(steps) < 1:
        return None
    return sum(under) * 1e-6 / (len(steps) * min(steps))


def _write_by_scope(out_dir, took, steps):
    if not steps or min(steps) < 1:
        return
    innermost = collections.Counter()
    for op_name, ns in took.items():
        innermost[(_SCOPE.findall(op_name) or ["none"])[-1]] += ns
    per_step = 1e-6 / (len(steps) * min(steps))
    with open(os.path.join(out_dir, "scopes.json"), "w", encoding="utf-8") as f:
        json.dump({"ms_per_step_by_innermost_scope": {
            scope: ns * per_step for scope, ns in sorted(innermost.items())}},
            f, indent=1)
