"""The device time of a training step, split by the phases the program names.

``apex_tpu.training.make_train_step`` wraps what it issues in
``jax.named_scope``s (``apex.cast``, ``apex.forward``, ``apex.allreduce``,
``apex.scaler``, ``apex.optimizer``, ``apex.metrics``).  A scope is metadata:
it reaches the compiled module as the ``op_name`` of each instruction, and it
does not reach the profiler's trace, where an ``XLA Ops`` event is named by
the instruction's text *without* its metadata.  So the phase of a device
operation is found by joining the two on the instruction's name: the compiled
step's HLO text says which scope issued an instruction, the trace says how
long it ran.

What the join cannot see:

* A fusion is attributed whole to the scope of its own metadata (that of the
  instruction XLA built it around).  Where XLA fused the last operation of
  one phase into the first of the next, the whole fusion counts for one.
* Instructions the compiler adds without metadata (copies, prefetches) are
  ``other``, which is what ``unattributed_ms_per_step`` reports.
* JAX's persistent compile cache leaves metadata out of its key.  A step
  executable read from a cache that a checkout without the scopes wrote
  carries that checkout's HLO, with no ``apex.`` scope in it; ``phases``
  then says so and reports nothing.
"""

import bisect
import collections
import glob
import json
import os
import re
import time

from benchmark import trace_reduce

PHASES = ("forward", "backward", "optimizer", "cast", "scaler", "allreduce",
          "other")
#: first match wins; ``apex.forward`` is split by ``_phase_of``
_SCOPES = (("apex.optimizer", "optimizer"), ("apex.scaler", "scaler"),
           ("apex.allreduce", "allreduce"), ("apex.cast", "cast"),
           ("apex.metrics", "other"))
_FORWARD = "apex.forward"
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([^\s=]+) = (.*)$", re.M)
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_SCOPED = re.compile(r'op_name="[^"]*apex\.')
_NO_SCOPE = (
    "benchmark: no instruction of the step executable carries an 'apex.' "
    "scope, so no phase is reported: either this checkout's make_train_step "
    "has no scopes, or the step executable came from a compile cache written "
    "before the scopes existed (the cache's key leaves metadata out); clear "
    "it or point JAX_COMPILATION_CACHE_DIR elsewhere")
_memo = {}      # path of a trace -> phases(): six readers, one parse


def _phase_of(op_name):
    for scope, phase in _SCOPES:
        if scope in op_name:
            return phase
    at = op_name.find(_FORWARD)
    if at < 0:
        return "other"
    # the transposed equations of the scope, custom_vjp backward rules and
    # the forward rematerialised for them: all of it is what backward costs
    return "backward" if "transpose(" in op_name[:at] else "forward"


def scopes_of(hlo_text):
    """``{instruction name: phase}`` for every instruction of a compiled
    module's text, from the ``op_name`` of its metadata (no metadata:
    ``other``).  Instruction names are unique in a module, so the entry
    computation, loop bodies and called computations share one table; the
    inner instructions of fused computations are in it too and never match
    an event, because a fusion executes, and is attributed, as one."""
    out = {}
    for name, rest in _INSTRUCTION.findall(hlo_text):
        m = _OP_NAME.search(rest)
        out[name] = _phase_of(m.group(1)) if m else "other"
    return out


def _inside_runs(events, runs, scope_of):
    """``(phase, name, ns)`` of every event inside a whole execution."""
    runs = runs[1:-1]                       # the two ends may be cut
    starts = [a for a, _ in runs]
    for a, b, name in events:
        i = bisect.bisect_right(starts, a) - 1
        if i >= 0 and b <= runs[i][1]:
            instruction = name.partition(" = ")[0].strip().lstrip("%")
            yield scope_of.get(instruction, "other"), name, b - a


def attribute(events, runs, scope_of):
    """Seconds per phase of the ``(start_ns, end_ns, name)`` device events
    that lie inside whole executions of the step program.  ``runs``: every
    execution, sorted; the first and the last may be cut by the trace and are
    left out, as in ``trace_reduce``.  An event's instruction is its name up
    to `` = ``, without ``%``; one the module does not have is ``other``.
    ``XLA Ops`` is flat and sequential, so the phases add up to the device's
    busy time inside those executions."""
    out = dict.fromkeys(PHASES, 0.0)
    for phase, _, ns in _inside_runs(events, runs, scope_of):
        out[phase] += ns / 1e9
    return out


def _step_runs(lines):
    """Every execution of the step program (the module with most time)."""
    by_name = collections.defaultdict(list)
    for ev in lines["XLA Modules"].events:
        by_name[ev.name].append((ev.start_ns, ev.end_ns))
    return sorted(max(by_name.values(), key=lambda r: sum(b - a for a, b in r)))


def _reduce(path, k, scope_of):
    """Per phase, averaged over the chips like ``trace_reduce.reduce``: ms
    per step, the same split by opcode (``trace_reduce.describe``'s: ``pad``,
    ``fusion/loop``, ``custom-call/mosaic``), and the ten groups of
    operations that took most of it."""
    from jax.profiler import ProfileData

    by_label = {phase: collections.Counter() for phase in PHASES}    # ns
    by_opcode = {phase: collections.Counter() for phase in PHASES}
    steps = []
    for plane in ProfileData.from_file(path).planes:
        lines = {line.name: line for line in plane.lines}
        if not (trace_reduce._DEVICE.match(plane.name)
                and "XLA Modules" in lines and "XLA Ops" in lines):
            continue
        runs = _step_runs(lines)
        steps.append((len(runs) - 2) * k)
        events = ((ev.start_ns, ev.end_ns, ev.name)
                  for ev in lines["XLA Ops"].events)
        for phase, name, took in _inside_runs(events, runs, scope_of):
            label, opcode, _ = trace_reduce.describe(name)
            by_label[phase][label] += took
            by_opcode[phase][opcode] += took
    if not steps or min(steps) < 1:
        return None
    ms_per_step = 1e-6 / (len(steps) * min(steps))
    return {phase: {
        "ms_per_step": sum(by_label[phase].values()) * ms_per_step,
        "by_opcode": {opcode: took * ms_per_step
                      for opcode, took in by_opcode[phase].most_common()},
        "largest": [[label, took * ms_per_step]
                    for label, took in by_label[phase].most_common(10)]}
        for phase in PHASES}


def _join(ctx, path, out_dir):
    if _SCOPED.search(ctx.hlo) is None:
        print(_NO_SCOPE, flush=True)
        return None
    t0 = time.perf_counter()
    detail = _reduce(path, ctx.k, scopes_of(ctx.hlo))
    if detail is None:
        return None
    with open(os.path.join(out_dir, "phases.json"), "w",
              encoding="utf-8") as f:
        json.dump(detail, f, indent=1)
    split = {phase: d["ms_per_step"] for phase, d in detail.items()}
    print("phases, ms per step: " + ", ".join(
        f"{phase} {ms:.3f}" for phase, ms in split.items())
        + f" (joined in {time.perf_counter() - t0:.1f} s)", flush=True)
    return split


def phases(ctx):
    """``{phase: ms per step}`` of a traced run, or ``None`` where the step
    executable carries no scope (said once, on the output) or there is no
    trace.  Also writes ``out/<workload>/phases.json`` (per phase: ms per
    step, that by opcode, and the ten largest groups of operations) for
    whoever has to find what a phase's time is made of."""
    out_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)), "out",
                           ctx.workload)
    found = sorted(glob.glob(os.path.join(out_dir, "trace", "**",
                                          "*.xplane.pb"), recursive=True))
    if not found:
        return None
    if found[-1] not in _memo:
        _memo[found[-1]] = _join(ctx, found[-1], out_dir)
    return _memo[found[-1]]


def ms_per_step(ctx, *names):
    """What a reader returns: the sum of these phases, or ``None``."""
    split = phases(ctx)
    return None if split is None else sum(split[name] for name in names)
