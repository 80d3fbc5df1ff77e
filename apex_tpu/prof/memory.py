"""HBM memory ledger — peak device memory per compiled step, attributed
per :func:`apex_tpu.prof.capture.scope` region (ISSUE 10 tentpole,
piece 3).

The stack measures FLOPs (``prof.roofline``) and wire bytes
(``collective`` events) everywhere but has had zero visibility into
HBM — the resource that actually kills runs first at scale (an OOM is
instant; a 20% MFU gap is Tuesday).  This module is the missing column:

1. **compiled totals** (:func:`harvest_memory`) — XLA's own accounting
   from ``jit(fn).lower(*args).compile().memory_analysis()``:
   argument / output / temp / generated-code bytes (the numbers the
   compiler actually reserves), when the jax in use exposes the API;
2. **live-buffer jaxpr walk** — a conservative fallback (and ALWAYS the
   per-region attribution source, mirroring how
   :func:`apex_tpu.prof.roofline.harvest_costs` keeps the matmul split
   on the walk): replay the jaxpr tracking which buffers are live after
   each equation (an output is born at its equation, dies after its
   last use; jaxpr outputs never die), record the running total's peak
   and snapshot the live set AT the peak — each buffer attributed to
   the :func:`~apex_tpu.prof.capture.region_path` region that produced
   it.  Conservative: no donation/aliasing, no XLA rematerialization —
   an upper bound XLA usually beats;
3. **the join** — :func:`apex_tpu.prof.roofline.mfu_ledger` takes
   ``memory=`` and adds a peak-HBM column (totals + per-region peak
   attribution + top allocations) to the roofline ledger ``bench.py``
   records in ``BENCH_EXTRA.json``;
4. **live gauges + watchdog** — :func:`device_memory` reads the
   backend's per-device allocator stats where exposed
   (``Device.memory_stats()``: TPU yes, CPU no), published as
   ``hbm_bytes_in_use``/``hbm_bytes_limit`` gauges by the Prometheus
   exporter, and :func:`record_memory` emits the ``memory`` event the
   ``memory_headroom`` watchdog rule folds (headroom below threshold →
   debounced alert BEFORE the OOM, not a post-mortem).

Everything here is trace/compile-time or host-API work: nothing runs on
the device, nothing is donated, and the training step's own jit cache
is untouched.

CLI::

    python -m apex_tpu.prof.memory --fn mymod:make_step [--json]
"""

from __future__ import annotations

import json
import math
import sys
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence

import jax
from jax.extend.core import Literal

from .capture import region_path

__all__ = ["MemoryHarvest", "harvest_memory", "live_buffer_walk",
           "stats_from_analysis", "device_memory",
           "update_device_memory_gauges", "record_memory", "main"]


@dataclass
class MemoryHarvest:
    """One computation's memory ledger (one call of ``fn(*args)``).

    ``peak_bytes`` is the headline: XLA's compiled accounting
    (``argument + output + temp + generated``) when
    ``memory_analysis()`` exists (``source="memory_analysis"``), else
    the jaxpr walk's conservative live-buffer peak (``source="jaxpr"``).
    ``walk_peak_bytes`` is ALWAYS the walk's number (the XLA
    cross-check; the walk has no donation/remat, so expect it >= the
    compiled peak).  ``by_region`` maps each region to the bytes of its
    buffers live AT the walk's peak moment; ``top_allocations`` are the
    largest of those buffers individually."""
    peak_bytes: int
    argument_bytes: int
    output_bytes: int
    temp_bytes: int
    generated_code_bytes: int
    source: str                  # "memory_analysis" | "jaxpr"
    walk_peak_bytes: int
    by_region: Dict[str, int] = field(default_factory=dict)
    top_allocations: List[Dict[str, Any]] = field(default_factory=list)

    @property
    def peak_gb(self) -> float:
        return self.peak_bytes / 1e9


def _aval_bytes(aval) -> int:
    try:
        import jax.numpy as jnp
        return (math.prod(aval.shape) if aval.shape else 1) \
            * jnp.dtype(aval.dtype).itemsize
    except Exception:
        return 0


def _inner_jaxpr(eqn):
    from .analysis import _inner_jaxpr as inner
    return inner(eqn)


def live_buffer_walk(closed_jaxpr, *, region_depth: int = 1,
                     top: int = 8) -> Dict[str, Any]:
    """Conservative live-buffer replay of a jaxpr.

    Walks the equations in program order keeping the set of live
    buffers (born at their producing equation, freed after their last
    use at this jaxpr level; jaxpr outputs and invars live to the end),
    and records the peak running total plus a snapshot of the live set
    at that moment.  Call-like equations (pjit/scan/cond/custom-vjp)
    recurse: the callee's own transient peak — its walk peak minus its
    input bytes, which the caller already holds live — is charged while
    the call runs.  Scan bodies execute once per step but reuse the
    same buffers, so one body recursion is the right charge.

    Returns ``{"peak_bytes", "argument_bytes", "output_bytes",
    "by_region", "top_allocations"}``; regions come from the equations'
    ``named_scope`` stacks via :func:`~apex_tpu.prof.capture.region_path`
    (forward and backward of one user scope land in one row).
    """
    jaxpr = closed_jaxpr.jaxpr if hasattr(closed_jaxpr, "jaxpr") \
        else closed_jaxpr

    def walk(j, scope):
        """Returns (peak_bytes, peak_snapshot) for jaxpr ``j`` with its
        invars+constvars live; snapshot is {var-ish: (bytes, region,
        shape, dtype)} of the live set at the peak."""
        live: Dict[Any, tuple] = {}
        for v in list(j.invars) + list(j.constvars):
            if hasattr(v, "aval"):
                live[v] = (_aval_bytes(v.aval), "<arguments>",
                           tuple(getattr(v.aval, "shape", ())),
                           str(getattr(v.aval, "dtype", "?")))
        # last use per var AT THIS LEVEL; outvars never die.  Literals
        # are unhashable non-buffers and are skipped everywhere (a real
        # train step's jaxpr returns some: constant-folded metrics).
        last_use: Dict[Any, int] = {}
        for i, eqn in enumerate(j.eqns):
            for v in eqn.invars:
                if hasattr(v, "aval") and not isinstance(v, Literal):
                    last_use[v] = i
        # never free outputs NOR this jaxpr's own inputs: XLA keeps
        # (non-donated) arguments allocated for the whole execution, so
        # a conservative upper bound must hold them resident even after
        # their last in-program use (review finding — freeing them made
        # the fallback peak an UNDER-estimate on argument-heavy steps,
        # which would have silenced the memory_headroom pre-OOM rule).
        keep = set(live)
        keep.update(v for v in j.outvars
                    if hasattr(v, "aval")
                    and not isinstance(v, Literal))
        total = sum(b for b, *_ in live.values())
        peak, snap = total, dict(live)
        for i, eqn in enumerate(j.eqns):
            ns = getattr(getattr(eqn, "source_info", None),
                         "name_stack", None)
            ns = str(ns) if ns is not None else ""
            region = region_path("/".join(p for p in (scope, ns) if p),
                                 depth=region_depth)
            inner = _inner_jaxpr(eqn)
            transient = 0
            if inner is not None:
                name = eqn.params.get("name", eqn.primitive.name)
                sub_peak, sub_snap = walk(inner, f"{scope}/{name}"
                                          if scope else str(name))
                # charge only the callee's INTERNAL temps while the
                # call runs: its inputs are the operands the caller
                # already holds live, and its outputs are born as this
                # equation's outvars below — counting either inside the
                # transient would double-book them (a bare relu is a
                # custom_jvp call; its output must not count twice).
                sub_args = sum(
                    _aval_bytes(v.aval)
                    for v in list(inner.invars) + list(inner.constvars)
                    if hasattr(v, "aval"))
                sub_outs = sum(
                    _aval_bytes(v.aval) for v in inner.outvars
                    if hasattr(v, "aval")
                    and not isinstance(v, Literal))
                transient = max(0, sub_peak - sub_args - sub_outs)
            # outputs are born...
            born = []
            for v in eqn.outvars:
                if not hasattr(v, "aval"):
                    continue
                nbytes = _aval_bytes(v.aval)
                live[v] = (nbytes, region,
                           tuple(getattr(v.aval, "shape", ())),
                           str(getattr(v.aval, "dtype", "?")))
                born.append(v)
                total += nbytes
            if total + transient > peak:
                peak, snap = total + transient, dict(live)
                if transient:
                    snap[("transient", i)] = (transient, region, (),
                                              "<callee temps>")
            # ...then operands whose last use this was are freed
            for v in eqn.invars:
                if isinstance(v, Literal):
                    continue
                if (last_use.get(v) == i and v in live and v not in keep):
                    total -= live.pop(v)[0]
        return peak, snap

    peak, snap = walk(jaxpr, "")
    by_region: Dict[str, int] = {}
    allocs: List[Dict[str, Any]] = []
    for (nbytes, region, shape, dtype) in snap.values():
        by_region[region] = by_region.get(region, 0) + nbytes
        allocs.append({"bytes": int(nbytes), "region": region,
                       "shape": list(shape), "dtype": dtype})
    allocs.sort(key=lambda a: -a["bytes"])
    arg_bytes = sum(_aval_bytes(v.aval)
                    for v in list(jaxpr.invars) + list(jaxpr.constvars)
                    if hasattr(v, "aval"))
    out_bytes = sum(_aval_bytes(v.aval) for v in jaxpr.outvars
                    if hasattr(v, "aval")
                    and not isinstance(v, Literal))
    return {"peak_bytes": int(peak), "argument_bytes": int(arg_bytes),
            "output_bytes": int(out_bytes), "by_region": by_region,
            "top_allocations": allocs[:max(1, top)]}


def stats_from_analysis(ma) -> Optional[Dict[str, int]]:
    """``CompiledMemoryStats`` -> plain byte dict (None when the object
    carries nothing usable).  ``peak_bytes`` is the reservation XLA
    itself reports: arguments + outputs + temps + generated code, less
    input/output aliasing (donated buffers counted once)."""
    if ma is None:
        return None
    def g(name):
        try:
            return int(getattr(ma, name, 0) or 0)
        except Exception:
            return 0
    arg = g("argument_size_in_bytes")
    out = g("output_size_in_bytes")
    temp = g("temp_size_in_bytes")
    gen = g("generated_code_size_in_bytes")
    alias = g("alias_size_in_bytes")
    if not any((arg, out, temp, gen)):
        return None
    return {"argument_bytes": arg, "output_bytes": out,
            "temp_bytes": temp, "generated_code_bytes": gen,
            "alias_bytes": alias,
            "peak_bytes": max(0, arg + out + temp + gen - alias)}


def _xla_memory(fn, *args, **kwargs) -> Optional[Dict[str, int]]:
    """Compile ``fn`` on its OWN jit instance (the training step's
    cache is untouched) and read ``memory_analysis()``.  None on old
    jax (no API) or any compile failure — callers fall back to the
    walk.  Kept separate so tests can monkeypatch it."""
    try:
        compiled = jax.jit(fn).lower(*args, **kwargs).compile()
        return stats_from_analysis(compiled.memory_analysis())
    except Exception:
        return None


def harvest_memory(fn, *args, xla: bool = True, region_depth: int = 1,
                   top: int = 8, **kwargs) -> MemoryHarvest:
    """Harvest the memory ledger for ONE call of ``fn(*args)``.

    Totals come from XLA's ``memory_analysis()`` when ``xla=True`` and
    the API exists; the per-region attribution (and, as fallback, the
    totals) always comes from :func:`live_buffer_walk` — the same
    primary/fallback split as :func:`~apex_tpu.prof.roofline
    .harvest_costs`, and for the same reason: the attribution must not
    shift when jax versions change what they expose.  Pure trace /
    AOT-compile analysis — nothing executes on a device."""
    closed = jax.make_jaxpr(fn)(*args, **kwargs)
    w = live_buffer_walk(closed, region_depth=region_depth, top=top)
    xm = _xla_memory(fn, *args, **kwargs) if xla else None
    if xm is not None:
        return MemoryHarvest(
            peak_bytes=xm["peak_bytes"],
            argument_bytes=xm["argument_bytes"],
            output_bytes=xm["output_bytes"],
            temp_bytes=xm["temp_bytes"],
            generated_code_bytes=xm["generated_code_bytes"],
            source="memory_analysis",
            walk_peak_bytes=w["peak_bytes"],
            by_region=w["by_region"],
            top_allocations=w["top_allocations"])
    return MemoryHarvest(
        peak_bytes=w["peak_bytes"],
        argument_bytes=w["argument_bytes"],
        output_bytes=w["output_bytes"],
        temp_bytes=max(0, w["peak_bytes"] - w["argument_bytes"]
                       - w["output_bytes"]),
        generated_code_bytes=0,
        source="jaxpr",
        walk_peak_bytes=w["peak_bytes"],
        by_region=w["by_region"],
        top_allocations=w["top_allocations"])


# -- live device memory -------------------------------------------------------

def device_memory() -> List[Dict[str, Any]]:
    """Per-local-device allocator stats where the backend exposes them
    (``Device.memory_stats()`` — TPU/GPU yes, CPU typically None).
    Returns ``[{"id", "kind", "bytes_in_use", "bytes_limit", ...}]``,
    possibly empty.  A host API read — no device sync."""
    out: List[Dict[str, Any]] = []
    try:
        devices = jax.local_devices()
    except Exception:
        return out
    for d in devices:
        try:
            stats = d.memory_stats()
        except Exception:
            stats = None
        if not stats:
            continue
        out.append({
            "id": int(getattr(d, "id", len(out))),  # jaxlint: disable=J001 -- Device.memory_stats()/.id are host allocator-API reads (plain python ints), not device round-trips
            "kind": str(getattr(d, "device_kind", "?")),
            "bytes_in_use": int(stats.get("bytes_in_use", 0)),
            "bytes_limit": int(stats.get("bytes_limit", 0)),
            "peak_bytes_in_use": int(stats.get("peak_bytes_in_use", 0)),
        })
    return out


def update_device_memory_gauges(recorder) -> bool:
    """Publish summed local-device memory into the recorder's registry
    (``hbm_bytes_in_use`` / ``hbm_bytes_limit`` / ``hbm_headroom_pct``
    gauges the Prometheus exporter renders).  Returns True when the
    backend exposed anything."""
    devs = device_memory()
    if not devs:
        return False
    in_use = sum(d["bytes_in_use"] for d in devs)
    limit = sum(d["bytes_limit"] for d in devs)
    recorder.metrics.gauge("hbm_bytes_in_use").set(in_use)
    # allocator high-water mark: monotonic, never dips with a poll
    recorder.metrics.gauge("hbm_peak_bytes_in_use").set_max(
        sum(d["peak_bytes_in_use"] or d["bytes_in_use"] for d in devs))
    if limit:
        recorder.metrics.gauge("hbm_bytes_limit").set(limit)
        recorder.metrics.gauge("hbm_headroom_pct").set(
            100.0 * max(0.0, 1.0 - in_use / limit))
    return True


def record_memory(recorder, harvest_or_stats,
                  limit_bytes: Optional[int] = None,
                  **fields) -> Optional[dict]:
    """Emit one ``memory`` event (``phase="harvest"``) into the stream —
    the hook the ``memory_headroom`` watchdog rule folds and
    ``prof.fleet`` reads per host.

    ``harvest_or_stats`` is a :class:`MemoryHarvest` or a plain byte
    dict (:func:`stats_from_analysis` shape).  ``limit_bytes`` defaults
    to the SMALLEST per-device ``bytes_limit`` the backend exposes —
    an executable's peak is a per-device footprint, so the binding
    constraint is one chip's HBM, and comparing against the summed
    fleet limit would overstate headroom ~n_devices-fold and silence
    the pre-OOM rule (review finding).  With a limit the event carries
    ``headroom_pct``; the ``peak_hbm_bytes`` gauge is set either way.
    Returns the event fields (or None with no recorder)."""
    if recorder is None:
        return None
    if isinstance(harvest_or_stats, MemoryHarvest):
        h = harvest_or_stats
        stats = {"peak_bytes": h.peak_bytes,
                 "argument_bytes": h.argument_bytes,
                 "output_bytes": h.output_bytes,
                 "temp_bytes": h.temp_bytes,
                 "generated_code_bytes": h.generated_code_bytes,
                 "source": h.source}
    else:
        stats = dict(harvest_or_stats)
    if limit_bytes is None:
        limits = [d["bytes_limit"] for d in device_memory()
                  if d["bytes_limit"]]
        limit_bytes = min(limits) if limits else None
    ev = {"phase": "harvest", **stats, **fields}
    if limit_bytes:
        ev["bytes_limit"] = int(limit_bytes)
        ev["headroom_pct"] = round(
            100.0 * max(0.0, 1.0 - stats.get("peak_bytes", 0)
                        / limit_bytes), 2)
    # high-water mark across harvests (a smaller re-harvest — e.g. a
    # second pipeline's ledger — must not shrink the run's peak)
    recorder.metrics.gauge("peak_hbm_bytes").set_max(
        stats.get("peak_bytes", 0))
    recorder.event("memory", **ev)
    return ev


# -- CLI ----------------------------------------------------------------------

def format_harvest(h: MemoryHarvest) -> str:
    """Human-readable ledger (the CLI's default output)."""
    lines = [f"memory ledger ({h.source}): peak "
             f"{h.peak_bytes / 1e6:.3f} MB  (args "
             f"{h.argument_bytes / 1e6:.3f}, outputs "
             f"{h.output_bytes / 1e6:.3f}, temps "
             f"{h.temp_bytes / 1e6:.3f}, code "
             f"{h.generated_code_bytes / 1e6:.3f})"]
    if h.source != "jaxpr":
        lines.append(f"walk peak (conservative, no donation/remat): "
                     f"{h.walk_peak_bytes / 1e6:.3f} MB")
    lines.append("{:<30} {:>12}".format("region @ walk peak", "MB"))
    for name, b in sorted(h.by_region.items(), key=lambda kv: -kv[1]):
        lines.append("{:<30} {:>12.3f}".format(name[:30], b / 1e6))
    lines.append("top allocations at peak:")
    for a in h.top_allocations:
        lines.append(f"  {a['bytes'] / 1e6:10.3f} MB  {a['region']}  "
                     f"{a['dtype']}{a['shape']}")
    return "\n".join(lines)


def main(argv: Optional[Sequence[str]] = None) -> int:
    """``python -m apex_tpu.prof.memory`` — harvest one target's memory
    ledger (``--fn module:callable`` returning ``(fn, example_args)``,
    the ``prof.analysis`` convention)."""
    import argparse

    ap = argparse.ArgumentParser(
        prog="python -m apex_tpu.prof.memory",
        description="Peak-HBM ledger with per-region attribution.")
    ap.add_argument("--fn", default="__graft_entry__:entry",
                    help="module:callable returning (fn, example_args)")
    ap.add_argument("--region-depth", type=int, default=1)
    ap.add_argument("--top", type=int, default=8)
    ap.add_argument("--no-xla", action="store_true",
                    help="skip memory_analysis() (jaxpr walk only)")
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args(argv)

    from .analysis import _load_target

    fn, ex = _load_target(args.fn)()
    h = harvest_memory(fn, *ex, xla=not args.no_xla,
                       region_depth=args.region_depth, top=args.top)
    if args.json:
        from dataclasses import asdict
        print(json.dumps(asdict(h), indent=1))
    else:
        print(format_harvest(h))
    return 0


if __name__ == "__main__":  # pragma: no cover - exercised via subprocess
    sys.exit(main())
