"""Per-op roofline attribution — the PyProf ``prof`` stage joined with
the run's own clock (ISSUE 6 tentpole).

The reference's PyProf maps every kernel in a profile back to the op
that launched it and reports FLOPs, bytes, and silicon efficiency per
op (``pyprof/prof/prof.py``).  The TPU-native equivalent has three
inputs, all already in this repo, and this module is the join:

1. **cost harvest** (:func:`harvest_costs`) — per-computation FLOP/byte
   totals at trace time from ``jit(fn).lower(*args).cost_analysis()``
   (falling back to ``.compile().cost_analysis()``, and on old jax to
   the :func:`apex_tpu.prof.analysis.profile_function` jaxpr walk).
   Harvesting uses its OWN ``jax.jit`` instance, so it never touches —
   and never retraces — the training step's jitted callable.
2. **region attribution** — the jaxpr walk carries every op's
   ``named_scope`` path (:func:`apex_tpu.prof.capture.scope` /
   ``annotate`` names); :func:`apex_tpu.prof.capture.region_path` peels
   jax's transform wrappers so forward and backward ops of one region
   land in the same row.  Harvested FLOPs/bytes are grouped per region.
3. **MFU ledger** (:func:`mfu_ledger`) — the harvest joined with
   measured time: each region gets a roofline time model
   (``max(flops/peak_flops, bytes/peak_bw)``), a compute-vs-memory
   boundedness classification against measured peaks (the
   ``BENCH_EXTRA.json`` calibration written next to ``BASELINE.json``
   — :func:`load_peaks`), modeled-time share of the measured step, and
   achieved FLOP/s; the run-level gap section splits the
   steady-vs-best-window distance into compile, loader stall, dispatch
   gap, and other host time read from a
   :func:`apex_tpu.prof.timeline.analyze` result.

CLI::

    python -m apex_tpu.prof.roofline --fn mymod:make_step \\
        --timeline run.jsonl --peaks BENCH_EXTRA.json [--json]

``bench.py`` records this ledger per benchmark workload in
``BENCH_EXTRA.json`` and replaces its hand-coded BERT FLOPs estimate
with the harvested ``matmul_flops`` (old formula kept as a 10%
cross-check gate).
"""

from __future__ import annotations

import json
import os
import sys
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence

import jax

from .capture import region_path
from .ledger import COMPUTE_OPS

__all__ = ["CostHarvest", "harvest_costs", "mfu_ledger", "load_peaks",
           "device_peaks", "DEVICE_PEAKS", "DEFAULT_HBM_GB_S", "main"]

#: THE peak table: published per-chip peaks (bf16 FLOP/s, HBM GB/s)
#: keyed by jax's ``device_kind`` — the v5e reports "TPU v5 lite".  A
#: device that is not here is an error, never a default: a utilization
#: against an assumed peak is not a number.
DEVICE_PEAKS = {
    "TPU v5 lite": {
        "flops": 197e12, "hbm_gb_s": 819.0,
        "source": "Google Cloud documentation, 'TPU v5e' (published)"},
}

#: bandwidth assumed for a calibration ARTIFACT that measured a matmul
#: rate but no bandwidth (the v5e's published figure; the ledger records
#: ``bw_source`` so the assumption is visible).
DEFAULT_HBM_GB_S = DEVICE_PEAKS["TPU v5 lite"]["hbm_gb_s"]


def device_peaks(device_kind: Optional[str] = None) -> Dict[str, Any]:
    """Published peaks of ``device_kind`` (default: this process's first
    device) from :data:`DEVICE_PEAKS`; raises ``ValueError`` naming the
    unknown device otherwise."""
    if device_kind is None:
        device_kind = jax.devices()[0].device_kind
    try:
        return dict(DEVICE_PEAKS[device_kind])
    except KeyError:
        raise ValueError(
            f"unknown device {device_kind!r}: no published peaks in "
            f"apex_tpu.prof.roofline.DEVICE_PEAKS (known: "
            f"{sorted(DEVICE_PEAKS)})") from None


@dataclass
class CostHarvest:
    """One computation's harvested costs (one call of ``fn(*args)``).

    ``flops``/``bytes`` are the totals from XLA's cost analysis when
    available (``source`` says which path produced them), else the
    jaxpr-walk totals.  ``matmul_flops`` is ALWAYS the jaxpr walk's
    dot/conv-only count (:data:`apex_tpu.prof.ledger.COMPUTE_OPS`) —
    the MFU numerator, deliberately independent of XLA's op costing so
    cross-round comparisons stay stable.  ``by_region`` maps each
    :func:`~apex_tpu.prof.capture.region_path` region to its
    ``{"flops", "bytes", "matmul_flops", "ops"}`` row.
    """
    flops: float
    bytes: Optional[float]
    source: str                      # "xla_lowered" | "xla_compiled" | "jaxpr"
    matmul_flops: float
    jaxpr_flops: float               # fallback totals (XLA cross-check)
    jaxpr_bytes: float
    by_region: Dict[str, Dict[str, float]] = field(default_factory=dict)

    @property
    def coverage_pct(self) -> float:
        """How much of the harvested total the region rows account for
        (jaxpr-attributed flops / reported total) — the acceptance
        number ("ledger accounts for >= 90% of the step FLOPs")."""
        if not self.flops:
            return 0.0
        attributed = sum(r["flops"] for r in self.by_region.values())
        return 100.0 * attributed / self.flops


def _xla_cost(fn, *args, **kwargs) -> Optional[Dict[str, float]]:
    """XLA's own cost analysis for one call, cheapest path first:
    ``Lowered.cost_analysis()`` (HLO-level, no backend compile), then
    ``Compiled.cost_analysis()``.  Returns ``{"flops", "bytes",
    "source"}`` or None when neither API exists (old jax) or yields a
    usable flops count.  Kept as its own function so tests can
    monkeypatch it to force the old-jax fallback."""
    try:
        lowered = jax.jit(fn).lower(*args, **kwargs)
    except Exception:
        return None
    try:
        cost = _first(lowered.cost_analysis())
    except Exception:
        cost = None
    if cost and cost.get("flops"):
        return {"flops": float(cost["flops"]),
                "bytes": (float(cost["bytes accessed"])
                          if cost.get("bytes accessed") else None),
                "source": "xla_lowered"}
    try:
        cost = _first(lowered.compile().cost_analysis())
    except Exception:
        cost = None
    if cost and cost.get("flops"):
        return {"flops": float(cost["flops"]),
                "bytes": (float(cost["bytes accessed"])
                          if cost.get("bytes accessed") else None),
                "source": "xla_compiled"}
    return None


def _first(cost):
    if isinstance(cost, (list, tuple)):
        return cost[0] if cost else None
    return cost


def harvest_costs(fn, *args, xla: bool = True, region_depth: int = 1,
                  prof=None, **kwargs) -> CostHarvest:
    """Harvest FLOP/byte estimates for ONE call of ``fn(*args)``.

    Totals come from XLA's cost analysis when ``xla=True`` and the API
    is available (``jit(fn).lower(...).cost_analysis()``, then the
    compiled fallback); otherwise — and always for the per-region and
    matmul splits — from the static jaxpr walk
    (:func:`~apex_tpu.prof.analysis.profile_function`), which needs no
    compile and runs on any backend.  ``region_depth`` controls how many
    leading :func:`~apex_tpu.prof.capture.scope` components form a
    region key; ``prof`` reuses an existing ``profile_function`` result
    (the jaxpr trace of a full train step is seconds of host work —
    ``bench.py`` shares one across its ledgers).

    Pure trace-time analysis: nothing executes on the device, no buffer
    is donated or consumed, and the training step's own jit cache is
    untouched (pin with :func:`apex_tpu.prof.assert_trace_count`).
    """
    from .analysis import profile_function

    if prof is None:
        prof = profile_function(fn, *args, xla_cost=False, **kwargs)
    by_region: Dict[str, Dict[str, float]] = {}
    matmul = 0.0
    for r in prof.records:
        row = by_region.setdefault(
            region_path(r.name, depth=region_depth),
            {"flops": 0.0, "bytes": 0.0, "matmul_flops": 0.0, "ops": 0})
        row["flops"] += r.flops * r.count
        row["bytes"] += r.bytes * r.count
        row["ops"] += r.count
        if r.op in COMPUTE_OPS:
            row["matmul_flops"] += r.flops * r.count
            matmul += r.flops * r.count
    jaxpr_flops = prof.total_flops
    jaxpr_bytes = prof.total_bytes
    cost = _xla_cost(fn, *args, **kwargs) if xla else None
    if cost is not None:
        return CostHarvest(
            flops=cost["flops"], bytes=cost["bytes"], source=cost["source"],
            matmul_flops=matmul, jaxpr_flops=jaxpr_flops,
            jaxpr_bytes=jaxpr_bytes, by_region=by_region)
    return CostHarvest(
        flops=jaxpr_flops, bytes=jaxpr_bytes, source="jaxpr",
        matmul_flops=matmul, jaxpr_flops=jaxpr_flops,
        jaxpr_bytes=jaxpr_bytes, by_region=by_region)


# -- measured peaks -----------------------------------------------------------

def load_peaks(path: Optional[str] = None, *,
               device_kind: Optional[str] = None) -> Dict[str, Any]:
    """Roofline ceilings: ``{"flops": peak FLOP/s, "hbm_gb_s":
    bandwidth, "source": where they came from}``.

    With ``path`` (a calibration artifact, or a directory holding a
    ``BENCH_EXTRA.json``): its measured ``measured_matmul_tflops`` (else
    its ``peak_bf16_tflops``) and, when its trace rows carry one, the
    measured loop-fusion bandwidth.  Without: the published peaks of
    ``device_kind`` (default: this process's device) from
    :func:`device_peaks`.  An unusable artifact or an unknown device
    raises ``ValueError`` — there is no default peak."""
    if not path:
        pk = device_peaks(device_kind)
        return {"flops": pk["flops"], "hbm_gb_s": pk["hbm_gb_s"],
                "source": pk["source"], "bw_source": pk["source"]}
    cand = (os.path.join(path, "BENCH_EXTRA.json")
            if os.path.isdir(path) else path)
    try:
        with open(cand) as f:
            extra = json.load(f)
    except (OSError, ValueError) as e:
        raise ValueError(f"no usable peaks artifact at {cand}: {e}") from e
    tflops = extra.get("measured_matmul_tflops") \
        or extra.get("peak_bf16_tflops")
    if not tflops:
        raise ValueError(f"{cand} carries no measured_matmul_tflops / "
                         f"peak_bf16_tflops")
    src = ("measured_matmul_tflops"
           if extra.get("measured_matmul_tflops") else "peak_bf16_tflops")
    bw, bw_src = DEFAULT_HBM_GB_S, "published_v5e_hbm"
    prof = (extra.get("resnet50") or {}).get("prof_measured") or {}
    for row in prof.get("by_category", []):
        if row.get("category") == "loop fusion" and row.get("gb_per_s"):
            bw, bw_src = float(row["gb_per_s"]), "measured_loop_fusion"
            break
    return {"flops": float(tflops) * 1e12, "hbm_gb_s": bw,
            "source": f"{os.path.basename(cand)}:{src}",
            "bw_source": bw_src}


# -- the MFU ledger -----------------------------------------------------------

def mfu_ledger(harvest: CostHarvest, *, step_time_s: Optional[float] = None,
               timeline: Optional[Dict[str, Any]] = None,
               peaks: Optional[Dict[str, Any]] = None,
               best_window_step_s: Optional[float] = None,
               top: Optional[int] = None,
               memory=None) -> Dict[str, Any]:
    """Join one :class:`CostHarvest` with measured time into the
    per-region MFU ledger.

    ``step_time_s`` is the measured wall seconds per step; with a
    ``timeline`` (an :func:`apex_tpu.prof.timeline.analyze` result) it
    defaults to the stream's ``elapsed / steps``.  ``peaks`` is a
    :func:`load_peaks`-shaped dict (defaults to loading one).

    ``memory`` (ISSUE 10) is a
    :class:`apex_tpu.prof.memory.MemoryHarvest` of the SAME step: the
    ledger gains a ``memory`` section (peak-HBM totals + top
    allocations) and each region row a ``peak_hbm_mb`` column from the
    walk's live-set-at-peak attribution — FLOPs, wire bytes, and HBM
    residency finally read off one table.

    Each region row models its roofline time as
    ``max(flops/peak_flops, bytes/peak_bw)`` and is classified
    ``compute``- or ``memory``-bound by which side dominates; modeled
    times are normalized so they sum to the measured step, giving every
    region a modeled-ms share and an achieved FLOP/s.  The run-level
    ``gap`` section attributes the distance between the steady step and
    its best window (``best_window_step_s``) — and, from the timeline,
    the compile seconds (retrace-event dispatch durations), loader
    stall, dispatch gap, and other host time.
    """
    peaks = dict(peaks or load_peaks())
    peak_f = float(peaks["flops"])
    peak_bw = float(peaks["hbm_gb_s"]) * 1e9
    if step_time_s is None and timeline:
        steps = timeline.get("steps") or 0
        elapsed = timeline.get("elapsed_s") or 0.0
        if steps and elapsed:
            step_time_s = elapsed / steps

    mem_by_region: Dict[str, float] = {}
    if memory is not None:
        mem_by_region = dict(getattr(memory, "by_region", None)
                             or (memory.get("by_region", {})
                                 if isinstance(memory, dict) else {}))

    regions: List[Dict[str, Any]] = []
    modeled_total = 0.0
    for name, row in harvest.by_region.items():
        t_compute = row["flops"] / peak_f
        t_memory = row["bytes"] / peak_bw if row["bytes"] else 0.0
        modeled = max(t_compute, t_memory)
        modeled_total += modeled
        entry = {
            "region": name,
            "flops_g": round(row["flops"] / 1e9, 6),
            "matmul_flops_g": round(row["matmul_flops"] / 1e9, 6),
            "bytes_gb": round(row["bytes"] / 1e9, 6),
            "ops": int(row["ops"]),
            "intensity": (round(row["flops"] / row["bytes"], 2)
                          if row["bytes"] else None),
            "bound": ("compute" if t_compute >= t_memory else "memory"),
            "_modeled_s": modeled,
        }
        if name in mem_by_region:
            # this region's buffers live at the walk's peak-HBM moment
            entry["peak_hbm_mb"] = round(mem_by_region[name] / 1e6, 3)
        regions.append(entry)
    # Normalize the roofline time model onto the measured clock: the
    # scale factor is also a diagnostic — how far the real schedule sits
    # from the no-overlap roofline ideal (> 1: slower than ideal).
    model_scale = ((step_time_s / modeled_total)
                   if step_time_s and modeled_total else None)
    for r in regions:
        modeled = r.pop("_modeled_s")
        if model_scale:
            t = modeled * model_scale
            r["modeled_ms"] = round(t * 1e3, 3)
            r["share_pct"] = round(100.0 * modeled * model_scale
                                   / step_time_s, 1) if step_time_s else None
            r["achieved_tflops"] = (round(r["flops_g"] / 1e3 / t, 4)
                                    if t > 0 else None)
            # MFU numerator is the region's MATMUL flops — same
            # definition as total.mfu_pct, so an elementwise-dominated
            # region (optimizer sweep) cannot report phantom MXU use.
            r["mfu_pct"] = (round(100.0 * r["matmul_flops_g"] * 1e9
                                  / t / peak_f, 1)
                            if t > 0 else None)
    regions.sort(key=lambda r: -(r.get("modeled_ms") or r["flops_g"]))
    if top:
        dropped = max(0, len(regions) - top)
        regions = regions[:top]
    else:
        dropped = 0

    out: Dict[str, Any] = {
        # versioned with the analyzer's schema: regress.py diffs these
        "schema_version": _schema_version(),
        "source": harvest.source,
        "peaks": {"tflops": round(peak_f / 1e12, 1),
                  "hbm_gb_s": round(peak_bw / 1e9, 1),
                  "ridge_intensity": round(peak_f / peak_bw, 1),
                  "source": peaks.get("source"),
                  "bw_source": peaks.get("bw_source")},
        "total": {
            "flops_g": round(harvest.flops / 1e9, 6),
            "matmul_flops_g": round(harvest.matmul_flops / 1e9, 6),
            "bytes_gb": (round(harvest.bytes / 1e9, 6)
                         if harvest.bytes else None),
            "intensity": (round(harvest.flops / harvest.bytes, 2)
                          if harvest.bytes else None),
        },
        "coverage_pct": round(harvest.coverage_pct, 1),
        "regions": regions,
        "regions_dropped": dropped,
    }
    if memory is not None:
        get = (lambda k: getattr(memory, k, None)
               if not isinstance(memory, dict) else memory.get(k))
        peak_b = float(get("peak_bytes") or 0)
        out["total"]["peak_hbm_gb"] = round(peak_b / 1e9, 6)
        out["memory"] = {
            "peak_hbm_gb": round(peak_b / 1e9, 6),
            "source": get("source"),
            "argument_gb": round(float(get("argument_bytes") or 0)
                                 / 1e9, 6),
            "output_gb": round(float(get("output_bytes") or 0) / 1e9, 6),
            "temp_gb": round(float(get("temp_bytes") or 0) / 1e9, 6),
            "walk_peak_gb": round(float(get("walk_peak_bytes") or 0)
                                  / 1e9, 6),
            "top_allocations": list(get("top_allocations") or [])[:8],
        }
    if step_time_s:
        out["total"]["step_ms"] = round(step_time_s * 1e3, 3)
        out["total"]["achieved_tflops"] = round(
            harvest.flops / step_time_s / 1e12, 4)
        out["total"]["mfu_pct"] = round(
            100.0 * harvest.matmul_flops / step_time_s / peak_f, 1)
        out["model_scale"] = (round(model_scale, 2) if model_scale else None)

    gap: Dict[str, Any] = {}
    if best_window_step_s and step_time_s:
        gap["steady_vs_best_pct"] = round(
            max(0.0, 100.0 * (1.0 - best_window_step_s / step_time_s)), 1)
    if timeline:
        att = timeline.get("attribution") or {}
        rt = timeline.get("retraces") or {}
        elapsed = float(timeline.get("elapsed_s") or 0.0)
        compile_s = float(rt.get("compile_s") or 0.0)
        gap.update({
            # where the non-device wall time went, % of the stream's wall
            "compile_pct": (round(100.0 * compile_s / elapsed, 2)
                            if elapsed else None),
            "loader_stall_pct": att.get("loader_stall_pct"),
            "dispatch_gap_pct": att.get("dispatch_gap_pct"),
            # host time between dispatches NOT explained by the loader:
            # metric fetches, python glue, GC — the "host sync" bucket
            "host_other_pct": att.get("gap_minus_loader_pct"),
        })
    if gap:
        out["gap"] = gap
    return out


def _schema_version() -> str:
    from .timeline import SCHEMA_VERSION
    return SCHEMA_VERSION


def _fmt_g(v) -> str:
    return f"{v:10.3f}" if v is not None else "       n/a"


def format_ledger(ledger: Dict[str, Any]) -> str:
    """Human-readable ledger (the CLI's default output)."""
    lines: List[str] = []
    t = ledger["total"]
    pk = ledger["peaks"]
    lines.append(
        f"roofline ledger ({ledger['source']}; peaks {pk['tflops']} TFLOP/s"
        f" / {pk['hbm_gb_s']} GB/s [{pk['source']}])")
    head = (f"total: {t['flops_g']} GFLOP ({t['matmul_flops_g']} matmul)"
            + (f", {t['bytes_gb']} GB" if t.get("bytes_gb") else ""))
    if t.get("step_ms"):
        head += (f" in {t['step_ms']} ms -> {t['achieved_tflops']} TFLOP/s"
                 f" ({t['mfu_pct']}% MFU vs measured peak)")
    lines.append(head)
    mem = ledger.get("memory")
    if mem:
        lines.append(
            f"peak HBM: {mem['peak_hbm_gb']} GB [{mem['source']}] "
            f"(args {mem['argument_gb']}, outputs {mem['output_gb']}, "
            f"temps {mem['temp_gb']}; walk {mem['walk_peak_gb']})")
    lines.append(f"region coverage: {ledger['coverage_pct']}% of total flops")
    lines.append("{:<26} {:>10} {:>10} {:>8} {:>9} {:>7}  {}".format(
        "region", "GFLOP", "GB", "ms", "TFLOP/s", "MFU%", "bound"))
    for r in ledger["regions"]:
        lines.append("{:<26} {} {} {:>8} {:>9} {:>7}  {}".format(
            r["region"][:26], _fmt_g(r["flops_g"]), _fmt_g(r["bytes_gb"]),
            r.get("modeled_ms", ""), r.get("achieved_tflops", ""),
            r.get("mfu_pct", ""), r["bound"]))
    if ledger.get("regions_dropped"):
        lines.append(f"... {ledger['regions_dropped']} smaller regions "
                     f"not shown")
    gap = ledger.get("gap")
    if gap:
        parts = [f"{k.replace('_pct', '')} {v}%"
                 for k, v in gap.items() if v is not None]
        lines.append("gap attribution: " + ", ".join(parts))
    return "\n".join(lines)


# -- CLI ----------------------------------------------------------------------

def main(argv: Optional[Sequence[str]] = None) -> int:
    """``python -m apex_tpu.prof.roofline`` — harvest one target's costs
    and print its MFU ledger, optionally joined with a telemetry stream
    (for step timing + gap attribution) and a measured-peaks file.

    The target follows the ``prof.analysis`` convention: ``--fn
    module:callable`` where a zero-argument callable returns
    ``(fn, example_args)`` (``__graft_entry__:entry`` works out of the
    box)."""
    import argparse

    ap = argparse.ArgumentParser(
        prog="python -m apex_tpu.prof.roofline",
        description="Per-region roofline attribution / MFU ledger.")
    ap.add_argument("--fn", default="__graft_entry__:entry",
                    help="module:callable returning (fn, example_args)")
    ap.add_argument("--timeline", default=None, metavar="RUN_JSONL",
                    help="telemetry stream: step timing + gap attribution")
    ap.add_argument("--peaks", default=None,
                    help="calibration artifact (or a dir holding a "
                         "BENCH_EXTRA.json) with measured peaks; "
                         "default: the published peaks of --device-kind")
    ap.add_argument("--device-kind", default=None,
                    help="device whose published peaks to use (a "
                         "DEVICE_PEAKS key, e.g. 'TPU v5 lite'); "
                         "default: this process's device — an unknown "
                         "device is an error, not a default")
    ap.add_argument("--step-ms", type=float, default=None,
                    help="measured step time (overrides --timeline)")
    ap.add_argument("--region-depth", type=int, default=1)
    ap.add_argument("--top", type=int, default=None)
    ap.add_argument("--no-xla", action="store_true",
                    help="skip XLA cost analysis (jaxpr totals only)")
    ap.add_argument("--memory", action="store_true",
                    help="also harvest the peak-HBM ledger "
                         "(prof.memory) and join it as the ledger's "
                         "memory section / peak_hbm columns")
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args(argv)

    from .analysis import _load_target

    fn, ex = _load_target(args.fn)()
    harvest = harvest_costs(fn, *ex, xla=not args.no_xla,
                            region_depth=args.region_depth)
    mem = None
    if args.memory:
        from . import memory as memory_mod
        mem = memory_mod.harvest_memory(fn, *ex, xla=not args.no_xla,
                                        region_depth=args.region_depth)
    tl = None
    if args.timeline:
        from . import timeline as timeline_mod
        tl = timeline_mod.analyze(timeline_mod.load_events(args.timeline))
    ledger = mfu_ledger(
        harvest,
        step_time_s=(args.step_ms / 1e3 if args.step_ms else None),
        timeline=tl,
        peaks=load_peaks(args.peaks, device_kind=args.device_kind),
        top=args.top,
        memory=mem)
    if args.json:
        print(json.dumps(ledger, indent=1))
    else:
        print(format_ledger(ledger))
    return 0


if __name__ == "__main__":  # pragma: no cover - exercised via subprocess
    sys.exit(main())
