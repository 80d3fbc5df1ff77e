"""Measurement harness — on-device candidate timing (ISSUE 14, layer 2).

The discipline every number in ``bench.py`` already follows, applied to
kernel configs:

* **compile excluded** — each candidate's jitted case runs once (and is
  synced) before any clock starts;
* **min-of-K** — ``reps`` timed passes of ``iters`` calls each, fenced
  with an explicit ``jax.block_until_ready`` on the last output (async
  dispatch means an unfenced clock measures enqueue, not compute —
  jaxlint J009's whole reason to exist), and the minimum taken (the
  least-interfered pass);
* **reject before timing** — candidates failing the spec's VMEM/
  legality constraint never compile; candidates whose outputs fail the
  oracle against the default config (bitwise for ``exact`` kernels,
  tolerance for flash attention's reordered online softmax) are
  measured-then-discarded, so a "fast but wrong" config can never win;
* **ledger-driven priority** — :func:`bound_from_ledger` maps a
  roofline MFU ledger's compute-vs-memory verdicts onto a kernel's
  regions, and the spec orders its candidate space accordingly
  (memory-bound → layout candidates first, compute-bound → block-size
  candidates first).  With a candidate budget (``max_candidates``) the
  ordering decides WHAT gets measured at all.

CPU/interpret paths never tune implicitly: :func:`tune_kernel` refuses
to measure off-TPU unless the caller explicitly opts into
``interpret=True`` (the CPU CI determinism tests, marked as such in the
stored meta) or ``allow_non_tpu=True``.
"""

from __future__ import annotations

import random
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Mapping, Optional, Sequence

import jax
import numpy as np

from . import store
from .registry import KernelSpec, all_specs, get_spec

__all__ = ["TuneResult", "time_case", "tune_kernel", "bound_from_ledger",
           "tune_from_ledger", "max_scaled_error",
           "check_against_reference"]


@dataclass
class TuneResult:
    kernel: str
    version: int
    bucket: str
    device_kind: str
    bound: str
    config: Dict[str, int]                 # the winner (may == default)
    default_config: Dict[str, int]
    best_ms: Optional[float]
    default_ms: Optional[float]
    candidates: int                        # measured (constraint-passing)
    rejected_constraint: int
    rejected_oracle: int
    truncated: int = 0                     # dropped by max_candidates
    order: List[Dict[str, int]] = field(default_factory=list)
    stored: bool = False
    source: str = "device"                 # "device" | "interpret"

    @property
    def tuned_over_default(self) -> Optional[float]:
        if not self.best_ms or not self.default_ms:
            return None
        return round(self.best_ms / self.default_ms, 4)


def time_case(run: Callable[[], Any], *, iters: int = 5,
              reps: int = 3) -> float:
    """Seconds per call, min-of-``reps`` over ``iters``-call passes.
    ``run`` must already be warm (compiled); the fence is one
    ``block_until_ready`` on the final output per pass."""
    best = float("inf")
    for _ in range(max(1, reps)):
        t0 = time.perf_counter()
        out = None
        for _ in range(max(1, iters)):
            out = run()
        jax.block_until_ready(out)  # jaxlint: disable=J001 -- timing fence: the measurement is invalid without draining the dispatched candidates
        best = min(best, (time.perf_counter() - t0) / max(1, iters))
    return best


def _tree_equal_bitwise(a, b) -> bool:
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    if len(la) != len(lb):
        return False
    for x, y in zip(la, lb):
        ax, ay = np.asarray(x), np.asarray(y)  # jaxlint: disable=J008 -- oracle compare IS the host boundary: both trees are finished candidate outputs, fetched once outside any hot loop
        if ax.dtype != ay.dtype or ax.shape != ay.shape \
                or not np.array_equal(ax.reshape(-1).view(np.uint8),
                                      ay.reshape(-1).view(np.uint8)):
            return False
    return True


def _tree_close(a, b, rtol: float, atol: float) -> bool:
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    if len(la) != len(lb):
        return False
    for x, y in zip(la, lb):
        ax = np.asarray(x, dtype=np.float32)  # jaxlint: disable=J008 -- oracle compare IS the host boundary (see _tree_equal_bitwise)
        ay = np.asarray(y, dtype=np.float32)  # jaxlint: disable=J008 -- oracle compare IS the host boundary (see _tree_equal_bitwise)
        if ax.shape != ay.shape or not np.allclose(ax, ay, rtol=rtol,
                                                   atol=atol):
            return False
    return True


def max_scaled_error(out, ref) -> float:
    """Largest ``max|out - ref| / max|ref|`` over the leaves of two
    matching pytrees — the kernel-vs-jnp-reference agreement an on-chip
    sweep gates on (against the spec's ``tol[0]``).  Scaled per leaf
    rather than per element: a bf16 gradient's near-zero entries carry
    absolute error on the scale of the leaf's largest ones, which an
    elementwise ``allclose`` would misread as disagreement.  Non-finite
    values or mismatched structure return ``inf``."""
    la = jax.tree_util.tree_leaves(out)
    lb = jax.tree_util.tree_leaves(ref)
    if len(la) != len(lb):
        return float("inf")
    worst = 0.0
    for x, y in zip(la, lb):
        ax = np.asarray(x, dtype=np.float32)  # jaxlint: disable=J008 -- oracle compare IS the host boundary (see _tree_equal_bitwise)
        ay = np.asarray(y, dtype=np.float32)  # jaxlint: disable=J008 -- oracle compare IS the host boundary (see _tree_equal_bitwise)
        if ax.shape != ay.shape or not (np.isfinite(ax).all()
                                        and np.isfinite(ay).all()):
            return float("inf")
        scale = float(np.max(np.abs(ay))) if ay.size else 0.0
        err = float(np.max(np.abs(ax - ay))) if ay.size else 0.0
        worst = max(worst, err / scale if scale else err)
    return worst


def check_against_reference(spec_or_name, shape: Mapping) -> float:
    """Compile ``spec``'s kernel with Mosaic (``interpret=False``) at
    ``shape`` with its default config — forward and backward, as the
    tune case runs it — and compare with the jnp reference the kernel's
    module carries.  Returns the :func:`max_scaled_error`; raises
    ``AssertionError`` when the case traced the jnp path instead of the
    kernel (a dispatch gate said no) or when the error exceeds the
    case's ``tol[0]``.  A compiler refusal propagates as raised — there
    is no fallback here.  The on-chip half of the kernel contract:
    ``chip_smoke.py`` and ``tests/test_pallas_tpu.py`` both call it."""
    spec = (spec_or_name if isinstance(spec_or_name, KernelSpec)
            else get_spec(spec_or_name))
    case = spec.build(shape, False)
    cfg = spec.defaults(shape)
    if "pallas_call" not in str(jax.make_jaxpr(lambda: case.run(cfg))()):
        raise AssertionError(
            f"{spec.name} {dict(shape)}: the jnp path was traced, not "
            f"the kernel")
    err = max_scaled_error(case.run(cfg), case.ref())
    if not err <= case.tol[0]:
        raise AssertionError(
            f"{spec.name} {dict(shape)}: kernel disagrees with its jnp "
            f"reference: max scaled error {err:.3g} > {case.tol[0]}")
    return err


def _oracle_ok(spec: KernelSpec, case, ref, out) -> bool:
    if spec.exact:
        return _tree_equal_bitwise(ref, out)
    rtol, atol = case.tol
    return _tree_close(ref, out, rtol, atol)


def _config_key(spec: KernelSpec, shape: Mapping,
                cfg: Dict[str, int]) -> object:
    """Dedupe key: the EFFECTIVE block when the spec can compute one
    (two configs clamping onto the same program must only be timed
    once), else the raw config."""
    if spec.effective is not None:
        try:
            return ("eff", repr(spec.effective(shape, cfg)))
        except Exception:
            pass
    return tuple(sorted(cfg.items()))


def _dedupe(spec: KernelSpec, shape: Mapping,
            configs: Sequence[Dict[str, int]]) -> List[Dict[str, int]]:
    seen, out = set(), []
    for c in configs:
        key = _config_key(spec, shape, c)
        if key not in seen:
            seen.add(key)
            out.append(dict(c))
    return out


def tune_kernel(spec_or_name, shape: Optional[Mapping] = None, *,
                bound: Optional[str] = None,
                seed: int = 0,
                iters: int = 5, reps: int = 3,
                max_candidates: Optional[int] = None,
                interpret: bool = False,
                allow_non_tpu: bool = False,
                measure: Optional[Callable[[Dict[str, int],
                                            Callable[[], Any]],
                                           float]] = None,
                store_result: bool = True,
                path: Optional[str] = None) -> TuneResult:
    """Search one kernel's config space on this device and (by default)
    persist the winner into the config cache.

    ``shape`` defaults to the spec's representative on-chip shape (its
    ``small_shape`` under ``interpret``).  ``bound`` overrides the
    candidate-priority verdict (normally from
    :func:`bound_from_ledger`); ``seed`` fixes the candidate visit
    order (the default-config candidate always measures first, the rest
    are deterministically shuffled — two equal-seed runs measure the
    same list in the same order, the CPU-determinism contract).

    ``measure`` injects a timing function ``(config, run) -> seconds``
    (tests substitute a deterministic model; the default is
    :func:`time_case` on the real device clock).  Off-TPU measurement
    requires ``interpret=True`` (stored with ``source="interpret"``) or
    ``allow_non_tpu=True`` — dispatch never calls this; CPU/interpret
    paths never tune implicitly.
    """
    spec = spec_or_name if isinstance(spec_or_name, KernelSpec) \
        else get_spec(spec_or_name)
    on_tpu = jax.default_backend() == "tpu"
    if not on_tpu and not (interpret or allow_non_tpu):
        raise RuntimeError(
            f"tune_kernel({spec.name!r}) measures on-device and the "
            f"default backend is {jax.default_backend()!r} — tuning "
            f"only runs on TPU (pass interpret=True for an explicit "
            f"interpreter-mode probe, e.g. in CPU CI)")
    if shape is None:
        shape = (spec.small_shape or spec.example_shape) \
            if (interpret and not on_tpu) else spec.example_shape
    shape = dict(shape)
    bound = bound or spec.kind
    bucket = spec.bucket(shape)
    default = spec.defaults(shape)

    cands = _dedupe(spec, shape,
                    [default] + list(spec.candidates(shape, bound)))
    # Seeded candidate order (the CPU-determinism contract): the tail is
    # shuffled by ``seed``, then STABLY sorted by the spec's priority key
    # — the ledger-driven visit order survives, equal-priority configs
    # land in seeded order, and two equal-seed runs visit the same list.
    rng = random.Random(seed)
    tail = cands[1:]
    rng.shuffle(tail)
    if spec.priority is not None:
        tail.sort(key=lambda c: spec.priority(shape, c, bound))
    cands = [cands[0]] + tail
    kept, rejected_constraint = [], 0
    for c in cands:
        if c == default or spec.constraint(shape, c):
            kept.append(c)
        else:
            rejected_constraint += 1
    # the measurement budget is its own counter — a truncated candidate
    # passed the constraint and must not read as "VMEM-illegal"
    truncated = 0
    if max_candidates is not None:
        truncated = max(0, len(kept) - max(1, int(max_candidates)))
        kept = kept[:max(1, int(max_candidates))]

    case = spec.build(shape, interpret and not on_tpu)
    timer = measure or (lambda cfg, run: time_case(run, iters=iters,
                                                   reps=reps))

    # default first: its output is the oracle reference and its time the
    # fallback bound every candidate must beat to displace it.
    ref = case.run(default)
    jax.block_until_ready(ref)  # jaxlint: disable=J001 -- warmup fence: the default config's compile must finish before any candidate clock starts
    default_ms = 1e3 * float(timer(default, lambda: case.run(default)))  # jaxlint: disable=J001 -- the timer's return is a host float by contract, not a device value

    best_cfg, best_ms = dict(default), default_ms
    rejected_oracle = 0
    measured = 1
    for cfg in kept:
        if cfg == default:
            continue
        try:
            out = case.run(cfg)
            jax.block_until_ready(out)  # jaxlint: disable=J001 -- per-candidate warmup fence: compile + oracle fetch happen before this candidate's clock, excluded by design
        except Exception:
            rejected_constraint += 1         # did not even compile/run
            continue
        if not _oracle_ok(spec, case, ref, out):
            rejected_oracle += 1
            continue
        ms = 1e3 * float(timer(cfg, lambda: case.run(cfg)))
        measured += 1
        if ms < best_ms:
            best_cfg, best_ms = dict(cfg), ms

    dev = store.device_kind()
    res = TuneResult(
        kernel=spec.name, version=spec.version, bucket=bucket,
        device_kind=dev, bound=bound, config=best_cfg,
        default_config=dict(default),
        best_ms=round(best_ms, 6), default_ms=round(default_ms, 6),
        candidates=measured, rejected_constraint=rejected_constraint,
        rejected_oracle=rejected_oracle, truncated=truncated, order=kept,
        source=("interpret" if (interpret and not on_tpu) else "device"))
    if store_result:
        store.put(spec.name, spec.version, bucket, best_cfg,
                  meta={"best_ms": res.best_ms,
                        "default_ms": res.default_ms,
                        "default_config": res.default_config,
                        "bound": bound, "seed": seed,
                        "source": res.source},
                  path=path)
        res.stored = True
    try:
        from ..telemetry import get_recorder
        rec = get_recorder()
        if rec is not None:
            rec.event("tune", phase="result", kernel=spec.name,
                      bucket=bucket, bound=bound, config=res.config,
                      default_ms=res.default_ms, best_ms=res.best_ms,
                      candidates=res.candidates,
                      rejected_constraint=res.rejected_constraint,
                      rejected_oracle=res.rejected_oracle,
                      truncated=res.truncated,
                      stored=res.stored, source=res.source)
    except Exception:
        pass
    return res


# -- roofline-ledger priority -------------------------------------------------

def bound_from_ledger(ledger: Mapping, spec: KernelSpec) -> Optional[str]:
    """The boundedness verdict for this kernel read off an
    :func:`apex_tpu.prof.roofline.mfu_ledger` result: region rows whose
    name contains any of the spec's ``regions`` fragments vote with
    their modeled-ms weight (falling back to GFLOPs when the ledger has
    no measured clock).  Returns ``"compute"``/``"memory"``, or None
    when no region matches (the spec's own ``kind`` then decides)."""
    votes = {"compute": 0.0, "memory": 0.0}
    matched = False
    for row in (ledger.get("regions") or []):
        name = str(row.get("region", "")).lower()
        if not any(frag in name for frag in spec.regions):
            continue
        matched = True
        weight = float(row.get("modeled_ms") or row.get("flops_g") or 1.0)
        side = row.get("bound")
        if side in votes:
            votes[side] += weight
    if not matched:
        return None
    return "memory" if votes["memory"] >= votes["compute"] else "compute"


def tune_from_ledger(ledger: Mapping, *,
                     specs: Optional[Sequence[KernelSpec]] = None,
                     **kwargs) -> List[TuneResult]:
    """Tune every registered kernel, candidate priority driven by the
    ledger's verdicts; kwargs forward to :func:`tune_kernel`."""
    out = []
    for spec in (specs if specs is not None else all_specs()):
        out.append(tune_kernel(spec,
                               bound=bound_from_ledger(ledger, spec),
                               **kwargs))
    return out
