"""Kernel registry — the tunable-kernel contract (ISSUE 14, layer 1).

A :class:`KernelSpec` is everything the measurement harness needs to
search one kernel's config space safely:

* ``candidates(shape, bound)`` — the config space, ORDERED by the
  roofline verdict: a memory-bound region wants layout candidates
  (smaller blocks / different row blocking — less VMEM residency per
  byte moved) tried first, a compute-bound region wants block-size
  candidates (bigger MXU tiles) first.  The hard-coded default config
  is always a candidate, which is what makes the tuned-never-slower
  fallback guarantee structural: the winner is a min over a set that
  contains the default.
* ``constraint(shape, config)`` — the VMEM-budget/legality gate
  (:mod:`apex_tpu.tune.space`), applied BEFORE timing; an illegal
  candidate is rejected, never compiled.
* ``build(shape, interpret)`` — a :class:`TuneCase`: deterministic
  representative inputs plus a jitted ``run(config)`` closure the
  harness times, and the oracle policy (``exact`` kernels must match
  the default config's output BITWISE — row/tile partitioning that
  does not change per-element math; flash attention's online-softmax
  recurrence reorders with the KV block, so it checks to tolerance).
* ``regions`` — roofline-ledger region-name fragments that map ledger
  rows back to this kernel (:func:`apex_tpu.tune.measure.bound_from_ledger`).
* ``version`` — mirrors the kernel module's ``TUNE_VERSION``; bumping
  it invalidates every cached config for the kernel.

The six builtin kernels register from :mod:`apex_tpu.tune.kernels`
(imported lazily by :func:`load_builtin` so the kernel modules — which
themselves import ``tune.space``/``tune.dispatch`` for their dispatch
consult — never see an import cycle).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable, Dict, List, Mapping, Optional, Tuple

__all__ = ["KernelSpec", "TuneCase", "register", "get_spec", "all_specs",
           "load_builtin"]


@dataclass
class TuneCase:
    """One concrete tuning problem: ``run(config)`` executes the kernel
    end to end (fwd+bwd where the kernel has a custom VJP) on fixed
    representative inputs and returns its outputs as a pytree; the
    harness times it and compares candidates' outputs against the
    default config's."""
    run: Callable[[Dict[str, int]], object]
    #: the same computation on the same inputs through the jnp reference
    #: the kernel's module carries (its fallback and test oracle); same
    #: output pytree as ``run``.  What an on-chip kernel sweep compares
    #: the Mosaic-compiled ``run`` against.
    ref: Optional[Callable[[], object]] = None
    #: oracle tolerance for non-exact kernels (rtol, atol)
    tol: Tuple[float, float] = (2e-2, 2e-3)


@dataclass
class KernelSpec:
    name: str
    version: int
    #: config keys the kernel understands (the dispatch-consult filter)
    params: Tuple[str, ...]
    #: which side of the roofline the kernel's default workload stresses
    #: (the candidate-order default when no ledger verdict is supplied)
    kind: str                                    # "compute" | "memory"
    #: True: candidates must match the default config bitwise
    exact: bool
    defaults: Callable[[Mapping], Dict[str, int]]
    candidates: Callable[[Mapping, Optional[str]], List[Dict[str, int]]]
    constraint: Callable[[Mapping, Dict[str, int]], bool]
    build: Callable[[Mapping, bool], TuneCase]
    bucket: Callable[[Mapping], str]
    #: optional priority key ``(shape, config, bound) -> float``: the
    #: harness visits candidates in ascending key order (stable over a
    #: seeded shuffle, so equal-priority configs land in seeded order).
    #: This is where the ledger verdict steers the search — e.g. bigger
    #: MXU tiles first when compute-bound, smaller blocks first when
    #: memory-bound.  None: pure seeded order.
    priority: Optional[Callable[[Mapping, Dict[str, int], Optional[str]],
                                float]] = None
    #: optional ``(shape, config) -> hashable`` mapping a config to the
    #: EFFECTIVE block the kernel will actually run after its budget
    #: clamps — the harness dedupes candidates on this key, so two
    #: configs that clamp onto the same program are never both timed
    #: (and a clamped twin of the default can never be persisted as a
    #: noise "win").  None: dedupe on the raw config.
    effective: Optional[Callable[[Mapping, Dict[str, int]],
                                 object]] = None
    #: representative on-chip shape (bench / CLI default)
    example_shape: Dict[str, object] = field(default_factory=dict)
    #: small shape for interpret-mode probes (CPU CI, tests)
    small_shape: Dict[str, object] = field(default_factory=dict)
    #: roofline-ledger region-name fragments attributable to this kernel
    regions: Tuple[str, ...] = ()


_REGISTRY: Dict[str, KernelSpec] = {}
_BUILTIN_LOADED = False


def register(spec: KernelSpec) -> KernelSpec:
    """Add (or replace — re-registration is idempotent by name) one
    kernel spec; returns it so modules can keep a handle."""
    if spec.kind not in ("compute", "memory"):
        raise ValueError(f"spec.kind must be 'compute' or 'memory', "
                         f"got {spec.kind!r}")
    _REGISTRY[spec.name] = spec
    return spec


def get_spec(name: str) -> KernelSpec:
    """The registered spec, loading the builtins on first miss."""
    if name not in _REGISTRY:
        load_builtin()
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"no tunable kernel {name!r} registered; known: "
            f"{sorted(_REGISTRY)}") from None


def all_specs() -> List[KernelSpec]:
    """Every registered spec (builtins loaded), sorted by name."""
    load_builtin()
    return [_REGISTRY[k] for k in sorted(_REGISTRY)]


def registered_versions() -> Dict[str, int]:
    """``{kernel: version}`` of everything registered — the
    :func:`apex_tpu.tune.store.prune_stale` input."""
    load_builtin()
    return {s.name: s.version for s in _REGISTRY.values()}


def load_builtin() -> None:
    """Import the builtin registrations (flash_attention,
    fused_layer_norm, xentropy, quantized_matmul).
    Idempotent; kernels keep importing fine without it — this is the
    tuner/CLI side only."""
    global _BUILTIN_LOADED
    if _BUILTIN_LOADED:
        return
    from . import kernels as _kernels        # noqa: F401  (registers)
    _BUILTIN_LOADED = True
