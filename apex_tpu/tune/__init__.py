"""apex_tpu.tune — roofline-driven Pallas kernel autotuner (ISSUE 14).

Every Pallas kernel in the repo used to ship hand-picked block constants
from a single v5e sweep (``_DEFAULT_BLOCK_Q/_K`` in flash attention,
``_ROW_BLOCK`` in the fused LayerNorm, ``_BLOCK_M/_N`` in the
quantized matmuls).  This package replaces those frozen sweeps with a
measured, per-device search:

* :mod:`~apex_tpu.tune.registry` — each tunable kernel declares its
  config space (block sizes / grid layouts), VMEM-budget constraint,
  correctness oracle, and which roofline-ledger regions it lives in.
  flash_attention (fwd+bwd), fused_layer_norm, contrib xentropy, and
  the quantized matmuls all register.
* :mod:`~apex_tpu.tune.measure` — times candidate configs on-device
  (min-of-K with explicit sync, compile excluded; candidates failing
  the oracle or the VMEM gate are rejected before timing) and
  prioritizes the search by a roofline ledger's compute-vs-memory
  boundedness verdicts (:func:`~apex_tpu.tune.measure.bound_from_ledger`).
* :mod:`~apex_tpu.tune.store` — persistent config cache keyed by
  ``(device kind, kernel name, kernel version, shape bucket)``, stored
  beside :mod:`apex_tpu.cache`'s XLA compilation cache
  (:func:`apex_tpu.cache.enable` points both at the same directory).
* :mod:`~apex_tpu.tune.dispatch` — the zero-cost consult every
  registered kernel makes at dispatch time; a miss (or any cache
  problem) falls back to the kernel's hard-coded defaults.  CPU and
  interpret paths never tune — tuning is always an explicit
  :func:`~apex_tpu.tune.measure.tune_kernel` / CLI run.
* :mod:`~apex_tpu.tune.space` — the shared VMEM-budget / row-block
  math both the row-blocked kernels and the tuner's constraint
  checker use (hoisted out of ``fused_layer_norm``).

CLI::

    python -m apex_tpu.tune kernel flash_attention        # tune one
    python -m apex_tpu.tune ledger LEDGER.json            # ledger-driven
    python -m apex_tpu.tune show                          # cached table

Telemetry: the tuner emits ``tune`` events and dispatch maintains a
``tuned_kernel_pct`` gauge (exported through the existing Prometheus
path).  See ``docs/tune.md``.
"""

from . import space                                     # noqa: F401
from .dispatch import kernel_config, dispatch_stats     # noqa: F401
from .store import lookup, put, entries, cache_path     # noqa: F401

__all__ = ["space", "kernel_config", "dispatch_stats", "lookup", "put",
           "entries", "cache_path", "KernelSpec", "register", "get_spec",
           "all_specs", "load_builtin", "tune_kernel", "tune_from_ledger",
           "bound_from_ledger", "TuneResult"]

# The registry/measure layers import the kernel modules (which in turn
# import tune.space/tune.dispatch) — load them lazily so the kernel
# modules can import this package without a cycle.
_LAZY = {
    "KernelSpec": ("registry", "KernelSpec"),
    "register": ("registry", "register"),
    "get_spec": ("registry", "get_spec"),
    "all_specs": ("registry", "all_specs"),
    "load_builtin": ("registry", "load_builtin"),
    "tune_kernel": ("measure", "tune_kernel"),
    "tune_from_ledger": ("measure", "tune_from_ledger"),
    "bound_from_ledger": ("measure", "bound_from_ledger"),
    "TuneResult": ("measure", "TuneResult"),
}


def __getattr__(name):
    if name in _LAZY:
        import importlib
        mod_name, attr = _LAZY[name]
        mod = importlib.import_module("." + mod_name, __name__)
        val = getattr(mod, attr)
        globals()[name] = val
        return val
    raise AttributeError(
        "module 'apex_tpu.tune' has no attribute {!r}".format(name))
