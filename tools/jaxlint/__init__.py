"""jaxlint — tracing-safety & dtype-discipline static analyzer for the
apex_tpu stack.

Rules (see ``docs/jaxlint.md`` for the failure each one prevents):

====  =========================================================
J001  host sync in device code (device_get / .item() / float())
J002  jax.jit with non-array Python args not marked static
J003  fp32 dtype leak inside a bf16/amp-cast path
J004  retracing hazard (jit fed varying Python scalars)
J005  use-after-donate of a donate_argnums buffer
J006  Python control flow branching on a traced value under jit
====  =========================================================

Usage::

    python -m tools.jaxlint apex_tpu examples tools

Inline waiver (MUST carry a reason)::

    x = float(jax.device_get(v))  # jaxlint: disable=J001 -- checkpoint read

The runtime complement — catching the retraces J004 can only guess at
— is ``apex_tpu.prof.assert_trace_count``.
"""

from .linter import Finding, RULES, lint_file, lint_paths, lint_source  # noqa: F401
from .cli import main                                                   # noqa: F401

__all__ = ["Finding", "RULES", "lint_file", "lint_paths", "lint_source",
           "main"]
