"""Shared VMEM-budget / block-shape math (ISSUE 14 satellite).

One home for the sizing rules the row-blocked Pallas kernels and the
tuner's constraint checker must agree on: the kernels call these
helpers, and :mod:`apex_tpu.tune.measure` uses the same functions to
reject candidate configs that cannot fit scoped VMEM **before** timing
them.

The model: a row-blocked kernel holds ``rows x width`` blocks whose
per-element footprint is ``bytes_per_elem`` (the caller sums its live
operand/output/temporary widths — e.g. the LayerNorm backward holds
g, x, dx at the input itemsize plus four fp32 row-major temporaries,
``3*isz + 16``).  Blocks must be sublane multiples (8 rows) and the
whole block must fit a conservative slice of the ~16 MB scoped-VMEM
budget, leaving room for Mosaic's own pipelining copies.
"""

from __future__ import annotations

from typing import List, Optional

__all__ = ["VMEM_BUDGET_BYTES", "SUBLANE_ROWS", "LANE_COLS", "pick_rows",
           "floor_block_fits", "max_width", "row_block_candidates",
           "pow2_bucket"]

#: scoped-VMEM budget a single kernel block may claim (conservative
#: slice of the ~16 MB scoped limit; measured r5 — see fused_layer_norm)
VMEM_BUDGET_BYTES = int(12e6)
#: the sublane tile: the smallest legal row-block granularity
SUBLANE_ROWS = 8
#: the lane tile: last-dim block granularity for matmul-style kernels
LANE_COLS = 128


def pick_rows(n_rows: int, width: int, bytes_per_elem: int, *,
              row_block: int = 256,
              budget: int = VMEM_BUDGET_BYTES) -> int:
    """Row-block size capped at ``row_block`` that keeps a
    ``rows x width`` block of ``bytes_per_elem``-byte elements inside
    ``budget``: rounded down to the sublane multiple, floored at
    :data:`SUBLANE_ROWS`, and never exceeding ``n_rows``.

    ``row_block`` is the tunable knob (the autotuner's ``row_block``
    config); the budget clamp below it is a hard constraint, so any
    tuned value stays VMEM-legal by construction — and the cap itself
    is rounded to a legal sublane multiple first, so an out-of-band
    cache value (a hand-edited 100, a hostile 3) can never reach
    ``pallas_call`` as an illegal block shape.
    """
    cap = max(SUBLANE_ROWS,
              (int(row_block) // SUBLANE_ROWS) * SUBLANE_ROWS)
    budget_rows = budget // (bytes_per_elem * width)
    rows = min(cap,
               max(SUBLANE_ROWS,
                   (budget_rows // SUBLANE_ROWS) * SUBLANE_ROWS))
    return min(rows, n_rows)


def floor_block_fits(width: int, bytes_per_elem: int, *,
                     budget: int = VMEM_BUDGET_BYTES) -> bool:
    """Whether even the 8-row floor block fits the budget — the width
    gate: beyond it NO row count is legal and the caller must route to
    the jnp path rather than OOM Mosaic at compile."""
    return SUBLANE_ROWS * width * bytes_per_elem <= budget


def max_width(bytes_per_elem: int, *,
              budget: int = VMEM_BUDGET_BYTES) -> int:
    """Widest row the floor block admits for this per-element footprint
    (the inverse of :func:`floor_block_fits`)."""
    return budget // (bytes_per_elem * SUBLANE_ROWS)


def row_block_candidates(n_rows: int, width: int, bytes_per_elem: int, *,
                         budget: int = VMEM_BUDGET_BYTES,
                         blocks=(8, 16, 32, 64, 128, 256, 512, 1024)
                         ) -> List[int]:
    """Legal ``row_block`` candidates for a ``[n_rows, width]`` kernel:
    sublane multiples from ``blocks`` whose budget-clamped block is not
    degenerate (a candidate larger than what the budget admits would
    collapse onto the same clamped block as a smaller one — dedup so
    the tuner never times the same effective config twice)."""
    seen = set()
    out: List[int] = []
    for blk in blocks:
        if blk % SUBLANE_ROWS:
            continue
        eff = pick_rows(n_rows, width, bytes_per_elem,
                        row_block=blk, budget=budget)
        if eff in seen:
            continue
        seen.add(eff)
        out.append(blk)
    return out


def pow2_bucket(n: int) -> int:
    """Round ``n`` up to the next power of two — the shape-bucket
    granularity of the config cache keys (two batch sizes in the same
    pow2 bucket share a tuned config; re-tuning per exact shape would
    fragment the cache for no measured benefit)."""
    n = max(1, int(n))
    b = 1
    while b < n:
        b <<= 1
    return b

