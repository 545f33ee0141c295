"""Analytic block-size tuner for the fused crossbar kernels.

Replaces the hardcoded 128s in ``substrate/exec.py``: given the true
problem shape ``(M, K, N, r)`` the tuner picks ``(bm, bn, bk)`` and the
padded operand extents:

* A decode call streams 2 bytes of codes per weight and performs
  ``2*M`` flops per weight, so at a handful of rows (``M <=
  GEMV_MAX_M``) it is bound by streaming the codes — the tile choice
  there minimizes grid bookkeeping and streams the codes exactly once:
  a single sublane-aligned M block (the GEMV variant) with the largest
  ``(bk, bn)`` that fits VMEM.
* At prefill shapes (M >= 128) the kernel is compute-bound and tiles at
  the 128x128 MXU granule; ``bk``/``bn`` still grow to the VMEM budget
  so each x tile is revisited as few times as possible.
* In interpret mode (CPU hosts) there is no hardware tile constraint, so
  the plan avoids padding entirely: blocks equal the true extents (grid
  collapses to the K split only for very large K). This is what makes
  the decode hot path on a CPU container do no ``jnp.pad`` work at all
  once operands are prepared (``substrate/prepared.py``).

Plans are memoized in a module-level table (``tile_table()``) — shape
dispatch at trace time is a dict lookup.
"""
from __future__ import annotations

from typing import Dict, NamedTuple, Tuple

# VMEM working-set budget per grid cell: half of the 16 MiB/core so the
# pipeline can double-buffer the next block while computing.
VMEM_BUDGET_BYTES = 8 * 1024 * 1024

# largest single M block the GEMV (single-M-block) variant handles; above
# this the tiled kernel's M grid takes over.
GEMV_MAX_M = 64

_LANE = 128      # minor-dim tile granule (all dtypes)
_SUBLANE_F32 = 8
_SUBLANE_I8 = 32


class TilePlan(NamedTuple):
    """Block sizes plus the padded operand extents they imply."""

    bm: int
    bn: int
    bk: int
    m_pad: int
    k_pad: int
    n_pad: int

    @property
    def gemv(self) -> bool:
        """Single M block (decode-shaped): dispatch the GEMV variant."""
        return self.m_pad == self.bm


_TABLE: Dict[Tuple, TilePlan] = {}


def _round_up(x: int, mult: int) -> int:
    return x + (-x) % mult


def _largest_divisor(dim: int, cap: int, granule: int) -> int:
    """Largest multiple of ``granule`` that divides ``dim`` and is <= cap
    (``dim`` itself is a multiple of ``granule``)."""
    best = granule
    d = granule
    while d <= min(dim, cap):
        if dim % d == 0:
            best = d
        d += granule
    return best


def _vmem_bytes(bm: int, bn: int, bk: int, r: int, int8: bool) -> int:
    """Per-grid-cell working set of the fused dora_linear kernel."""
    x_b = (1 if int8 else 4) * bm * bk
    codes_b = 2 * bk * bn  # g_pos + g_neg, 1 byte each
    acc_b = 4 * bm * bn
    xa_b = 4 * bm * r + 4 * bk * r + 4 * r * bn  # xa scratch + a + b tiles
    epilogue_b = 3 * 4 * bn + 4 * bm  # scale + gamma + out row, x row-scale
    return x_b + codes_b + acc_b + xa_b + epilogue_b


def select_tiles(
    m: int, k: int, n: int, r: int, *,
    interpret: bool, int8: bool = False,
) -> TilePlan:
    """Pick ``(bm, bn, bk)`` + padded extents for a ``(M, K, N, r)`` fused
    crossbar linear. Memoized — see module docstring for the policy."""
    key = (m, k, n, r, interpret, int8)
    plan = _TABLE.get(key)
    if plan is not None:
        return plan

    if interpret:
        # CPU functional mode: no tile alignment, so never pad. Split only
        # K (accumulator reduction keeps the working set bounded) when it
        # is very large and splits evenly; the grid stays 1x1 otherwise.
        bm, bn, bk = m, n, k
        if k > 2048:
            for cand in range(2048, 0, -1):
                if k % cand == 0:
                    bk = cand
                    break
        plan = TilePlan(bm, bn, bk, m, k, n)
    else:
        sublane = _SUBLANE_I8 if int8 else _SUBLANE_F32
        k_pad = _round_up(k, _LANE)
        n_pad = _round_up(n, _LANE)
        if m <= GEMV_MAX_M:
            # code-streaming-bound region: one sublane-aligned M block,
            # codes streamed once through the K-parallel grid.
            bm = _round_up(m, sublane)
            m_pad = bm
        else:
            bm = _LANE
            m_pad = _round_up(m, _LANE)
        # grow bk first (fewer accumulator round-trips), then bn, while
        # the working set fits the double-buffered VMEM budget.
        bk = _largest_divisor(k_pad, 512, _LANE)
        while bk > _LANE and _vmem_bytes(bm, _LANE, bk, r, int8) > VMEM_BUDGET_BYTES:
            bk -= _LANE
        bn = _largest_divisor(n_pad, 512, _LANE)
        while bn > _LANE and _vmem_bytes(bm, bn, bk, r, int8) > VMEM_BUDGET_BYTES:
            bn -= _LANE
        plan = TilePlan(bm, bn, bk, m_pad, k_pad, n_pad)

    _TABLE[key] = plan
    return plan


def tile_table() -> Dict[Tuple, TilePlan]:
    """Snapshot of the memoized plan table (introspection/benchmarks)."""
    return dict(_TABLE)
