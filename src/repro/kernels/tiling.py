"""Tile-shape selection shared by the Pallas wire kernels and their
transport-layer dispatch (transport/codecs.py).

Mosaic takes a block whose last two dims are multiples of the native
(8, 128) tile, or equal to the whole array dims.  Two regimes:

* ``wire_tiling`` — the TILED kernels (q8 quantize_wire) block both dims,
  so the row block must respect the native f32 (8, 128) tile: the row
  block is the largest POWER-OF-TWO divisor of m capped at 256 (O(1),
  replacing an O(m) decrement scan that degraded to bm=1 on prime m), and
  shapes whose best row block would under-fill the 8-sublane tile get
  ``None`` — the dispatch falls back to the pure-jnp path rather than
  running 1-sublane tiles at 1/8th VPU utilization.

* ``column_tiling`` — the per-tensor / per-row kernels (q4 pair packing,
  TopK threshold) take any m: the row block is 8 when 8 divides m and the
  whole m otherwise, and the feature dim (padded by the caller to a lane
  multiple) is cut into column blocks that fit the VMEM budget.
"""
from __future__ import annotations

from typing import Optional, Tuple

LANE_BLOCKS = (2048, 1024, 512, 256, 128)
MIN_SUBLANES = 8               # native f32 sublane tile
MAX_ROW_BLOCK = 256
VMEM_BUDGET = 1024 * 1024      # input block bytes per column-tiled instance


def pow2_row_block(m: int, cap: int = MAX_ROW_BLOCK) -> int:
    """Largest power-of-two divisor of ``m``, capped at ``cap``."""
    return min(cap, m & -m) if m > 0 else 1


def lane_block(n: int) -> Optional[int]:
    for c in LANE_BLOCKS:
        if n % c == 0:
            return c
    return None


def wire_tiling(flat_shape) -> Optional[Tuple[int, int]]:
    """(bm, bn) for the tiled wire kernels, or None when no tiling fits
    (feature dim not a 128-multiple, or the row block would under-fill
    the native 8-sublane tile)."""
    m, n = flat_shape
    bn = lane_block(n)
    if bn is None:
        return None
    bm = pow2_row_block(m)
    if bm < MIN_SUBLANES:
        return None
    return bm, bn


def padded_width(n: int, multiple: int) -> int:
    """``n`` rounded up to a multiple of ``multiple``."""
    return -(-n // multiple) * multiple


def column_tiling(m: int, n: int, *, lane_multiple: int = 128,
                  max_lanes: int = 2048, bytes_per_elem: int = 4,
                  budget: int = VMEM_BUDGET) -> Tuple[int, int]:
    """(bm, bn) for the column-tiled kernels over an (m, n) operand whose
    ``n`` is a multiple of ``lane_multiple``: bm is 8 when 8 divides m and
    the whole m otherwise (both legal Mosaic blocks); bn is the largest
    power-of-two multiple of ``lane_multiple`` that divides n, is at most
    ``max_lanes`` and keeps the (bm, bn) block within ``budget``."""
    assert n % lane_multiple == 0, (n, lane_multiple)
    bm = MIN_SUBLANES if m % MIN_SUBLANES == 0 else m
    bn = lane_multiple
    while (2 * bn <= max_lanes and n % (2 * bn) == 0
           and bm * 2 * bn * bytes_per_elem <= budget):
        bn *= 2
    return bm, bn
