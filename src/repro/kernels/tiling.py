"""Dispatch-tile coarsening for row-parallel kernels.

The match kernels are row-elementwise: every program instance computes a
pure function of its row tile, so the *dispatch* tile (the BlockSpec row
count) is a free parameter as long as it divides the padded row count.
The public padding contracts stay at the fine tiles (``ROW_TILE`` = 8,
``FILTER_ROW_TILE`` = 128, ``SIG_ROW_TILE`` = 1024) -- callers pad to
those -- but launching one program per fine tile is ruinous at scale: a
1M-row corpus is 131072 grid steps for the SWAR kernel, and per-step
overhead (a few us on TPU, ~400us in interpret mode) dominates the
arithmetic.  Coarsening the dispatch tile amortizes the launch: same ops
per row, bit-identical output, O(grid) overhead shrunk by the coarsening
factor.

The tile grows by doubling (keeps divisibility trivially) until it stops
dividing the row count, exceeds the VMEM block budget, or hits the row
cap.  The VMEM budget is conservative: Mosaic double-buffers every
block and the kernel body holds temporaries, so we keep the *single-copy*
footprint of the blocks under ~2 MiB of the 16 MiB scoped VMEM limit a
v5e kernel gets by default.  Footprints count VMEM as laid out: the minor
dimension of every block occupies whole 128-lane rows (``lane_bytes``),
so an (R, 8) uint32 block costs 512 bytes per row, not 32.
"""

from __future__ import annotations

VMEM_BLOCK_BUDGET = 2 << 20   # bytes, single-copy footprint of all blocks
MAX_TILE_ROWS = 1 << 17       # diminishing returns past ~131K rows/program
LANES = 128


def lane_bytes(*minor_dims: int, itemsize: int = 4) -> int:
    """VMEM bytes per row of blocks with these minor dims (lane-padded)."""
    return sum(-(-d // LANES) * LANES for d in minor_dims) * itemsize


def coarse_row_tile(n_rows: int, base_tile: int, row_bytes: int, *,
                    budget_bytes: int = VMEM_BLOCK_BUDGET,
                    max_rows: int = MAX_TILE_ROWS) -> int:
    """Largest power-of-two multiple of ``base_tile`` that divides
    ``n_rows`` and keeps ``tile * row_bytes`` within the VMEM budget.

    ``row_bytes`` is the per-row VMEM footprint of every row-tiled block
    the kernel touches (inputs + outputs, lane-padded: see
    ``lane_bytes``).  Returns ``base_tile`` unchanged when nothing larger
    fits -- the fine tile is always legal.
    """
    tile = base_tile
    while (tile * 2 <= max_rows
           and n_rows % (tile * 2) == 0
           and tile * 2 * row_bytes <= budget_bytes):
        tile *= 2
    return tile
