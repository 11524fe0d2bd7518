"""Row-tile sizing shared by the row-streaming kernels.

Pallas double-buffers every streamed operand in VMEM, and the v5e
compiler's default scoped VMEM limit is 16 MiB.  A float32 tile of 256
rows at d_model 4096 is already 4 MiB, so ``rms_norm`` (in + out) and
``ce_loss`` (rows + vocab tiles) overflow it at yi-6b widths.  Capping
each tile at ``TILE_BYTES`` keeps them inside the limit.
"""
from __future__ import annotations

TILE_BYTES = 2 << 20


def tile_rows(requested: int, row_bytes: int) -> int:
    """``requested`` rows, cut to a multiple of 8 whose tile fits in
    ``TILE_BYTES`` (at least 8 rows)."""
    return max(8, min(requested, TILE_BYTES // row_bytes) // 8 * 8)
