"""Row-block sizing for the row-blocked kernels, worked out from bytes.

Mosaic stages every block of a ``pallas_call`` in VMEM, double-buffered,
under a scoped VMEM limit of 16 MiB (the compiler's default on TPU v5e;
exceeding it is a compile-time ``RESOURCE_EXHAUSTED``). A row-blocked
kernel therefore picks its rows per block from what one row costs in
VMEM: the padded bytes of every block row it reads or writes, twice for
the pipeline, plus the in-kernel temporaries. Padding follows the
native tile of the dtype — the last dim rounds up to 128 lanes and the
second-to-last to 8 (32-bit), 16 (16-bit) or 32 (8-bit) sublanes.
"""
from __future__ import annotations

import numpy as np

LANE = 128
SCOPED_VMEM_LIMIT = 16 * 2**20
# share of the scoped limit spent on pipelined blocks; the rest is left
# to the kernel's own temporaries and Mosaic's internal scratch
BLOCK_BUDGET = SCOPED_VMEM_LIMIT // 2


def _ceil_to(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def tile_bytes(shape: tuple[int, ...], dtype) -> int:
    """VMEM bytes of one array of ``shape`` laid out in native tiles."""
    itemsize = np.dtype(dtype).itemsize
    sublane = 8 * (4 // itemsize)
    dims = list(shape)
    if len(dims) >= 1:
        dims[-1] = _ceil_to(dims[-1], LANE)
    if len(dims) >= 2:
        dims[-2] = _ceil_to(dims[-2], sublane)
    return int(np.prod(dims)) * itemsize


def row_bytes(width: int, dtype) -> int:
    """VMEM bytes one row of a 2-D (rows, width) block adds."""
    return _ceil_to(width, LANE) * np.dtype(dtype).itemsize


def rows_per_block(per_row: int, n: int, *, cap: int = 256,
                   mult: int = 8, budget: int = BLOCK_BUDGET) -> int:
    """Largest multiple of ``mult`` rows (at most ``cap``, and no more
    than ``n`` rounded up to ``mult``) whose blocks, at ``per_row`` bytes
    of VMEM per row (pipeline buffers and temporaries), fit in
    ``budget``."""
    fit = max(mult, (budget // max(per_row, 1)) // mult * mult)
    return max(mult, min(cap, fit, _ceil_to(max(n, 1), mult)))
