"""Work of the mapspace scoring kernel, counted from the mapspace rows it
scores and never from the kernel's own layout, block shapes or padding.

A row is one candidate mapping of a `PackedMapspace`: `factors` and
`rank` are [L, 7] int32 and `store` is [Lm, 3] bool (L tiling levels, Lm
of them memories), and the kernel returns two float32 scores (cycles,
energy).  Those bytes are the least any scorer must move per row.

Operations per row follow the cost model's formulas (TRIM §6,
`bench/reference/trim/evaluator.py`), each add, multiply, divide or
compare counted once:

  * tile extents T(l) per memory level: 7 multiplies per level (7 L);
  * per tensor and storage-chain pair (Lm pairs per tensor, 3 tensors):
    the union tile (7), the per-instance and union word counts (8), the
    used instances (7), the delivery visits over at most 7 Lm loops
    (7 Lm), the read/write/fill traffic (6) and the zero-skip discount
    (4): 32 + 7 Lm;
  * per memory level, cycles and energy (6); the NoC's cycles and energy
    (6); the compute level (4);
  * the max over the L + 1 levels' cycles and the sum of their energies
    (2 (L + 1)), static energy (2 Lm + 2) and the score (1).

The roofline holds the bytes against the HBM peak alone.  The operations
are float32 and int32 elementwise work on the vector unit, whose peak
Google does not publish for the v5e; the MXU's bf16 peak does not bound
them.  The bytes' least time is a lower bound on the kernel's least
time, so the share never reads above the true one.  The operations are
printed beside it.
"""
from __future__ import annotations

from typing import Dict, Tuple

SCORE_BYTES = 2 * 4            # cycles and energy, float32
TENSORS = 3


def row_bytes(n_levels: int, n_mem: int) -> int:
    return n_levels * 7 * 4 * 2 + n_mem * 3 * 1 + SCORE_BYTES


def row_ops(n_levels: int, n_mem: int) -> int:
    return (7 * n_levels
            + TENSORS * n_mem * (32 + 7 * n_mem)
            + 6 * n_mem + 6 + 4
            + 2 * (n_levels + 1) + 2 * n_mem + 2 + 1)


def mapspace_work(factors, rank, store) -> Tuple[int, int]:
    """(operations, bytes) to score a packed mapspace's rows, from the
    arrays as they are: their row count, shapes and dtypes."""
    n, n_levels = factors.shape[0], factors.shape[1]
    n_mem = store.shape[1]
    nbytes = factors.nbytes + rank.nbytes + store.nbytes + n * SCORE_BYTES
    return n * row_ops(n_levels, n_mem), int(nbytes)


def rows_work(rows: int, n_levels: int, n_mem: int) -> Tuple[int, int]:
    """(operations, bytes) for `rows` rows of one level layout."""
    return rows * row_ops(n_levels, n_mem), rows * row_bytes(n_levels, n_mem)


def roofline(nbytes: float, seconds: float,
             peaks: Dict[str, float]) -> float:
    """-> share of the roofline in %: the bytes' least time at the peak
    HBM bandwidth over the measured time."""
    return 100.0 * nbytes / peaks["hbm_bytes_per_s"] / seconds
