"""Operations and bytes of one call of the min-plus kernel, and its least
time on a chip.

The kernel (``repro.kernels.minplus.minplus``) computes
``out[b, i, j] = min_k a[b, i, k] + b[b, k, j]`` over a grid
``(B, Np/bm, Np/bn, Np/bk)`` of ``(bm, bk) x (bk, bn)`` blocks, with
``bm = bn = bk = min(128, N)`` and ``N`` padded with +INF up to ``Np``, a
multiple of the block. Counted at the padded size:

* operations: an add and a min for every (i, k, j) triple, ``2 B Np^3``;
* bytes: an A block and a B block for every grid step (the k axis is
  innermost, so both block indices change at every step), and the output
  block once for every (b, i, j), when the k loop leaves it: f32, 4 bytes.

The add and the min run on the vector unit (there is no min-plus form of the
matrix unit), so the compute ceiling is the chip's f32 vector add/min rate
from ``peaks.json``, not its matrix-unit FLOP/s.
"""

from __future__ import annotations

BLOCK = 128
BYTES_F32 = 4


def blocks(n: int) -> tuple[int, int]:
    """(block edge, padded N) of the kernel at ``n`` tiles."""
    bk = min(BLOCK, n)
    return bk, -(-n // bk) * bk


def grid(batch: int, n: int) -> tuple[int, int, int, int]:
    bk, npad = blocks(n)
    nb = npad // bk
    return (batch, nb, nb, nb)


def call_cost(batch: int, n: int) -> tuple[int, int]:
    """(operations, bytes) of one ``minplus`` call on a (batch, n, n) stack."""
    bk, npad = blocks(n)
    b, ni, nj, nk = grid(batch, n)
    steps = b * ni * nj * nk
    ops = 2 * batch * npad ** 3
    nbytes = BYTES_F32 * (steps * 2 * bk * bk + b * ni * nj * bk * bk)
    return ops, nbytes


def least_time(ops: float, nbytes: float, peaks: dict) -> tuple[float, str]:
    """(seconds, bound) of the larger of the compute and memory times."""
    t_ops = ops / peaks["vpu_f32_addmin_ops_per_s"]
    t_mem = nbytes / peaks["hbm_bytes_per_s"]
    return (t_ops, "vpu") if t_ops >= t_mem else (t_mem, "hbm")
