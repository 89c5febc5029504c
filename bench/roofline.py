"""Operations and bytes of one D3Q19 TRT stream-collide cell update.

Bytes: the least traffic of one update is its 19 float32 populations read
and written once, plus the int32 cell type read: 19*4 + 19*4 + 4 = 156 B.
Ghost cells and the kernel's tile layout add traffic that this count leaves
out on purpose, so the roofline share falls when they grow.

Operations, counted from the update's formula over the velocity set
(adds, multiplies and one reciprocal each count one):
bounce-back lid terms (one add per direction), density (Q-1 adds),
momentum (one add per non-zero velocity component), three divisions,
|u|^2 (5), per direction c.u (non-zero components), the equilibrium
(7 per direction), TRT (the symmetric and antisymmetric parts, 8 per
direction) and the fluid blend (3 per direction).
"""

from __future__ import annotations

import numpy as np

BYTES_PER_UPDATE = 19 * 4 + 19 * 4 + 4


def flops_per_update(c) -> int:
    c = np.asarray(c)
    q = len(c)
    nz = int(np.count_nonzero(c))
    return q + (q - 1) + nz + 3 + 5 + nz + 7 * q + 8 * q + 3 * q


def least_seconds(updates: int, c, peaks: dict) -> tuple[float, str]:
    """The least time the chip could take for ``updates`` cell updates, and
    which peak bounds it ("hbm" or "flops")."""
    t_bytes = updates * BYTES_PER_UPDATE / float(peaks["hbm_bytes_per_s"])
    t_flops = updates * flops_per_update(c) / float(peaks["bf16_flops_per_s"])
    return (t_bytes, "hbm") if t_bytes >= t_flops else (t_flops, "flops")
