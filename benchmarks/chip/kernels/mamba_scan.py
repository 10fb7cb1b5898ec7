"""Operations and bytes of one Mamba-1 selective-scan call over a sequence,
from its shapes and the recurrence it computes, whatever the chunking:

    s_t = exp(dt_t A) * s_{t-1} + (dt_t u_t) B_t     (C x N)
    y_t = s_t C_t + D u_t

per token and channel: ``dt A`` ``N``, decay ``N``, ``dt u`` 1, times B
``N``, sum ``N``, read-out ``2 N``, skip 2.  Bytes: u and y in the
activation type, dt in float32, B and C in the activation type, A (C x N)
and D in float32, and the final state in float32.
"""

from __future__ import annotations

from typing import Tuple


def cost(seq_len: int, channels: int, state: int,
         itemsize: int = 2) -> Tuple[float, float]:
    L, C, N = seq_len, channels, state
    flops = L * C * (6.0 * N + 3.0)
    nbytes = (2.0 * L * C * itemsize               # u in, y out
              + 4.0 * L * C                        # dt
              + 2.0 * L * N * itemsize             # B, C
              + 4.0 * C * N + 4.0 * C              # A, D
              + 4.0 * C * N)                       # final state
    return flops, nbytes
