"""Operations and bytes of one paged decode-attention call for one slot and
one layer, from the live context alone: the work the model needs, whatever
pages the kernel streams.

    s = q k^T / sqrt(D)  over the ``context`` cached keys, softmax, o = p v

Operations: the scores and the weighted sum of values, 2 each per query
head, key and head dimension (the softmax's few operations a score, under
2% at D 128, are left out).  Bytes: the context's keys and values of every
KV head, read once, and the query and the output, all in the cache's type.
"""

from __future__ import annotations

from typing import Tuple


def cost(context: int, q_heads: int, kv_heads: int, head_dim: int,
         itemsize: int = 2) -> Tuple[float, float]:
    flops = 4.0 * q_heads * head_dim * context
    nbytes = (2.0 * kv_heads * context * head_dim * itemsize    # K, V
              + 2.0 * q_heads * head_dim * itemsize)            # q in, out
    return flops, nbytes
