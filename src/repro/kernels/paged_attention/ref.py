"""Pure-jnp oracle for paged decode attention.

The paged layout stores K/V in a shared block pool of ``(pool_pages, Hkv,
page_size, D)``; each batch slot owns a page table of pool indices.  Token
``t`` of slot ``b`` lives in pool page ``page_table[b, t // page_size]`` at
row ``t % page_size`` of every head.

The oracle gathers the slot's pages back into a contiguous per-slot cache and
runs the exact dense decode-attention math
(:func:`repro.kernels.flash_attention.ref.decode_attention_ref`).  This is
what anchors the dense-equivalence invariant: with a single full-size page
per slot whose table is the identity, the gathered array IS the dense cache
(same shape, same rows), so the computation is bit-identical to the dense
path — not merely numerically close.

Unused page-table entries must still hold valid pool indices (0 is fine);
their rows are masked out by ``cache_len`` exactly like the dense cache's
tail.  Sliding-window attention is not supported in the paged layout (the
window would straddle page boundaries the pallas kernel skips wholesale).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.ref import decode_attention_ref


def gather_pages(pool: jax.Array, page_table: jax.Array) -> jax.Array:
    """(P, Hkv, ps, D) pool + (B, n_pages) table -> (B, Hkv, n_pages*ps, D)."""
    b, n_pages = page_table.shape
    _, hkv, ps, d = pool.shape
    gathered = pool[page_table].transpose(0, 2, 1, 3, 4)  # (B, Hkv, n, ps, D)
    return gathered.reshape(b, hkv, n_pages * ps, d)


def paged_decode_attention_ref(
    q: jax.Array,           # (B, 1, Hq, D)
    k_pages: jax.Array,     # (P, Hkv, page_size, D) shared pool
    v_pages: jax.Array,     # (P, Hkv, page_size, Dv)
    page_table: jax.Array,  # (B, n_pages) int32 pool indices
    cache_len: jax.Array,   # (B,) int32 valid tokens (incl. the new one)
    *,
    logit_softcap: float = 0.0,
    scale: Optional[float] = None,
) -> jax.Array:
    """Single-token decode attention over a block-paged KV pool."""
    k_cache = gather_pages(k_pages, page_table)
    v_cache = gather_pages(v_pages, page_table)
    return decode_attention_ref(
        q, k_cache, v_cache, cache_len,
        logit_softcap=logit_softcap, scale=scale)
