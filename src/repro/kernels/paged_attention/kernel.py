"""Pallas TPU paged decode attention (single new token over a paged KV pool).

Structure mirrors the dense decode kernel
(:mod:`repro.kernels.flash_attention.kernel`): grid ``(B, Hkv, n_pages)``
with the page dimension sequential so the online-softmax scratch carries
across a slot's pages.  The difference is *where* each kv block comes from:
the block index map reads the slot's page table (scalar-prefetched, so it is
available at index-map time) and streams pool page ``page_table[ib, ip]``
into VMEM instead of a contiguous cache slice.  This is the vLLM-style
paged-attention dataflow: K/V never materialize contiguously per slot.

Both the page table and the per-slot valid lengths ride in scalar prefetch
(``num_scalar_prefetch=2``); unused table entries must hold valid pool
indices (their rows are masked by ``cache_len``).

Layouts: q (B, 1, Hq, D), viewed as (B, Hkv, G, D) inside the wrapper;
pools (P, Hkv, page_size, D), stored that way so every block's last two
dimensions are (page_size, head_dim) — the TPU tiling rule's (8, 128)-or-whole
— with no per-tick transpose; out (B, 1, Hq, Dv).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro import compat

NEG_INF = -1e30
_LANE = 128


def _paged_decode_kernel(tbl_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                         acc_ref, m_ref, l_ref, *, scale: float,
                         logit_softcap: float, page_size: int, n_pages: int):
    ib = pl.program_id(0)
    ip = pl.program_id(2)
    cache_len = len_ref[ib]

    @pl.when(ip == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    kv_start = ip * page_size

    @pl.when(kv_start < cache_len)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale              # (G, D)
        k = k_ref[0, 0].astype(jnp.float32)                      # (ps, D)
        v = v_ref[0, 0].astype(jnp.float32)                      # (ps, Dv)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # (G, ps)
        if logit_softcap > 0.0:
            s = logit_softcap * jnp.tanh(s / logit_softcap)
        k_pos = kv_start + jax.lax.broadcasted_iota(
            jnp.int32, (q.shape[0], page_size), 1)
        mask = k_pos < cache_len
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[:, 0]
        l_prev = l_ref[:, 0]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur[:, None])
        p = jnp.where(mask, p, 0.0)
        l_cur = alpha * l_prev + jnp.sum(p, axis=-1)
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + pv
        m_ref[...] = jnp.broadcast_to(m_cur[:, None], m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_cur[:, None], l_ref.shape)

    @pl.when(ip == n_pages - 1)
    def _finalize():
        l = l_ref[:, 0]
        denom = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / denom[:, None]).astype(o_ref.dtype)


def paged_decode_attention_pallas(
    q: jax.Array,           # (B, 1, Hq, D)
    k_pages: jax.Array,     # (P, Hkv, page_size, D)
    v_pages: jax.Array,     # (P, Hkv, page_size, Dv)
    page_table: jax.Array,  # (B, n_pages) int32 pool indices
    cache_len: jax.Array,   # (B,) int32 valid tokens (incl. the new one)
    *,
    logit_softcap: float = 0.0,
    scale: Optional[float] = None,
    interpret: bool = False,
) -> jax.Array:
    b, sq, hq, d = q.shape
    _, hkv, page_size, dv = v_pages.shape
    assert sq == 1
    assert hq % hkv == 0, (hq, hkv)
    g = hq // hkv
    n_pages = page_table.shape[1]
    if scale is None:
        scale = d ** -0.5

    kernel = functools.partial(
        _paged_decode_kernel, scale=scale, logit_softcap=logit_softcap,
        page_size=page_size, n_pages=n_pages)

    grid_spec = compat.prefetch_scalar_grid_spec(
        num_scalar_prefetch=2,
        grid=(b, hkv, n_pages),
        in_specs=[
            pl.BlockSpec((1, 1, g, d),
                         lambda ib, ih, ip, tbl, lens: (ib, ih, 0, 0)),
            pl.BlockSpec((1, 1, page_size, d),
                         lambda ib, ih, ip, tbl, lens: (tbl[ib, ip], ih, 0, 0)),
            pl.BlockSpec((1, 1, page_size, dv),
                         lambda ib, ih, ip, tbl, lens: (tbl[ib, ip], ih, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, dv),
                               lambda ib, ih, ip, tbl, lens: (ib, ih, 0, 0)),
        scratch_shapes=[
            compat.vmem((g, dv), jnp.float32),
            compat.vmem((g, _LANE), jnp.float32),
            compat.vmem((g, _LANE), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, dv), q.dtype),
        compiler_params=compat.tpu_compiler_params(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(page_table.astype(jnp.int32), cache_len.astype(jnp.int32),
      q.reshape(b, hkv, g, d), k_pages, v_pages)
    return out.reshape(b, 1, hq, dv)
