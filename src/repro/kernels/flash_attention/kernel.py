"""Pallas TPU flash attention (forward) + single-token decode attention.

TPU adaptation notes
--------------------
- Online-softmax accumulation lives in VMEM scratch; the kv grid dimension is
  sequential ("arbitrary") so the scratch carries across kv blocks, exactly
  the HBM->VMEM streaming structure flash attention wants on TPU.
- Block sizes (``q_block`` x ``kv_block``) are first-class tuning knobs
  (CAMEO tunes them); defaults are MXU-aligned multiples of 128.
- Causal / sliding-window block-level skipping is done with ``pl.when`` so
  fully-masked blocks do no FLOPs (the grid point still issues, which is the
  TPU idiom — grids are static).
- GQA is handled in the index maps: the kv head index is ``q_head // group``,
  so no K/V replication ever materializes in HBM or VMEM.
- Heads sit ahead of positions in every block, so each block's last two
  dimensions are (positions, head_dim): the TPU tiling rule wants them
  divisible by (8, 128) or equal to the array's own, and ``head_dim`` is
  always whole.

Layouts: prefill takes q (B, Sq, Hq, D), k/v (B, Skv, Hkv, D) and transposes
them to heads-major inside the wrapper; decode reads the cache as it is
stored, (B, Hkv, S, D), with q viewed as (B, Hkv, G, D).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro import compat

NEG_INF = -1e30
_LANE = 128  # TPU lane width: scratch second-minor stats padded to this


def _pad_to(x: jax.Array, axis: int, mult: int) -> jax.Array:
    size = x.shape[axis]
    rem = size % mult
    if rem == 0:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, mult - rem)
    return jnp.pad(x, pad)


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                 scale: float, causal: bool, sliding_window: int,
                 logit_softcap: float, q_offset: int, kv_valid: int,
                 q_block: int, kv_block: int, n_kv: int):
    iq = pl.program_id(2)
    ikv = pl.program_id(3)

    @pl.when(ikv == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = q_offset + iq * q_block
    kv_start = ikv * kv_block

    # Block-level visibility: skip blocks that are entirely masked.
    visible = kv_start < kv_valid
    if causal:
        visible &= kv_start <= q_start + q_block - 1
    if sliding_window > 0:
        visible &= kv_start + kv_block - 1 > q_start - sliding_window

    @pl.when(visible)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale            # (Qb, D)
        k = k_ref[0, 0].astype(jnp.float32)                    # (Kb, D)
        v = v_ref[0, 0].astype(jnp.float32)                    # (Kb, Dv)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # (Qb, Kb)
        if logit_softcap > 0.0:
            s = logit_softcap * jnp.tanh(s / logit_softcap)

        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (q_block, kv_block), 0)
        k_pos = kv_start + jax.lax.broadcasted_iota(jnp.int32, (q_block, kv_block), 1)
        mask = k_pos < kv_valid
        if causal:
            mask &= k_pos <= q_pos
        if sliding_window > 0:
            mask &= k_pos > q_pos - sliding_window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[:, 0]                                    # (Qb,)
        l_prev = l_ref[:, 0]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur[:, None])
        p = jnp.where(mask, p, 0.0)  # exact zero for masked (handles -inf rows)
        l_cur = alpha * l_prev + jnp.sum(p, axis=-1)
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + pv
        m_ref[...] = jnp.broadcast_to(m_cur[:, None], m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_cur[:, None], l_ref.shape)

    @pl.when(ikv == n_kv - 1)
    def _finalize():
        l = l_ref[:, 0]
        denom = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows -> 0 output
        o_ref[0, 0] = (acc_ref[...] / denom[:, None]).astype(o_ref.dtype)


def flash_attention_pallas(
    q: jax.Array,  # (B, Sq, Hq, D)
    k: jax.Array,  # (B, Skv, Hkv, D)
    v: jax.Array,  # (B, Skv, Hkv, Dv)
    *,
    causal: bool = True,
    sliding_window: int = 0,
    logit_softcap: float = 0.0,
    scale: Optional[float] = None,
    q_offset: int = 0,
    q_block: int = 512,
    kv_block: int = 1024,
    interpret: bool = False,
) -> jax.Array:
    b, sq, hq, d = q.shape
    _, skv, hkv, dv = v.shape
    assert hq % hkv == 0, (hq, hkv)
    g = hq // hkv
    if scale is None:
        scale = d ** -0.5

    q_block = max(8, min(q_block, sq))
    kv_block = max(8, min(kv_block, skv))
    qp = _pad_to(q.transpose(0, 2, 1, 3), 2, q_block)   # (B, Hq, Sq', D)
    kp = _pad_to(k.transpose(0, 2, 1, 3), 2, kv_block)  # (B, Hkv, Skv', D)
    vp = _pad_to(v.transpose(0, 2, 1, 3), 2, kv_block)
    n_q = qp.shape[2] // q_block
    n_kv = kp.shape[2] // kv_block

    kernel = functools.partial(
        _attn_kernel, scale=scale, causal=causal,
        sliding_window=sliding_window, logit_softcap=logit_softcap,
        q_offset=q_offset, kv_valid=skv, q_block=q_block, kv_block=kv_block,
        n_kv=n_kv)

    grid = (b, hq, n_q, n_kv)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, q_block, d), lambda ib, ih, iq, ikv: (ib, ih, iq, 0)),
            pl.BlockSpec((1, 1, kv_block, d), lambda ib, ih, iq, ikv: (ib, ih // g, ikv, 0)),
            pl.BlockSpec((1, 1, kv_block, dv), lambda ib, ih, iq, ikv: (ib, ih // g, ikv, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, q_block, dv), lambda ib, ih, iq, ikv: (ib, ih, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hq, qp.shape[2], dv), q.dtype),
        scratch_shapes=[
            compat.vmem((q_block, dv), jnp.float32),
            compat.vmem((q_block, _LANE), jnp.float32),
            compat.vmem((q_block, _LANE), jnp.float32),
        ],
        compiler_params=compat.tpu_compiler_params(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qp, kp, vp)
    return out[:, :, :sq].transpose(0, 2, 1, 3)


# --------------------------------------------------------------------------
# decode attention (single new token over a KV cache)
# --------------------------------------------------------------------------

def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
                   *, scale: float, sliding_window: int, logit_softcap: float,
                   g: int, kv_block: int, n_kv: int):
    ib = pl.program_id(0)
    ikv = pl.program_id(2)
    cache_len = len_ref[ib]

    @pl.when(ikv == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    kv_start = ikv * kv_block
    visible = kv_start < cache_len
    if sliding_window > 0:
        visible &= kv_start + kv_block - 1 >= cache_len - sliding_window

    @pl.when(visible)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale             # (G, D)
        k = k_ref[0, 0].astype(jnp.float32)                     # (Kb, D)
        v = v_ref[0, 0].astype(jnp.float32)                     # (Kb, Dv)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # (G, Kb)
        if logit_softcap > 0.0:
            s = logit_softcap * jnp.tanh(s / logit_softcap)
        k_pos = kv_start + jax.lax.broadcasted_iota(jnp.int32, (q.shape[0], kv_block), 1)
        mask = k_pos < cache_len
        if sliding_window > 0:
            mask &= k_pos >= cache_len - sliding_window
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

    @pl.when(ikv == n_kv - 1)
    def _finalize():
        l = l_ref[:, 0]
        denom = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / denom[:, None]).astype(o_ref.dtype)


def decode_attention_pallas(
    q: jax.Array,        # (B, 1, Hq, D)
    k_cache: jax.Array,  # (B, Hkv, Skv, D)
    v_cache: jax.Array,  # (B, Hkv, Skv, Dv)
    cache_len: jax.Array,  # (B,) int32 valid entries (incl. the new token)
    *,
    sliding_window: int = 0,
    logit_softcap: float = 0.0,
    scale: Optional[float] = None,
    kv_block: int = 1024,
    interpret: bool = False,
) -> jax.Array:
    b, sq, hq, d = q.shape
    _, hkv, skv, dv = v_cache.shape
    assert sq == 1
    g = hq // hkv
    if scale is None:
        scale = d ** -0.5
    kv_block = max(8, min(kv_block, skv))
    kp = _pad_to(k_cache, 2, kv_block)
    vp = _pad_to(v_cache, 2, kv_block)
    n_kv = kp.shape[2] // kv_block

    kernel = functools.partial(
        _decode_kernel, scale=scale, sliding_window=sliding_window,
        logit_softcap=logit_softcap, g=g, kv_block=kv_block, n_kv=n_kv)

    grid_spec = compat.prefetch_scalar_grid_spec(
        num_scalar_prefetch=1,
        grid=(b, hkv, n_kv),
        in_specs=[
            pl.BlockSpec((1, 1, g, d), lambda ib, ih, ikv, len_ref: (ib, ih, 0, 0)),
            pl.BlockSpec((1, 1, kv_block, d), lambda ib, ih, ikv, len_ref: (ib, ih, ikv, 0)),
            pl.BlockSpec((1, 1, kv_block, dv), lambda ib, ih, ikv, len_ref: (ib, ih, ikv, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, dv), lambda ib, ih, ikv, len_ref: (ib, ih, 0, 0)),
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
    )(cache_len.astype(jnp.int32), q.reshape(b, hkv, g, d), kp, vp)
    return out.reshape(b, 1, hq, dv)
