"""Pure-jnp oracle for flash attention (GQA / causal / sliding window / softcap).

This is the semantic reference the Pallas kernel must match, and also the
implementation used when lowering for XLA cost analysis (the dry-run path),
since it produces honest HLO FLOPs for the attention contraction.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def attention_ref(
    q: jax.Array,  # (B, Sq, Hq, D)
    k: jax.Array,  # (B, Skv, Hkv, D)
    v: jax.Array,  # (B, Skv, Hkv, Dv)
    *,
    causal: bool = True,
    sliding_window: int = 0,
    logit_softcap: float = 0.0,
    scale: Optional[float] = None,
    q_offset: int = 0,
    kv_len: Optional[jax.Array] = None,  # (B,) valid kv length for decode
) -> jax.Array:
    """Grouped-query attention oracle. Returns (B, Sq, Hq, Dv)."""
    b, sq, hq, d = q.shape
    _, skv, hkv, dv = v.shape
    assert hq % hkv == 0, (hq, hkv)
    g = hq // hkv
    if scale is None:
        scale = d ** -0.5

    qf = q.astype(jnp.float32) * scale
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)

    # (B, Hkv, G, Sq, Skv)
    qg = qf.reshape(b, sq, hkv, g, d)
    logits = jnp.einsum("bqhgd,bkhd->bhgqk", qg, kf)
    if logit_softcap > 0.0:
        logits = logit_softcap * jnp.tanh(logits / logit_softcap)

    q_pos = q_offset + jnp.arange(sq)[:, None]  # (Sq, 1)
    k_pos = jnp.arange(skv)[None, :]  # (1, Skv)
    mask = jnp.ones((sq, skv), dtype=bool)
    if causal:
        mask &= k_pos <= q_pos
    if sliding_window > 0:
        mask &= k_pos > q_pos - sliding_window
    mask_b = jnp.broadcast_to(mask, (b, 1, 1, sq, skv))
    if kv_len is not None:
        valid = k_pos < kv_len[:, None]  # (B, Skv)
        mask_b = mask_b & valid[:, None, None, None, :]
    logits = jnp.where(mask_b, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, vf)
    return out.reshape(b, sq, hq, dv).astype(q.dtype)


def attention_blockwise_ref(
    q: jax.Array,  # (B, Sq, Hq, D)
    k: jax.Array,  # (B, Skv, Hkv, D)
    v: jax.Array,  # (B, Skv, Hkv, Dv)
    *,
    causal: bool = True,
    sliding_window: int = 0,
    logit_softcap: float = 0.0,
    scale: Optional[float] = None,
    q_offset: int = 0,
    kv_block: int = 1024,
) -> jax.Array:
    """Online-softmax attention, scanned over kv blocks.

    Mathematically identical to ``attention_ref`` (f32 accumulation), but the
    lowered HLO mirrors the Pallas kernel's streaming structure: the (Sq x
    kv_block) score block is a loop-local temporary instead of a full (Sq x
    Skv) HBM materialization.  This is the implementation the dry-run lowers,
    so the roofline's memory term reflects the TPU kernel, not a CPU oracle.
    """
    b, sq, hq, d = q.shape
    _, skv, hkv, dv = v.shape
    g = hq // hkv
    if scale is None:
        scale = d ** -0.5
    kv_block = max(8, min(kv_block, skv))
    pad = (-skv) % kv_block
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    n_blocks = k.shape[1] // kv_block

    qf = (q.astype(jnp.float32) * scale).reshape(b, sq, hkv, g, d)
    ks = k.astype(jnp.float32).reshape(b, n_blocks, kv_block, hkv, d
                                       ).transpose(1, 0, 2, 3, 4)
    vs = v.astype(jnp.float32).reshape(b, n_blocks, kv_block, hkv, dv
                                       ).transpose(1, 0, 2, 3, 4)
    q_pos = q_offset + jnp.arange(sq)

    def body(carry, xs):
        m, l, acc, blk = carry
        kb, vb = xs
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qf, kb)
        if logit_softcap > 0.0:
            s = logit_softcap * jnp.tanh(s / logit_softcap)
        k_pos = blk * kv_block + jnp.arange(kv_block)
        mask = (k_pos[None, :] < skv)
        if causal:
            mask &= k_pos[None, :] <= q_pos[:, None]
        if sliding_window > 0:
            mask &= k_pos[None, :] > q_pos[:, None] - sliding_window
        s = jnp.where(mask[None, None, None], s, -1e30)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        p = jnp.where(mask[None, None, None], p, 0.0)
        alpha = jnp.exp(m - m_new)
        l_new = alpha * l + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum("bhgqk,bkhd->bhgqd", p, vb)
        return (m_new, l_new, acc_new, blk + 1), None

    m0 = jnp.full((b, hkv, g, sq), -1e30, jnp.float32)
    l0 = jnp.zeros((b, hkv, g, sq), jnp.float32)
    acc0 = jnp.zeros((b, hkv, g, sq, dv), jnp.float32)
    # checkpoint: differentiating through the scan then saves only the
    # (m, l, acc) carries per block and recomputes the (Sq x kv_block)
    # score block in the backward — the flash-attention backward contract
    body = jax.checkpoint(body, prevent_cse=False)
    (m, l, acc, _), _ = jax.lax.scan(body, (m0, l0, acc0, jnp.int32(0)), (ks, vs))
    denom = jnp.where(l == 0.0, 1.0, l)
    out = (acc / denom[..., None]).transpose(0, 3, 1, 2, 4)  # (b, sq, hkv, g, dv)
    return out.reshape(b, sq, hq, dv).astype(q.dtype)


def decode_attention_ref(
    q: jax.Array,      # (B, 1, Hq, D)
    k_cache: jax.Array,  # (B, Hkv, Skv, D) — heads-major, as the cache is stored
    v_cache: jax.Array,  # (B, Hkv, Skv, Dv)
    cache_len: jax.Array,  # (B,) int32 — number of valid entries incl. new one
    *,
    sliding_window: int = 0,
    logit_softcap: float = 0.0,
    scale: Optional[float] = None,
) -> jax.Array:
    """Single-token decode attention over a (possibly ring) KV cache."""
    b, sq, hq, d = q.shape
    _, hkv, skv, dv = v_cache.shape
    g = hq // hkv
    if scale is None:
        scale = d ** -0.5
    qf = q.astype(jnp.float32) * scale
    qg = qf.reshape(b, sq, hkv, g, d)
    logits = jnp.einsum("bqhgd,bhkd->bhgqk", qg, k_cache.astype(jnp.float32))
    if logit_softcap > 0.0:
        logits = logit_softcap * jnp.tanh(logits / logit_softcap)
    k_pos = jnp.arange(skv)[None, :]
    valid = k_pos < cache_len[:, None]
    if sliding_window > 0:
        valid &= k_pos >= (cache_len[:, None] - sliding_window)
    logits = jnp.where(valid[:, None, None, None, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgqk,bhkd->bqhgd", probs, v_cache.astype(jnp.float32))
    return out.reshape(b, sq, hq, dv).astype(q.dtype)
