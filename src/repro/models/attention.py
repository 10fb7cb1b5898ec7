"""Attention variants: GQA (with optional sliding window / softcap), MLA
(DeepSeek latent attention with compressed KV cache), and cross-attention.

All functions are functional (params dict in, activations out) and carry an
optional KV cache for decode.  The inner attention contraction dispatches to
``kernels.ops`` (Pallas on TPU, jnp oracle elsewhere).
"""

from __future__ import annotations

from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ops
from repro.models.layers import apply_rope, dense_init
from repro.utils.config import ModelConfig, ParallelConfig


class KVCache(NamedTuple):
    """Append (or sliding-window ring) cache, stored heads-major as the decode
    kernel reads it: k/v (B, H_kv, S_max, D); length: (B,) int32."""
    k: jax.Array
    v: jax.Array
    length: jax.Array


class PagedKVCache(NamedTuple):
    """Block-paged KV cache over a shared page pool.

    ``k_pages``/``v_pages``: ``(P, H_kv, page_size, D)`` — the pool, shared by
    every slot of the batch, stored in the layout the paged kernel reads.  ``page_table``: ``(B, pages_per_slot_max)``
    int32 — token ``t`` of slot ``b`` lives at pool page
    ``page_table[b, t // page_size]``, row ``t % page_size``.  Unused table
    entries must still hold *valid* pool indices (the attention mask from
    ``length`` makes their contents irrelevant).  ``length``: ``(B,)`` int32.

    With a single pool page per slot and ``page_size == cache_len`` the
    gathered layout IS the dense :class:`KVCache` — the dense-equivalence
    anchor the paged serving stack is tested against.
    """
    k_pages: jax.Array
    v_pages: jax.Array
    page_table: jax.Array
    length: jax.Array


class MLACache(NamedTuple):
    """DeepSeek MLA compressed cache: latent c_kv + rope key."""
    c_kv: jax.Array  # (B, S_max, kv_lora_rank)
    k_pe: jax.Array  # (B, S_max, qk_rope_head_dim)
    length: jax.Array


# --------------------------------------------------------------------------
# GQA
# --------------------------------------------------------------------------

def init_gqa(key, cfg: ModelConfig, dtype) -> Dict:
    hd = cfg.head_dim
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {
        "wq": dense_init(k1, cfg.d_model, cfg.num_heads * hd, dtype),
        "wk": dense_init(k2, cfg.d_model, cfg.num_kv_heads * hd, dtype),
        "wv": dense_init(k3, cfg.d_model, cfg.num_kv_heads * hd, dtype),
        "wo": dense_init(k4, cfg.num_heads * hd, cfg.d_model, dtype),
    }


def apply_gqa(
    p: Dict,
    cfg: ModelConfig,
    par: ParallelConfig,
    x: jax.Array,  # (B, S, D)
    positions: jax.Array,  # (S,)
    cache: Optional[KVCache] = None,
    decode: bool = False,
) -> Tuple[jax.Array, Optional[KVCache]]:
    b, s, _ = x.shape
    hd = cfg.head_dim
    q = jnp.einsum("bsd,de->bse", x, p["wq"]).reshape(b, s, cfg.num_heads, hd)
    k = jnp.einsum("bsd,de->bse", x, p["wk"]).reshape(b, s, cfg.num_kv_heads, hd)
    v = jnp.einsum("bsd,de->bse", x, p["wv"]).reshape(b, s, cfg.num_kv_heads, hd)
    if cfg.rope_theta:  # 0: no positional encoding (jamba)
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)

    if decode and isinstance(cache, PagedKVCache):
        assert s == 1
        if cfg.sliding_window > 0:
            raise NotImplementedError(
                "paged KV cache does not support sliding-window attention "
                "(the ring layout and the page layout disagree about where "
                "token t lives); serve sliding-window models dense")
        ps = cache.k_pages.shape[2]
        rows = jnp.arange(b)
        page_ids = cache.page_table[rows, cache.length // ps]  # (B,)
        row_ids = cache.length % ps                            # (B,)
        # (B,) page x (B,) row around the head slice: a (B, Hkv, D) write
        k_pages = cache.k_pages.at[page_ids, :, row_ids].set(k[:, 0])
        v_pages = cache.v_pages.at[page_ids, :, row_ids].set(v[:, 0])
        new_len = cache.length + 1
        o = ops.paged_decode_attention(
            q, k_pages, v_pages, cache.page_table, new_len,
            logit_softcap=cfg.attn_logit_softcap)
        new_cache = PagedKVCache(k_pages, v_pages, cache.page_table, new_len)
    elif decode:
        assert cache is not None and s == 1
        size = cache.k.shape[2]
        ring = cfg.sliding_window > 0 and size <= cfg.sliding_window
        idx = cache.length % size if ring else cache.length  # (B,)
        k_cache = _scatter_time(cache.k, k, idx)
        v_cache = _scatter_time(cache.v, v, idx)
        new_len = cache.length + 1
        # Ring cache holds exactly the window -> validity mask suffices; the
        # window mask is only needed when the cache is longer than the window.
        attn_len = jnp.minimum(new_len, size) if ring else new_len
        window = 0 if ring else cfg.sliding_window
        o = ops.decode_attention(
            q, k_cache, v_cache, attn_len,
            sliding_window=window, logit_softcap=cfg.attn_logit_softcap,
            kv_block=par.attn_kv_block)
        new_cache = KVCache(k_cache, v_cache, new_len)
    else:
        if isinstance(cache, PagedKVCache):
            # prefill runs dense (batch-1, one compiled program) and the
            # batcher scatters the filled rows into the slot's pages — see
            # repro.serving.scheduler._scatter_paged_rows
            raise NotImplementedError(
                "prefill directly into a paged cache is not supported; "
                "prefill dense and scatter the rows into pages")
        o = ops.flash_attention(
            q, k, v, causal=True, sliding_window=cfg.sliding_window,
            logit_softcap=cfg.attn_logit_softcap,
            q_block=par.attn_q_block, kv_block=par.attn_kv_block)
        new_cache = None
        if cache is not None:  # prefill into the heads-major cache
            size = cache.k.shape[2]
            kt, vt = k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)
            if s <= size:
                k_cache = jax.lax.dynamic_update_slice(cache.k, kt, (0, 0, 0, 0))
                v_cache = jax.lax.dynamic_update_slice(cache.v, vt, (0, 0, 0, 0))
            else:
                # ring cache smaller than the prompt (sliding window): pack
                # the last `size` keys at their ring slots (pos % size)
                j = jnp.arange(size)
                tok = s - size + ((j - s) % size)
                k_cache, v_cache = kt[:, :, tok], vt[:, :, tok]
            new_cache = KVCache(k_cache, v_cache, cache.length + s)
    out = jnp.einsum("bse,ed->bsd", o.reshape(b, s, cfg.num_heads * hd), p["wo"])
    return out, new_cache


def _scatter_time(cache: jax.Array, new: jax.Array, idx: jax.Array) -> jax.Array:
    """Write `new` (B, 1, H, D) at per-batch time index `idx` (B,) of a
    heads-major (B, H, S, D) cache."""
    onehot = jax.nn.one_hot(idx, cache.shape[2], dtype=cache.dtype)  # (B, S)
    oh = onehot[:, None, :, None]
    return cache * (1 - oh) + oh * new.transpose(0, 2, 1, 3)


def init_paged_kv_cache(cfg: ModelConfig, batch: int, pool_pages: int,
                        page_size: int, pages_per_slot_max: int,
                        dtype) -> PagedKVCache:
    """Paged cache with ``pool_pages`` allocatable pages plus one *scratch*
    page (index ``pool_pages``).  Every table entry starts on the scratch
    page, and the scheduler points freed slots back at it: an empty slot's
    decode step still scatters its pad-token K/V (exactly like the dense
    batcher writes into its own unused rows), so empty slots must land on a
    page no live request owns — otherwise they corrupt it."""
    hd = cfg.head_dim
    if cfg.sliding_window > 0:
        raise NotImplementedError(
            "paged KV cache does not support sliding-window attention")
    return PagedKVCache(
        k_pages=jnp.zeros((pool_pages + 1, cfg.num_kv_heads, page_size, hd),
                          dtype),
        v_pages=jnp.zeros((pool_pages + 1, cfg.num_kv_heads, page_size, hd),
                          dtype),
        page_table=jnp.full((batch, pages_per_slot_max), pool_pages,
                            jnp.int32),
        length=jnp.zeros((batch,), jnp.int32),
    )


def init_kv_cache(cfg: ModelConfig, batch: int, max_len: int, dtype) -> KVCache:
    hd = cfg.head_dim
    if cfg.sliding_window > 0:
        # ring buffer: the cache never needs to exceed the attention window
        max_len = min(max_len, cfg.sliding_window)
    return KVCache(
        k=jnp.zeros((batch, cfg.num_kv_heads, max_len, hd), dtype),
        v=jnp.zeros((batch, cfg.num_kv_heads, max_len, hd), dtype),
        length=jnp.zeros((batch,), jnp.int32),
    )


# --------------------------------------------------------------------------
# MLA (DeepSeek-V3 multi-head latent attention)
# --------------------------------------------------------------------------

def init_mla(key, cfg: ModelConfig, dtype) -> Dict:
    ks = jax.random.split(key, 6)
    qk_dim = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    return {
        "w_dq": dense_init(ks[0], cfg.d_model, cfg.q_lora_rank, dtype),
        "w_uq": dense_init(ks[1], cfg.q_lora_rank, cfg.num_heads * qk_dim, dtype),
        "w_dkv": dense_init(ks[2], cfg.d_model,
                            cfg.kv_lora_rank + cfg.qk_rope_head_dim, dtype),
        "w_uk": dense_init(ks[3], cfg.kv_lora_rank,
                           cfg.num_heads * cfg.qk_nope_head_dim, dtype),
        "w_uv": dense_init(ks[4], cfg.kv_lora_rank,
                           cfg.num_heads * cfg.v_head_dim, dtype),
        "wo": dense_init(ks[5], cfg.num_heads * cfg.v_head_dim, cfg.d_model, dtype),
    }


def apply_mla(
    p: Dict,
    cfg: ModelConfig,
    par: ParallelConfig,
    x: jax.Array,
    positions: jax.Array,
    cache: Optional[MLACache] = None,
    decode: bool = False,
) -> Tuple[jax.Array, Optional[MLACache]]:
    b, s, _ = x.shape
    h, dn, dr, dv = cfg.num_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim

    cq = jnp.einsum("bsd,dr->bsr", x, p["w_dq"])
    q = jnp.einsum("bsr,re->bse", cq, p["w_uq"]).reshape(b, s, h, dn + dr)
    q_nope, q_pe = q[..., :dn], q[..., dn:]
    q_pe = apply_rope(q_pe, positions, cfg.rope_theta)

    ckv_full = jnp.einsum("bsd,dr->bsr", x, p["w_dkv"])
    c_kv, k_pe_flat = ckv_full[..., :cfg.kv_lora_rank], ckv_full[..., cfg.kv_lora_rank:]
    k_pe = apply_rope(k_pe_flat[:, :, None, :], positions, cfg.rope_theta)[:, :, 0, :]

    if decode:
        assert cache is not None and s == 1
        idx = cache.length
        onehot = jax.nn.one_hot(idx, cache.c_kv.shape[1], dtype=c_kv.dtype)
        c_cache = cache.c_kv * (1 - onehot)[..., None] + onehot[..., None] * c_kv
        pe_cache = cache.k_pe * (1 - onehot)[..., None] + onehot[..., None] * k_pe
        new_len = cache.length + 1
        # absorbed attention: score = q_nope^T W_uk c + q_pe^T k_pe
        q_lat = jnp.einsum("bshn,rhn->bshr", q_nope.reshape(b, s, h, dn),
                           p["w_uk"].reshape(cfg.kv_lora_rank, h, dn))
        scale = (dn + dr) ** -0.5
        logits = (jnp.einsum("bshr,btr->bhst", q_lat, c_cache)
                  + jnp.einsum("bshr,btr->bhst", q_pe, pe_cache)) * scale
        t_pos = jnp.arange(c_cache.shape[1])[None, :]
        valid = t_pos < new_len[:, None]
        logits = jnp.where(valid[:, None, None, :], logits, -1e30)
        probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1).astype(x.dtype)
        o_lat = jnp.einsum("bhst,btr->bshr", probs, c_cache)  # (B,1,H,rank)
        o = jnp.einsum("bshr,rhv->bshv", o_lat, p["w_uv"].reshape(cfg.kv_lora_rank, h, dv))
        new_cache = MLACache(c_cache, pe_cache, new_len)
    else:
        k_nope = jnp.einsum("bsr,re->bse", c_kv, p["w_uk"]).reshape(b, s, h, dn)
        vfull = jnp.einsum("bsr,re->bse", c_kv, p["w_uv"]).reshape(b, s, h, dv)
        k = jnp.concatenate([k_nope, jnp.broadcast_to(k_pe[:, :, None, :], (b, s, h, dr))], -1)
        q_cat = jnp.concatenate([q_nope, q_pe], -1)
        o = ops.flash_attention(q_cat, k, vfull, causal=True,
                                q_block=par.attn_q_block, kv_block=par.attn_kv_block)
        new_cache = None
        if cache is not None:
            c_cache = jax.lax.dynamic_update_slice(cache.c_kv, c_kv, (0, 0, 0))
            pe_cache = jax.lax.dynamic_update_slice(cache.k_pe, k_pe, (0, 0, 0))
            new_cache = MLACache(c_cache, pe_cache, cache.length + s)
    out = jnp.einsum("bse,ed->bsd", o.reshape(b, s, h * dv), p["wo"])
    return out, new_cache


def init_mla_cache(cfg: ModelConfig, batch: int, max_len: int, dtype) -> MLACache:
    return MLACache(
        c_kv=jnp.zeros((batch, max_len, cfg.kv_lora_rank), dtype),
        k_pe=jnp.zeros((batch, max_len, cfg.qk_rope_head_dim), dtype),
        length=jnp.zeros((batch,), jnp.int32),
    )


# --------------------------------------------------------------------------
# Cross-attention (VLM image layers / enc-dec)
# --------------------------------------------------------------------------

def init_cross_attn(key, cfg: ModelConfig, kv_dim: int, dtype) -> Dict:
    hd = cfg.head_dim
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {
        "wq": dense_init(k1, cfg.d_model, cfg.num_heads * hd, dtype),
        "wk": dense_init(k2, kv_dim, cfg.num_kv_heads * hd, dtype),
        "wv": dense_init(k3, kv_dim, cfg.num_kv_heads * hd, dtype),
        "wo": dense_init(k4, cfg.num_heads * hd, cfg.d_model, dtype),
    }


def apply_cross_attn(p: Dict, cfg: ModelConfig, par: ParallelConfig,
                     x: jax.Array, kv_src: jax.Array) -> jax.Array:
    """x: (B, S, D); kv_src: (B, T, D_kv) — no causal mask, no rope on kv."""
    b, s, _ = x.shape
    t = kv_src.shape[1]
    hd = cfg.head_dim
    q = jnp.einsum("bsd,de->bse", x, p["wq"]).reshape(b, s, cfg.num_heads, hd)
    k = jnp.einsum("btd,de->bte", kv_src, p["wk"]).reshape(b, t, cfg.num_kv_heads, hd)
    v = jnp.einsum("btd,de->bte", kv_src, p["wv"]).reshape(b, t, cfg.num_kv_heads, hd)
    o = ops.flash_attention(q, k, v, causal=False,
                            q_block=par.attn_q_block, kv_block=par.attn_kv_block)
    return jnp.einsum("bse,ed->bsd", o.reshape(b, s, cfg.num_heads * hd), p["wo"])
