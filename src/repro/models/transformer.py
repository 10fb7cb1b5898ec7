"""Decoder-only LM covering dense / moe / ssm / hybrid / vlm families.

Layers are organized as *super-blocks*: the smallest repeating pattern of
sub-layers (e.g. llama4 = [dense, moe], zamba2 = 5x[mamba2] + [mamba2+shared
attention], jamba = 7x[mamba1+mlp] + [dense] + 6x[mamba1+mlp], vlm =
4x[dense] + [cross]).  Super-block weights are stacked on a
leading axis and iterated with ``jax.lax.scan`` so that 61-layer 671B configs
lower to compact HLO.  Decode threads stacked per-super-block caches through
the same scan.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models import ssm
from repro.models.layers import (
    apply_mlp, apply_rmsnorm, embed_tokens, init_embed, init_mlp,
    init_rmsnorm, lm_logits,
)
from repro.models.moe import apply_moe, init_moe
from repro.utils.config import ModelConfig, ParallelConfig


# --------------------------------------------------------------------------
# super-block patterns
# --------------------------------------------------------------------------

def block_pattern(cfg: ModelConfig) -> List[str]:
    """Sub-layer kinds within one super-block."""
    if cfg.attn_layer_period:
        return ["dense" if i == cfg.attn_layer_offset else "mamba1"
                for i in range(cfg.attn_layer_period)]
    if cfg.family == "ssm":
        return ["mamba1"]
    if cfg.family == "hybrid":
        period = cfg.hybrid_attn_period or 6
        return ["mamba2"] * (period - 1) + ["mamba2_shared_attn"]
    if cfg.family == "vlm":
        period = cfg.cross_attn_period or 5
        return ["dense"] * (period - 1) + ["cross"]
    if cfg.is_moe:
        if cfg.moe_layer_period > 1:
            return ["dense"] * (cfg.moe_layer_period - 1) + ["moe"]
        return ["moe"]
    return ["dense"]


def num_superblocks(cfg: ModelConfig) -> int:
    pat = len(block_pattern(cfg))
    assert cfg.num_layers % pat == 0, (cfg.num_layers, pat)
    return cfg.num_layers // pat


# --------------------------------------------------------------------------
# init
# --------------------------------------------------------------------------

def _init_attn(key, cfg: ModelConfig, dtype) -> Dict:
    if cfg.attn_type == "mla":
        return attn.init_mla(key, cfg, dtype)
    return attn.init_gqa(key, cfg, dtype)


def _init_sublayer(key, cfg: ModelConfig, kind: str, dtype) -> Dict:
    ks = jax.random.split(key, 4)
    if kind == "mamba1":
        p = {"norm": init_rmsnorm(cfg.d_model, dtype),
             "mixer": ssm.init_mamba1(ks[0], cfg, dtype)}
        if cfg.d_ff:
            p["mlp_norm"] = init_rmsnorm(cfg.d_model, dtype)
            p["mlp"] = init_mlp(ks[1], cfg.d_model, cfg.d_ff, cfg.mlp_type,
                                dtype)
        return p
    if kind in ("mamba2", "mamba2_shared_attn"):
        return {"norm": init_rmsnorm(cfg.d_model, dtype),
                "mixer": ssm.init_mamba2(ks[0], cfg, dtype)}
    p = {
        "attn_norm": init_rmsnorm(cfg.d_model, dtype),
        "attn": _init_attn(ks[0], cfg, dtype),
        "mlp_norm": init_rmsnorm(cfg.d_model, dtype),
    }
    if kind == "moe":
        p["moe"] = init_moe(ks[1], cfg, dtype)
    else:
        p["mlp"] = init_mlp(ks[1], cfg.d_model, cfg.d_ff, cfg.mlp_type, dtype)
    if kind == "cross":
        p["cross_norm"] = init_rmsnorm(cfg.d_model, dtype)
        p["cross"] = attn.init_cross_attn(ks[2], cfg, cfg.vision_dim or cfg.d_model, dtype)
        p["cross_gate"] = jnp.zeros((), dtype)  # gated cross-attn (llama3.2-v)
    return p


def init_lm_params(cfg: ModelConfig, key: jax.Array, dtype) -> Dict[str, Any]:
    pat = block_pattern(cfg)
    nsb = num_superblocks(cfg)
    k_embed, k_blocks, k_shared, k_mtp = jax.random.split(key, 4)

    def init_superblock(k):
        sub_keys = jax.random.split(k, len(pat))
        return {f"sub{i}": _init_sublayer(sub_keys[i], cfg, kind, dtype)
                for i, kind in enumerate(pat)}

    params: Dict[str, Any] = {
        "embed": init_embed(k_embed, cfg.vocab_size, cfg.d_model, dtype, cfg.tie_embeddings),
        "blocks": jax.vmap(init_superblock)(jax.random.split(k_blocks, nsb)),
        "final_norm": init_rmsnorm(cfg.d_model, dtype),
    }
    if "mamba2_shared_attn" in pat:
        ks = jax.random.split(k_shared, 2)
        params["shared_attn"] = {
            "norm": init_rmsnorm(cfg.d_model, dtype),
            "attn": attn.init_gqa(ks[0], cfg, dtype),
            "mlp_norm": init_rmsnorm(cfg.d_model, dtype),
            "mlp": init_mlp(ks[1], cfg.d_model, cfg.d_ff, cfg.mlp_type, dtype),
        }
    if cfg.mtp_depth > 0:
        ks = jax.random.split(k_mtp, 2)
        params["mtp"] = {
            "proj": jax.random.normal(ks[0], (2 * cfg.d_model, cfg.d_model), jnp.float32
                                      ).astype(dtype) * (2 * cfg.d_model) ** -0.5,
            "block": _init_sublayer(ks[1], cfg, "dense" if not cfg.is_moe else "moe", dtype),
            "norm": init_rmsnorm(cfg.d_model, dtype),
        }
    return params


# --------------------------------------------------------------------------
# caches / decode state
# --------------------------------------------------------------------------

def init_decode_state(cfg: ModelConfig, batch: int, max_len: int, dtype) -> Dict:
    """Stacked per-super-block decode state matching the scan layout."""
    pat = block_pattern(cfg)
    nsb = num_superblocks(cfg)

    def one_sub(kind):
        if kind == "mamba1":
            return ssm.init_mamba1_state(cfg, batch, dtype)
        if kind in ("mamba2", "mamba2_shared_attn"):
            st = {"mixer": ssm.init_mamba2_state(cfg, batch, dtype)}
            if kind == "mamba2_shared_attn":
                st["shared_kv"] = attn.init_kv_cache(cfg, batch, max_len, dtype)
            return st
        if cfg.attn_type == "mla":
            return attn.init_mla_cache(cfg, batch, max_len, dtype)
        return attn.init_kv_cache(cfg, batch, max_len, dtype)

    def one_block(_):
        return {f"sub{i}": one_sub(kind) for i, kind in enumerate(pat)}

    # stack over super-blocks via tree_map on a template
    template = one_block(None)
    return jax.tree.map(lambda x: jnp.zeros((nsb,) + x.shape, x.dtype), template)


def init_paged_decode_state(cfg: ModelConfig, batch: int, pool_pages: int,
                            page_size: int, pages_per_slot_max: int,
                            dtype) -> Dict:
    """Paged variant of :func:`init_decode_state`: every attention KV cache
    becomes a :class:`~repro.models.attention.PagedKVCache` over a per-layer
    ``pool_pages``-page pool; recurrent SSM states (O(1) per slot) are
    unchanged.  The compiled decode shape is ``(pool_pages, page_size)`` —
    independent of any per-request context length, which is the point of the
    paged refactor."""
    if cfg.attn_type == "mla":
        raise NotImplementedError(
            "paged serving does not support the MLA compressed cache yet; "
            "serve MLA models dense")
    pat = block_pattern(cfg)
    nsb = num_superblocks(cfg)

    def paged_kv():
        return attn.init_paged_kv_cache(cfg, batch, pool_pages, page_size,
                                        pages_per_slot_max, dtype)

    def one_sub(kind):
        if kind == "mamba1":
            return ssm.init_mamba1_state(cfg, batch, dtype)
        if kind in ("mamba2", "mamba2_shared_attn"):
            st = {"mixer": ssm.init_mamba2_state(cfg, batch, dtype)}
            if kind == "mamba2_shared_attn":
                st["shared_kv"] = paged_kv()
            return st
        return paged_kv()

    template = {f"sub{i}": one_sub(kind) for i, kind in enumerate(pat)}
    # broadcast (not zeros): page tables start on the scratch page, a
    # non-zero index the template already carries
    return jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (nsb,) + x.shape).astype(x.dtype),
        template)


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------

def _apply_sublayer(sub_p, cfg, par, kind, h, positions, shared_p, vision_kv,
                    cache, decode):
    """Returns (h, new_cache, aux).

    ``cache`` may be present in two modes: decode (single-token recurrent
    step) and prefill (full sequence forward that also fills the cache /
    computes the final recurrent state).
    """
    aux = jnp.zeros((), jnp.float32)
    new_cache = cache
    use_cache = cache is not None
    if kind == "mamba1":
        y, st = ssm.apply_mamba1(sub_p["mixer"], cfg,
                                 apply_rmsnorm(sub_p["norm"], h, cfg.norm_eps),
                                 state=cache if decode else None, decode=decode,
                                 return_state=use_cache and not decode)
        h = h + y
        if cfg.d_ff:
            h = h + apply_mlp(sub_p["mlp"],
                              apply_rmsnorm(sub_p["mlp_norm"], h, cfg.norm_eps),
                              cfg.mlp_type)
        return h, (st if use_cache else cache), aux
    if kind in ("mamba2", "mamba2_shared_attn"):
        mixer_cache = cache["mixer"] if (decode and isinstance(cache, dict)) else None
        y, st = ssm.apply_mamba2(sub_p["mixer"], cfg,
                                 apply_rmsnorm(sub_p["norm"], h, cfg.norm_eps),
                                 state=mixer_cache, decode=decode,
                                 return_state=use_cache and not decode)
        h = h + y
        if kind == "mamba2_shared_attn":
            kv = cache["shared_kv"] if isinstance(cache, dict) else None
            y2, kv2 = attn.apply_gqa(shared_p["attn"], cfg, par,
                                     apply_rmsnorm(shared_p["norm"], h, cfg.norm_eps),
                                     positions, cache=kv, decode=decode)
            h = h + y2
            h = h + apply_mlp(shared_p["mlp"],
                              apply_rmsnorm(shared_p["mlp_norm"], h, cfg.norm_eps),
                              cfg.mlp_type)
            if use_cache:
                new_cache = {"mixer": st, "shared_kv": kv2}
        elif use_cache:
            new_cache = {"mixer": st}
        return h, new_cache, aux

    # attention + (mlp | moe) [+ cross]
    hn = apply_rmsnorm(sub_p["attn_norm"], h, cfg.norm_eps)
    if cfg.attn_type == "mla":
        y, kv = attn.apply_mla(sub_p["attn"], cfg, par, hn, positions,
                               cache=cache, decode=decode)
    else:
        y, kv = attn.apply_gqa(sub_p["attn"], cfg, par, hn, positions,
                               cache=cache, decode=decode)
    h = h + y
    if use_cache:
        new_cache = kv
    if kind == "cross":
        hc = apply_rmsnorm(sub_p["cross_norm"], h, cfg.norm_eps)
        yc = attn.apply_cross_attn(sub_p["cross"], cfg, par, hc, vision_kv)
        h = h + jnp.tanh(sub_p["cross_gate"]) * yc
    hm = apply_rmsnorm(sub_p["mlp_norm"], h, cfg.norm_eps)
    if kind == "moe":
        y, aux = apply_moe(sub_p["moe"], cfg, hm, router_mode=cfg.moe_router,
                           group_size=par.moe_group_size, dropless=decode)
        h = h + y
    else:
        h = h + apply_mlp(sub_p["mlp"], hm, cfg.mlp_type)
    return h, new_cache, aux


def forward(
    params: Dict,
    cfg: ModelConfig,
    par: ParallelConfig,
    tokens: jax.Array,           # (B, S) int32
    *,
    positions: Optional[jax.Array] = None,
    vision_embeds: Optional[jax.Array] = None,  # (B, T, D_v) for vlm
    decode_state: Optional[Dict] = None,
    decode: bool = False,
    return_hidden: bool = False,
) -> Tuple[jax.Array, Optional[Dict], jax.Array]:
    """Returns (logits, new_decode_state, aux_loss)."""
    pat = block_pattern(cfg)
    b, s = tokens.shape
    if positions is None:
        positions = jnp.arange(s)
    h = embed_tokens(params["embed"], tokens, cfg.d_model)
    h = _shard_act(h, par)
    shared_p = params.get("shared_attn")
    use_cache = decode_state is not None

    def body(carry, xs):
        h, aux = carry
        if use_cache:
            block_p, block_cache = xs
        else:
            block_p, block_cache = xs, None
        new_caches = {}
        for i, kind in enumerate(pat):
            cache_i = block_cache[f"sub{i}"] if block_cache is not None else None
            h, nc, a = _apply_sublayer(block_p[f"sub{i}"], cfg, par, kind, h,
                                       positions, shared_p, vision_embeds,
                                       cache_i, decode)
            h = _shard_act(h, par)
            new_caches[f"sub{i}"] = nc
            aux = aux + a
        return (h, aux), (new_caches if use_cache else None)

    body_fn = body
    if par.remat != "none" and not decode:
        policy = None
        if par.remat == "dots":
            policy = jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
        body_fn = jax.checkpoint(body, policy=policy, prevent_cse=False)

    aux0 = jnp.zeros((), jnp.float32)
    if par.scan_layers:
        xs = (params["blocks"], decode_state) if use_cache else params["blocks"]
        (h, aux), new_state = jax.lax.scan(body_fn, (h, aux0), xs)
    else:
        nsb = num_superblocks(cfg)
        new_list = []
        carry = (h, aux0)
        for i in range(nsb):
            block_p = jax.tree.map(lambda x: x[i], params["blocks"])
            if use_cache:
                cache_i = jax.tree.map(lambda x: x[i], decode_state)
                carry, nc = body_fn(carry, (block_p, cache_i))
                new_list.append(nc)
            else:
                carry, _ = body_fn(carry, block_p)
        h, aux = carry
        new_state = (jax.tree.map(lambda *xs: jnp.stack(xs), *new_list)
                     if use_cache else None)

    h = apply_rmsnorm(params["final_norm"], h, cfg.norm_eps)
    if return_hidden:
        return h, new_state, aux
    logits = lm_logits(params["embed"], h)
    return logits, new_state, aux


def _shard_act(h: jax.Array, par: ParallelConfig) -> jax.Array:
    """Activation sharding constraint (batch over data, optional SP over seq)."""
    from repro.sharding.specs import activation_sharding
    return activation_sharding(h, par)


# --------------------------------------------------------------------------
# MTP head (deepseek multi-token prediction)
# --------------------------------------------------------------------------

def mtp_logits(params: Dict, cfg: ModelConfig, par: ParallelConfig,
               h: jax.Array, tokens: jax.Array, positions: jax.Array) -> jax.Array:
    """Predict token t+2 from hidden t combined with embedding of token t+1."""
    mtp = params["mtp"]
    emb_next = embed_tokens(params["embed"], tokens, cfg.d_model)  # embeds of t+1
    hh = jnp.concatenate([h, emb_next], axis=-1)
    hh = jnp.einsum("bsd,de->bse", hh, mtp["proj"])
    kind = "moe" if "moe" in mtp["block"] else "dense"
    hh, _, _ = _apply_sublayer(mtp["block"], cfg, par, kind, hh, positions,
                               None, None, None, False)
    hh = apply_rmsnorm(mtp["norm"], hh, cfg.norm_eps)
    return lm_logits(params["embed"], hh)
