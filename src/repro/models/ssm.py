"""State-space blocks: Mamba-1 (falcon-mamba) and Mamba-2 (zamba2).

Both expose a sequence form (train/prefill, dispatching to the chunked scan /
SSD kernels) and a recurrent single-step form (decode) with explicit carried
state, so 500k-context decode is O(1) in sequence length.

Projections are kept as *separate* weight matrices (x, z, B, C, dt) rather
than one fused in_proj: the fused layout would slice a tensor-parallel-sharded
dimension at non-shard-aligned offsets (e.g. zamba2's 2*5120+128+80 fused
width over 16 TP shards), forcing XLA to reshard.  Separate matrices give
clean TP: d_inner/heads shard over the model axis, B/C (tiny, per-group) stay
replicated — matching production Mamba TP implementations.
"""

from __future__ import annotations

import math
from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ops
from repro.kernels.rmsnorm.ref import rmsnorm_ref
from repro.models.layers import dense_init, init_rmsnorm
from repro.utils.config import ModelConfig


class MambaState(NamedTuple):
    """Decode state for one mamba block."""
    conv: jax.Array  # (B, K-1, conv_channels) last inputs for causal conv
    ssm: jax.Array   # mamba1: (B, C, N); mamba2: (B, H, N, P)


def _dt_rank(cfg: ModelConfig) -> int:
    return max(1, math.ceil(cfg.d_model / 16))


def _causal_conv_seq(x: jax.Array, w: jax.Array, b: jax.Array) -> jax.Array:
    """Depthwise causal conv1d. x: (B, L, C); w: (K, C); b: (C,)."""
    k = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    out = jnp.zeros(x.shape, dtype=jnp.float32)
    for i in range(k):
        out = out + xp[:, i:i + x.shape[1], :].astype(jnp.float32) * w[i].astype(jnp.float32)
    return (out + b.astype(jnp.float32)).astype(x.dtype)


def _causal_conv_step(state: jax.Array, x_t: jax.Array, w: jax.Array, b: jax.Array):
    """state: (B, K-1, C); x_t: (B, C). Returns (new_state, y_t)."""
    window = jnp.concatenate([state, x_t[:, None, :]], axis=1)  # (B, K, C)
    y = jnp.einsum("bkc,kc->bc", window.astype(jnp.float32), w.astype(jnp.float32))
    y = (y + b.astype(jnp.float32)).astype(x_t.dtype)
    return window[:, 1:], y


def _dt_softplus_init(key, n: int):
    dt_init = jnp.exp(jax.random.uniform(key, (n,), jnp.float32)
                      * (math.log(0.1) - math.log(0.001)) + math.log(0.001))
    return dt_init + jnp.log1p(-jnp.exp(-dt_init))  # inverse softplus


# --------------------------------------------------------------------------
# Mamba-1
# --------------------------------------------------------------------------

def init_mamba1(key, cfg: ModelConfig, dtype) -> Dict:
    d_inner = cfg.ssm_expand * cfg.d_model
    n, rank, k = cfg.ssm_state, _dt_rank(cfg), cfg.ssm_conv
    ks = jax.random.split(key, 7)
    A = -jnp.tile(jnp.arange(1, n + 1, dtype=jnp.float32)[None, :], (d_inner, 1))
    p = {
        "w_x": dense_init(ks[0], cfg.d_model, d_inner, dtype),
        "w_z": dense_init(ks[1], cfg.d_model, d_inner, dtype),
        "conv_w": (jax.random.normal(ks[2], (k, d_inner), jnp.float32)
                   / math.sqrt(k)).astype(dtype),
        "conv_b": jnp.zeros((d_inner,), dtype),
        "w_bcdt": dense_init(ks[3], d_inner, rank + 2 * n, dtype),
        "w_dt": dense_init(ks[4], rank, d_inner, dtype, scale=rank ** -0.5),
        "dt_bias": _dt_softplus_init(ks[5], d_inner),
        "A_log": jnp.log(-A),  # stored as log(-A), fp32
        "D": jnp.ones((d_inner,), jnp.float32),
        "w_out": dense_init(ks[6], d_inner, cfg.d_model, dtype),
    }
    if cfg.ssm_dt_bc_norm:
        p.update(dt_norm=init_rmsnorm(rank, dtype),
                 b_norm=init_rmsnorm(n, dtype), c_norm=init_rmsnorm(n, dtype))
    return p


def _dt_b_c(p: Dict, cfg: ModelConfig, xdbc: jax.Array):
    """x_proj's output split into [dt_low | B | C], each through its weighted
    RMS norm where the config has them (jamba)."""
    n, rank = cfg.ssm_state, _dt_rank(cfg)
    parts = (xdbc[..., :rank], xdbc[..., rank:rank + n], xdbc[..., rank + n:])
    if not cfg.ssm_dt_bc_norm:
        return parts
    # plain XLA, fused with the projection around it: rows of 160 and 16
    # are too narrow for a kernel launch of their own
    return tuple(rmsnorm_ref(x, p[k]["scale"], cfg.norm_eps)
                 for x, k in zip(parts, ("dt_norm", "b_norm", "c_norm")))


def apply_mamba1(p: Dict, cfg: ModelConfig, x: jax.Array,
                 state: Optional[MambaState] = None, decode: bool = False,
                 return_state: bool = False
                 ) -> Tuple[jax.Array, Optional[MambaState]]:
    s = x.shape[1]
    xi = jnp.einsum("bsd,de->bse", x, p["w_x"])
    z = jnp.einsum("bsd,de->bse", x, p["w_z"])
    A = -jnp.exp(p["A_log"])

    if decode:
        assert state is not None and s == 1
        conv_state, y_t = _causal_conv_step(state.conv, xi[:, 0], p["conv_w"], p["conv_b"])
        u = jax.nn.silu(y_t)  # (B, C)
        dt_low, Bc, Cc = _dt_b_c(p, cfg, jnp.einsum("bc,ce->be", u, p["w_bcdt"]))
        dt = jax.nn.softplus(jnp.einsum("br,rc->bc", dt_low, p["w_dt"])
                             + p["dt_bias"][None, :])
        ssm_state, y = ops.selective_scan_step(state.ssm, u, dt, A, Bc, Cc, p["D"])
        y = y * jax.nn.silu(z[:, 0])
        out = jnp.einsum("bc,cd->bd", y, p["w_out"])[:, None, :]
        return out, MambaState(conv_state, ssm_state)

    u = jax.nn.silu(_causal_conv_seq(xi, p["conv_w"], p["conv_b"]))
    dt_low, Bc, Cc = _dt_b_c(p, cfg, jnp.einsum("bsc,ce->bse", u, p["w_bcdt"]))
    dt = jax.nn.softplus(jnp.einsum("bsr,rc->bsc", dt_low, p["w_dt"])
                         + p["dt_bias"][None, None, :])
    new_state = None
    if return_state:
        y, h_final = ops.selective_scan(u, dt, A, Bc, Cc, p["D"],
                                        chunk=cfg.ssm_chunk, return_state=True)
        new_state = MambaState(_conv_tail(xi, cfg.ssm_conv), h_final)
    else:
        y = ops.selective_scan(u, dt, A, Bc, Cc, p["D"], chunk=cfg.ssm_chunk)
    y = y * jax.nn.silu(z)
    out = jnp.einsum("bsc,cd->bsd", y, p["w_out"])
    return out, new_state


def _conv_tail(x: jax.Array, k: int) -> jax.Array:
    """Last k-1 inputs of the sequence, zero-padded on the left — the decode
    conv state after prefilling with `x` (B, S, C)."""
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    return xp[:, xp.shape[1] - (k - 1):, :]


def init_mamba1_state(cfg: ModelConfig, batch: int, dtype) -> MambaState:
    d_inner = cfg.ssm_expand * cfg.d_model
    return MambaState(
        conv=jnp.zeros((batch, cfg.ssm_conv - 1, d_inner), dtype),
        ssm=jnp.zeros((batch, d_inner, cfg.ssm_state), jnp.float32),
    )


# --------------------------------------------------------------------------
# Mamba-2
# --------------------------------------------------------------------------

def _m2_dims(cfg: ModelConfig):
    d_inner = cfg.ssm_expand * cfg.d_model
    heads = cfg.ssm_num_heads
    head_dim = d_inner // heads
    groups = 1
    return d_inner, heads, head_dim, groups


def init_mamba2(key, cfg: ModelConfig, dtype) -> Dict:
    d_inner, heads, head_dim, g = _m2_dims(cfg)
    n, k = cfg.ssm_state, cfg.ssm_conv
    ks = jax.random.split(key, 9)
    return {
        "w_z": dense_init(ks[0], cfg.d_model, d_inner, dtype),
        "w_x": dense_init(ks[1], cfg.d_model, d_inner, dtype),
        "w_B": dense_init(ks[2], cfg.d_model, g * n, dtype),
        "w_C": dense_init(ks[3], cfg.d_model, g * n, dtype),
        "w_dtp": dense_init(ks[4], cfg.d_model, heads, dtype),
        "conv_x_w": (jax.random.normal(ks[5], (k, d_inner), jnp.float32)
                     / math.sqrt(k)).astype(dtype),
        "conv_x_b": jnp.zeros((d_inner,), dtype),
        "conv_bc_w": (jax.random.normal(ks[6], (k, 2 * g * n), jnp.float32)
                      / math.sqrt(k)).astype(dtype),
        "conv_bc_b": jnp.zeros((2 * g * n,), dtype),
        "dt_bias": _dt_softplus_init(ks[7], heads),
        "A_log": jnp.log(jnp.arange(1, heads + 1, dtype=jnp.float32)),
        "D": jnp.ones((heads,), jnp.float32),
        "norm_scale": jnp.ones((d_inner,), dtype),
        "w_out": dense_init(ks[8], d_inner, cfg.d_model, dtype),
    }


def apply_mamba2(p: Dict, cfg: ModelConfig, x: jax.Array,
                 state: Optional[MambaState] = None, decode: bool = False,
                 return_state: bool = False
                 ) -> Tuple[jax.Array, Optional[MambaState]]:
    d_inner, heads, head_dim, g = _m2_dims(cfg)
    n = cfg.ssm_state
    b, s, _ = x.shape
    z = jnp.einsum("bsd,de->bse", x, p["w_z"])
    xi = jnp.einsum("bsd,de->bse", x, p["w_x"])
    bc = jnp.concatenate([jnp.einsum("bsd,de->bse", x, p["w_B"]),
                          jnp.einsum("bsd,de->bse", x, p["w_C"])], axis=-1)
    dt_raw = jnp.einsum("bsd,dh->bsh", x, p["w_dtp"])
    A = -jnp.exp(p["A_log"])

    if decode:
        assert state is not None and s == 1
        cs_x, cs_bc = state.conv[..., :d_inner], state.conv[..., d_inner:]
        cs_x, x_t = _causal_conv_step(cs_x, xi[:, 0], p["conv_x_w"], p["conv_x_b"])
        cs_bc, bc_t = _causal_conv_step(cs_bc, bc[:, 0], p["conv_bc_w"], p["conv_bc_b"])
        x_t = jax.nn.silu(x_t).reshape(b, heads, head_dim)
        bc_t = jax.nn.silu(bc_t)
        Bt = bc_t[..., :g * n].reshape(b, g, n)
        Ct = bc_t[..., g * n:].reshape(b, g, n)
        dt = jax.nn.softplus(dt_raw[:, 0] + p["dt_bias"][None, :])  # (B, H)
        ssm_state, y = ops.ssd_step(state.ssm, x_t, dt, A, Bt, Ct, p["D"])
        y = y.reshape(b, d_inner)
        y = _gated_rmsnorm(y, z[:, 0], p["norm_scale"], cfg.norm_eps)
        out = jnp.einsum("bc,cd->bd", y, p["w_out"])[:, None, :]
        return out, MambaState(jnp.concatenate([cs_x, cs_bc], -1), ssm_state)

    xs_ = jax.nn.silu(_causal_conv_seq(xi, p["conv_x_w"], p["conv_x_b"]))
    bcs = jax.nn.silu(_causal_conv_seq(bc, p["conv_bc_w"], p["conv_bc_b"]))
    xs_ = xs_.reshape(b, s, heads, head_dim)
    Bs = bcs[..., :g * n].reshape(b, s, g, n)
    Cs = bcs[..., g * n:].reshape(b, s, g, n)
    dt = jax.nn.softplus(dt_raw + p["dt_bias"][None, None, :])  # (B, S, H)
    new_state = None
    if return_state:
        y, ssm_final = ops.ssd(xs_, dt, A, Bs, Cs, p["D"], chunk=cfg.ssm_chunk,
                               return_state=True)
        conv_tail = _conv_tail(jnp.concatenate([xi, bc], -1), cfg.ssm_conv)
        new_state = MambaState(conv_tail, ssm_final)
    else:
        y = ops.ssd(xs_, dt, A, Bs, Cs, p["D"], chunk=cfg.ssm_chunk)
    y = y.reshape(b, s, d_inner)
    y = _gated_rmsnorm(y, z, p["norm_scale"], cfg.norm_eps)
    out = jnp.einsum("bsc,cd->bsd", y, p["w_out"])
    return out, new_state


def _gated_rmsnorm(y: jax.Array, z: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    yf = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    var = jnp.mean(jnp.square(yf), axis=-1, keepdims=True)
    return (yf * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)).astype(y.dtype)


def init_mamba2_state(cfg: ModelConfig, batch: int, dtype) -> MambaState:
    d_inner, heads, head_dim, g = _m2_dims(cfg)
    conv_ch = d_inner + 2 * g * cfg.ssm_state
    return MambaState(
        conv=jnp.zeros((batch, cfg.ssm_conv - 1, conv_ch), dtype),
        ssm=jnp.zeros((batch, heads, cfg.ssm_state, head_dim), jnp.float32),
    )
