"""Float32 reference of the Jamba hybrid LM (Lieber et al. 2024,
arXiv:2403.19887; the ``ai21labs/AI21-Jamba2-3B`` config and the
transformers ``JambaForCausalLM`` it names).  Layer ``i`` is attention when
``i % attn_layer_period == attn_layer_offset``, Mamba-1 otherwise, and
every layer is

    h = h + mixer(rmsnorm(h));  h = h + mlp(rmsnorm(h))

The Mamba-1 mixer normalises dt, B and C after the input projection, each
by a weighted RMS norm (``norm_eps``):

    xi = x Wx, z = x Wz, u = silu(conv(xi))
    [dt_low|B|C] = u Wbcdt;  dt_low, B, C = rms(dt_low), rms(B), rms(C)
    dt = softplus(dt_low Wdt + b_dt)
    s_t = exp(dt_t A) * s_{t-1} + (dt_t u_t) B_t;  y_t = s_t C_t + D u_t
    out = (y * silu(z)) Wout

run as the plain recurrence over time.  Attention is causal multi-query
attention with no positional encoding, ``softmax(q k^T / sqrt(head_dim))
v``, computed one block of queries at a time against every key.  The MLP is
``(silu(x Wgate) * (x Wup)) Wdown``.  A final rmsnorm, then the head tied
to the embedding.  The input embedding is multiplied by ``sqrt(d_model)``,
as the program does: with the tied head that is the published model with
its table scaled by ``sqrt(d_model)`` and every logit divided by
``sqrt(d_model)``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from ref_common import F32, HI, causal_conv, embed, mm, rmsnorm, weight

Q_BLOCK = 256       # queries scored at a time: (b, heads, 256, keys) floats


def _is_attention(cfg, i):
    return i % cfg["attn_layer_period"] == cfg["attn_layer_offset"]


def _mamba1(p, x, cfg, quant):
    n, eps = cfg["ssm_state"], cfg["norm_eps"]
    rank = max(1, math.ceil(cfg["d_model"] / 16))
    xi = mm(x, p["w_x"], quant)
    z = mm(x, p["w_z"], quant)
    u = jax.nn.silu(causal_conv(xi, p["conv_w"], p["conv_b"]))
    xdbc = mm(u, p["w_bcdt"], quant)
    dt_low = rmsnorm(xdbc[..., :rank], p["dt_norm"]["scale"], eps)
    Bm = rmsnorm(xdbc[..., rank:rank + n], p["b_norm"]["scale"], eps)
    Cm = rmsnorm(xdbc[..., rank + n:], p["c_norm"]["scale"], eps)
    dt = jax.nn.softplus(mm(dt_low, p["w_dt"], quant)
                         + p["dt_bias"].astype(F32))
    A = -jnp.exp(p["A_log"].astype(F32))                       # (C, N)

    def step(s, inp):
        u_t, dt_t, B_t, C_t = inp                               # (b, C) ...
        s = s * jnp.exp(dt_t[..., None] * A) \
            + (dt_t * u_t)[..., None] * B_t[:, None, :]
        return s, jnp.einsum("bcn,bn->bc", s, C_t, precision=HI)

    b, _, c = u.shape
    tm = lambda a: jnp.moveaxis(a, 1, 0)
    _, y = jax.lax.scan(step, jnp.zeros((b, c, n), F32),
                        (tm(u), tm(dt), tm(Bm), tm(Cm)))
    y = (jnp.moveaxis(y, 0, 1) + u * p["D"].astype(F32)) * jax.nn.silu(z)
    return mm(y, p["w_out"], quant)


def _attention(p, x, cfg, quant):
    b, L, _ = x.shape
    hq, hkv, hd = cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"]
    g = hq // hkv
    q = mm(x, p["wq"], quant).reshape(b, L, hkv, g, hd) * hd ** -0.5
    k = mm(x, p["wk"], quant).reshape(b, L, hkv, hd)
    v = mm(x, p["wv"], quant).reshape(b, L, hkv, hd)
    nb = -(-L // Q_BLOCK)
    qp = jnp.pad(q, ((0, 0), (0, nb * Q_BLOCK - L), (0, 0), (0, 0), (0, 0)))
    blocks = jnp.moveaxis(qp.reshape(b, nb, Q_BLOCK, hkv, g, hd), 1, 0)

    def one(args):
        qb, start = args
        s = jnp.einsum("bqkgd,bskd->bkgqs", qb, k, precision=HI)
        qpos = start + jnp.arange(Q_BLOCK)
        s = jnp.where(jnp.arange(L)[None, :] <= qpos[:, None], s, -jnp.inf)
        return jnp.einsum("bkgqs,bskd->bqkgd", jax.nn.softmax(s, -1), v,
                          precision=HI)

    o = jax.lax.map(one, (blocks, jnp.arange(nb) * Q_BLOCK))
    o = jnp.moveaxis(o, 0, 1).reshape(b, nb * Q_BLOCK, hq * hd)[:, :L]
    return mm(o, p["wo"], quant)


def _mlp(p, x, quant):
    return mm(jax.nn.silu(mm(x, p["w_gate"], quant)) * mm(x, p["w_up"], quant),
              p["w_down"], quant)


@functools.partial(jax.jit, static_argnames=("cfg_items", "attention",
                                             "quant"))
def _layer(h, p, cfg_items, attention, quant):
    cfg = dict(cfg_items)
    eps = cfg["norm_eps"]
    if attention:
        h = h + _attention(p["attn"], rmsnorm(h, p["attn_norm"]["scale"],
                                              eps), cfg, quant)
    else:
        h = h + _mamba1(p["mixer"], rmsnorm(h, p["norm"]["scale"], eps),
                        cfg, quant)
    return h + _mlp(p["mlp"], rmsnorm(h, p["mlp_norm"]["scale"], eps), quant)


def hidden(params, cfg, tokens, quant=None):
    items = tuple(sorted((k, v) for k, v in cfg.items()
                         if isinstance(v, (int, float, str))))
    period = cfg["attn_layer_period"]
    h = embed(params["embed"]["embedding"], tokens)
    for i in range(cfg["num_layers"]):
        p = jax.tree.map(lambda a: a[i // period],
                         params["blocks"][f"sub{i % period}"])
        h = _layer(h, p, items, _is_attention(cfg, i), quant)
    return rmsnorm(h, params["final_norm"]["scale"], cfg["norm_eps"])


def head(params, cfg, quant=None):
    e = params["embed"]
    w = e["lm_head"] if "lm_head" in e else e["embedding"].T
    return weight(w, quant)


# -- counts from the widths ------------------------------------------------

def _dims(cfg):
    d, n, k = cfg["d_model"], cfg["ssm_state"], cfg["ssm_conv"]
    di = cfg["ssm_expand"] * d
    rank = max(1, math.ceil(d / 16))
    return d, n, k, di, rank, cfg["vocab_size"]


def _layers(cfg):
    """(Mamba layers, attention layers)."""
    attn = sum(_is_attention(cfg, i) for i in range(cfg["num_layers"]))
    return cfg["num_layers"] - attn, attn


def _mamba_matmul(cfg):
    d, n, k, di, r, v = _dims(cfg)
    return 2 * d * di + di * (r + 2 * n) + r * di + di * d


def _attn_matmul(cfg):
    d = cfg["d_model"]
    q = cfg["num_heads"] * cfg["head_dim"]
    return 2 * d * q + 2 * d * cfg["num_kv_heads"] * cfg["head_dim"]


def _mlp_matmul(cfg):
    return 3 * cfg["d_model"] * cfg["d_ff"]


def param_count(cfg):
    d, n, k, di, r, v = _dims(cfg)
    mamba, attn = _layers(cfg)
    # norms: before the mixer and the MLP; dt, B and C in the mixer
    mixer = _mamba_matmul(cfg) + k * di + di + di + di * n + di + r + 2 * n
    per_mamba = 2 * d + mixer + _mlp_matmul(cfg)
    per_attn = 2 * d + _attn_matmul(cfg) + _mlp_matmul(cfg)
    head = 0 if cfg.get("tie_embeddings") else d * v
    return v * d + head + mamba * per_mamba + attn * per_attn + d


def _token_flops(cfg):
    """Every projection, the head, and the scan recurrence
    (``kernels/mamba_scan.py``'s count); attention scores are apart."""
    d, n, k, di, r, v = _dims(cfg)
    mamba, attn = _layers(cfg)
    matmul = (mamba * (_mamba_matmul(cfg) + _mlp_matmul(cfg))
              + attn * (_attn_matmul(cfg) + _mlp_matmul(cfg)) + d * v)
    return 2.0 * matmul + mamba * di * (6.0 * n + 3.0)


def _score_flops(cfg):
    """Scores and the weighted sum of values, per query and key, over the
    attention layers (``kernels/paged_attention.py``'s count)."""
    return _layers(cfg)[1] * 4.0 * cfg["num_heads"] * cfg["head_dim"]


def prefill_flops(cfg, seq_len):
    # causal: query t sees t + 1 keys
    return (_token_flops(cfg) * seq_len
            + _score_flops(cfg) * seq_len * (seq_len + 1) / 2)


def decode_flops(cfg, context):
    return _token_flops(cfg) + _score_flops(cfg) * context
