"""Float32 reference of the attention-free Mamba-1 LM (Gu & Dao 2023,
arXiv:2312.00752; the ``state-spaces/mamba-*`` models): per layer
``x + mamba1(rmsnorm(x))``, with

    xi = x Wx, z = x Wz, u = silu(conv(xi))
    [dt_low|B|C] = u Wbcdt, dt = softplus(dt_low Wdt + b_dt)
    s_t = exp(dt_t A) * s_{t-1} + (dt_t u_t) B_t;  y_t = s_t C_t + D u_t
    out = (y * silu(z)) Wout

run as the plain recurrence over time, then a final rmsnorm and the head
tied to the embedding.  The input embedding is multiplied by
``sqrt(d_model)``, as the program does: with the tied head that is the
published model with its table scaled by ``sqrt(d_model)`` and every logit
divided by ``sqrt(d_model)``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from ref_common import F32, HI, causal_conv, embed, mm, rmsnorm, weight


def _mamba1(p, x, cfg, quant):
    n = cfg["ssm_state"]
    rank = max(1, math.ceil(cfg["d_model"] / 16))
    xi = mm(x, p["w_x"], quant)
    z = mm(x, p["w_z"], quant)
    u = jax.nn.silu(causal_conv(xi, p["conv_w"], p["conv_b"]))
    xdbc = mm(u, p["w_bcdt"], quant)
    dt_low, Bm, Cm = xdbc[..., :rank], xdbc[..., rank:rank + n], \
        xdbc[..., rank + n:]
    dt = jax.nn.softplus(mm(dt_low, p["w_dt"], quant)
                         + p["dt_bias"].astype(F32))
    A = -jnp.exp(p["A_log"].astype(F32))                       # (C, N)

    def step(s, inp):
        u_t, dt_t, B_t, C_t = inp                               # (b, C) ...
        s = s * jnp.exp(dt_t[..., None] * A) \
            + (dt_t * u_t)[..., None] * B_t[:, None, :]
        y = jnp.einsum("bcn,bn->bc", s, C_t, precision=HI)
        return s, y

    b, _, c = u.shape
    tm = lambda a: jnp.moveaxis(a, 1, 0)
    _, y = jax.lax.scan(step, jnp.zeros((b, c, n), F32),
                        (tm(u), tm(dt), tm(Bm), tm(Cm)))
    y = (jnp.moveaxis(y, 0, 1) + u * p["D"].astype(F32)) * jax.nn.silu(z)
    return mm(y, p["w_out"], quant)


@functools.partial(jax.jit, static_argnames=("cfg_items", "quant"))
def _layer(h, p, cfg_items, quant):
    cfg = dict(cfg_items)
    p = p["sub0"]
    return h + _mamba1(p["mixer"], rmsnorm(h, p["norm"]["scale"],
                                           cfg["norm_eps"]), cfg, quant)


def hidden(params, cfg, tokens, quant=None):
    items = tuple(sorted((k, v) for k, v in cfg.items()
                         if isinstance(v, (int, float, str))))
    h = embed(params["embed"]["embedding"], tokens)
    for i in range(cfg["num_layers"]):
        h = _layer(h, jax.tree.map(lambda a: a[i], params["blocks"]), items,
                   quant)
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


def _layer_matmul(cfg):
    d, n, k, di, r, v = _dims(cfg)
    return 2 * d * di + di * (r + 2 * n) + r * di + di * d


def param_count(cfg):
    d, n, k, di, r, v = _dims(cfg)
    layer = d + _layer_matmul(cfg) + k * di + di + di + di * n + di
    head = 0 if cfg.get("tie_embeddings") else d * v
    return v * d + head + cfg["num_layers"] * layer + d


def _token_flops(cfg):
    """Every projection, the head, and the scan recurrence
    (``kernels/mamba_scan.py``'s count)."""
    d, n, k, di, r, v = _dims(cfg)
    return (2.0 * (cfg["num_layers"] * _layer_matmul(cfg) + d * v)
            + cfg["num_layers"] * di * (6.0 * n + 3.0))


def prefill_flops(cfg, seq_len):
    return _token_flops(cfg) * seq_len


def decode_flops(cfg, context):
    return _token_flops(cfg)
