"""Plain float32 building blocks shared by the references: written from the
papers' equations in straightforward ``jax.numpy``, with no kernel, cache
or batching of the program under test, and imported by nothing of it.

Every matrix product runs at ``highest`` precision (on a TPU a float32
product otherwise runs in bf16 passes).  ``quant="fp8"`` is the control: the
same forward with every projection matrix rounded to float8 e4m3 with one
scale per output column, the step below the bf16 the configurations state.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST


def weight(w, quant=None):
    """A projection matrix (..., fan_in, fan_out) in float32, or its float8
    e4m3 rounding (per output column scale) for the control."""
    w = w.astype(F32)
    if quant is None:
        return w
    if quant != "fp8":
        raise ValueError(f"unknown quantisation {quant!r}")
    scale = jnp.max(jnp.abs(w), axis=-2, keepdims=True) / 448.0
    scale = jnp.where(scale > 0, scale, 1.0)
    return (w / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


def mm(x, w, quant=None):
    return jnp.matmul(x.astype(F32), weight(w, quant), precision=HI)


def rmsnorm(x, scale, eps):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(F32)


def causal_conv(x, w, b):
    """Depthwise causal conv over time. x (B, L, C); w (K, C); b (C,)."""
    k = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    out = sum(xp[:, i:i + x.shape[1]] * w[i].astype(F32) for i in range(k))
    return out + b.astype(F32)


def embed(table, tokens):
    """Token embedding scaled by sqrt(d_model), as the program's LMs do."""
    d = table.shape[-1]
    return table.astype(F32)[tokens] * math.sqrt(d)
