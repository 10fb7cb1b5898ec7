"""Seeded weights in the program's parameter layout, made on the device in
one jitted call, in the types the program serves them in.

The layout (names, shapes, dtypes) is read from the program with
``jax.eval_shape``; the values are the benchmark's own, drawn by leaf name,
so the reference gets the same arrays without taking anything the program
made.  Scales follow the usual conventions: matrices fan-in scaled normals,
norms at one, embedding rows of norm ``d_model**-0.5``, Mamba's ``A_log``,
``dt_bias`` and ``D`` as the Mamba papers initialise them.
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp


def _leaf_name(path) -> str:
    return str(getattr(path[-1], "key", getattr(path[-1], "name", path[-1])))


def _draw(name: str, key, shape, dtype):
    f32 = jnp.float32
    if name == "scale":
        return jnp.ones(shape, dtype)
    if name == "conv_b":
        return (0.1 * jax.random.normal(key, shape, f32)).astype(dtype)
    if name == "conv_w":
        return (jax.random.normal(key, shape, f32)
                / math.sqrt(shape[-2])).astype(dtype)
    if name == "A_log":
        # per (channel, state): A in [1, 16]
        return jnp.log(jax.random.uniform(key, shape, f32, 1.0, 16.0)
                       ).astype(dtype)
    if name == "dt_bias":
        dt = jnp.exp(jax.random.uniform(key, shape, f32)
                     * (math.log(0.1) - math.log(0.001)) + math.log(0.001))
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
    if name == "D":
        return jax.random.uniform(key, shape, f32, 0.5, 1.5).astype(dtype)
    if name == "embedding":
        # rows of norm d_model**-0.5: the program scales by sqrt(d_model) on
        # the way in, and with larger rows a tied head ranks the input token
        # first whatever the state holds, so greedy decoding would only
        # repeat it
        return (jax.random.normal(key, shape, f32)
                / shape[-1]).astype(dtype)
    # projections: (..., fan_in, fan_out), stacked over layers
    return (jax.random.normal(key, shape, f32)
            / math.sqrt(shape[-2])).astype(dtype)


def make_params(model, key) -> Any:
    """Every parameter of ``model`` from ``key``, on the default device."""
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def build(k):
        keys = jax.random.split(k, len(flat))
        leaves = [_draw(_leaf_name(p), keys[i], s.shape, s.dtype)
                  for i, (p, s) in enumerate(flat)]
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return jax.block_until_ready(jax.jit(build)(key))
