"""PartitionSpec rules: how every parameter, activation, cache, and optimizer
slot shards over the production mesh.

Conventions
-----------
- data-like axes: ("pod", "data") when present — batch / FSDP / EP(optional)
- "model" axis — tensor parallelism (heads, d_ff, vocab, d_inner)
- parameters carry a leading super-block dim when scanned -> specs get a
  leading None
- FSDP (``par.fsdp > 1``) shards the *non-TP* matrix dimension of each weight
  over the data-like axes (ZeRO-3 style); optimizer state inherits the same
  spec.

Rules are keyed on parameter path suffixes; anything unmatched is replicated.
This table *is* part of the tunable surface: CAMEO mutates ``ParallelConfig``
and the rules react.
"""

from __future__ import annotations

import re
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import compat
from repro.utils.config import ModelConfig, ParallelConfig


def data_axes_of(mesh_axes: Tuple[str, ...]) -> Tuple[str, ...]:
    return tuple(a for a in mesh_axes if a in ("pod", "data"))


def _active_mesh() -> Optional[Mesh]:
    return compat.get_abstract_mesh()


def activation_sharding(h: jax.Array, par: ParallelConfig) -> jax.Array:
    """Constrain (B, S, D) activations: batch over data axes, seq over model
    when sequence parallelism is on."""
    mesh = _active_mesh()
    if mesh is None:
        return h
    daxes = data_axes_of(tuple(mesh.axis_names))
    if not daxes:
        return h
    dsize = int(np.prod([mesh.shape[a] for a in daxes]))
    batch_spec = daxes if (h.shape[0] % dsize == 0) else None
    seq_spec = None
    if par.sp and h.ndim >= 3 and "model" in mesh.axis_names \
            and h.shape[1] % mesh.shape["model"] == 0:
        seq_spec = "model"
    spec = P(batch_spec, seq_spec, *([None] * (h.ndim - 2)))
    return jax.lax.with_sharding_constraint(h, spec)


# --------------------------------------------------------------------------
# parameter rules
# --------------------------------------------------------------------------

# (regex on path, spec builder(fsdp_axes) -> tuple of axis assignments for the
#  *trailing* dims of the weight; leading scan dim handled separately)
def _rules(par: ParallelConfig):
    F = "__FSDP__"  # placeholder replaced by fsdp axes (or None)
    M = "model" if par.tp > 1 else None
    E = "model" if par.moe_expert_axis == "model" else "__EP__"
    return [
        # embeddings / head
        (r"embed/embedding$", (M, F)),
        (r"embed/lm_head$", (F, M)),
        (r"frame_proj$", (F, M)),
        # attention (gqa & cross)
        (r"attn/wq$|cross/wq$", (F, M)),
        (r"attn/wk$|cross/wk$", (F, M)),
        (r"attn/wv$|cross/wv$", (F, M)),
        (r"attn/wo$|cross/wo$", (M, F)),
        # MLA
        (r"attn/w_dq$", (F, None)),
        (r"attn/w_uq$", (None, M)),
        (r"attn/w_dkv$", (F, None)),
        (r"attn/w_uk$", (None, M)),
        (r"attn/w_uv$", (None, M)),
        # dense mlp
        (r"mlp/w_gate$|mlp/w_up$|shared/w_gate$|shared/w_up$", (F, M)),
        (r"mlp/w_down$|shared/w_down$", (M, F)),
        # MoE experts (leading expert dim)
        (r"moe/router$", (F, None)),
        (r"moe/w_gate$|moe/w_up$", (E, F, M if E != "model" else None)),
        (r"moe/w_down$", (E, M if E != "model" else None, F)),
        # mamba1
        (r"mixer/w_x$|mixer/w_z$", (F, M)),
        (r"mixer/conv_w$|mixer/conv_x_w$", (None, M)),
        (r"mixer/conv_b$|mixer/conv_x_b$", (M,)),
        (r"mixer/w_bcdt$", (M, None)),
        (r"mixer/w_dt$", (None, M)),
        (r"mixer/dt_bias$", (M,)),
        (r"mixer/A_log$", (M, None)),
        (r"mixer/D$", (M,)),
        (r"mixer/w_out$", (M, F)),
        # mamba2 extras
        (r"mixer/w_B$|mixer/w_C$|mixer/w_dtp$", (F, None)),
        (r"mixer/conv_bc_w$|mixer/conv_bc_b$", None),  # tiny, replicate
        (r"mixer/norm_scale$", (M,)),
        # mtp
        (r"mtp/proj$", (F, M)),
        # norms: replicate
        (r"norm", None),
        (r"scale$", None),
        (r"cross_gate$", None),
    ]


def _spec_for(path: str, shape: Tuple[int, ...], cfg: ModelConfig,
              par: ParallelConfig, mesh_axes: Tuple[str, ...],
              mesh_shape: Dict[str, int]) -> P:
    daxes = data_axes_of(mesh_axes)
    fsdp_axes: Any = daxes if (par.fsdp > 1 and daxes) else None
    scanned = any(seg in path for seg in ("blocks/",))

    dims = len(shape)
    body_dims = dims - 1 if scanned else dims
    assign: Any = None
    for pat, spec in _rules(par):
        if re.search(pat, path):
            assign = spec
            break

    out = [None] * dims
    if assign is not None:
        # tail-align the assignment onto the body dims
        assign = list(assign)[-body_dims:] if body_dims else []
        offset = dims - len(assign)
        for i, a in enumerate(assign):
            if a == "__FSDP__":
                a = fsdp_axes
            elif a == "__EP__":
                a = daxes if daxes else None
            if a is None:
                continue
            axes = a if isinstance(a, tuple) else (a,)
            size = int(np.prod([mesh_shape.get(x, 1) for x in axes]))
            if size > 1 and shape[offset + i] % size == 0:
                out[offset + i] = a
    # drop duplicate axis uses (an axis may appear only once in a spec)
    seen = set()
    for i, a in enumerate(out):
        axes = a if isinstance(a, tuple) else (a,) if a else ()
        if any(x in seen for x in axes):
            out[i] = None
        else:
            seen.update(axes)
    return P(*out)


def param_specs(params_shapes, cfg: ModelConfig, par: ParallelConfig,
                mesh: Mesh) -> Any:
    """Tree of PartitionSpec matching a (possibly abstract) params tree."""
    mesh_axes = tuple(mesh.axis_names)
    mesh_shape = dict(mesh.shape)
    flat, treedef = jax.tree_util.tree_flatten_with_path(params_shapes)
    specs = []
    for path, leaf in flat:
        pstr = "/".join(_key_str(p) for p in path)
        specs.append(_spec_for(pstr, tuple(leaf.shape), cfg, par, mesh_axes, mesh_shape))
    return jax.tree_util.tree_unflatten(treedef, specs)


def named_shardings(params_shapes, cfg: ModelConfig, par: ParallelConfig,
                    mesh: Mesh) -> Any:
    specs = param_specs(params_shapes, cfg, par, mesh)
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))


def cache_specs(state_shapes, cfg: ModelConfig, par: ParallelConfig,
                mesh: Mesh) -> Any:
    """Decode-state sharding: batch over data axes (when divisible), kv-heads
    / latent dims over model axis where aligned."""
    mesh_axes = tuple(mesh.axis_names)
    mesh_shape = dict(mesh.shape)
    daxes = data_axes_of(mesh_axes)
    dsize = int(np.prod([mesh_shape[a] for a in daxes])) if daxes else 1
    msize = mesh_shape.get("model", 1)

    def one(path, leaf):
        shape = leaf.shape
        # stacked caches have a leading super-block dim
        # find batch dim: first dim (after optional stack dim) that divides
        out = [None] * len(shape)
        start = 1 if len(shape) >= 3 else 0
        if len(shape) >= 2 and daxes and shape[start] % dsize == 0:
            out[start] = daxes
        # shard a heads-like or channel dim over model (k/v: (..., S, H, D))
        pstr = "/".join(_key_str(p) for p in path)
        if msize > 1 and len(shape) >= 2:
            for d in range(len(shape) - 1, start, -1):
                if out[d] is None and shape[d] % msize == 0 and shape[d] >= msize:
                    if ("length" not in pstr):
                        out[d] = "model"
                        break
        return P(*out)

    flat, treedef = jax.tree_util.tree_flatten_with_path(state_shapes)
    return jax.tree_util.tree_unflatten(treedef, [one(p, l) for p, l in flat])


def _key_str(p) -> str:
    if hasattr(p, "key"):
        return str(p.key)
    if hasattr(p, "name"):
        return str(p.name)
    if hasattr(p, "idx"):
        return str(p.idx)
    return str(p)


# --------------------------------------------------------------------------
# train / serve state + batch specs
# --------------------------------------------------------------------------

def _flat_by_path(tree) -> Dict[str, Any]:
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        out["/".join(_key_str(p) for p in path)] = leaf
    return out


def train_state_specs(state_template, cfg: ModelConfig, par: ParallelConfig,
                      mesh: Mesh):
    """PartitionSpecs for a full TrainState (params + optimizer slots + step
    + error buffer).

    Optimizer slots inherit the parameter's spec; adafactor's factored
    ``vr``/``vc`` slots drop the corresponding spec dimension (vr drops the
    last, vc the second-to-last) so ZeRO-style sharding carries over to the
    factored statistics.
    """
    pspecs = param_specs(state_template.params, cfg, par, mesh)
    pspec_by_path = _flat_by_path(pspecs)

    def opt_spec(path: str, leaf) -> P:
        parts = path.split("/")
        if parts and parts[0] in ("m", "v"):
            return pspec_by_path.get("/".join(parts[1:]), P())
        if parts and parts[0] == "slots":
            kind = parts[-1]
            ppath = "/".join(parts[1:-1])
            spec = tuple(pspec_by_path.get(ppath, P()))
            # pad the spec with Nones to the param rank before factoring
            rank = len(leaf.shape) + (1 if kind in ("vr", "vc") else 0)
            spec = (None,) * (rank - len(spec)) + spec
            if kind == "vr":
                return P(*spec[:-1])
            if kind == "vc":
                return P(*(spec[:-2] + spec[-1:]))
            return P(*spec)
        return P()

    flat, treedef = jax.tree_util.tree_flatten_with_path(state_template.opt_state)
    opt_specs = jax.tree_util.tree_unflatten(
        treedef,
        [opt_spec("/".join(_key_str(p) for p in path), leaf)
         for path, leaf in flat])

    err_specs = None
    if state_template.error_buf is not None:
        err_specs = pspecs
    return type(state_template)(
        params=pspecs, opt_state=opt_specs, step=P(), error_buf=err_specs)


def batch_specs(batch_template, mesh: Mesh):
    """Batch arrays shard dim 0 over the data-like axes."""
    daxes = data_axes_of(tuple(mesh.axis_names))
    dsize = int(np.prod([mesh.shape[a] for a in daxes])) if daxes else 1

    def one(leaf):
        if daxes and leaf.shape and leaf.shape[0] % dsize == 0:
            return P(daxes, *([None] * (len(leaf.shape) - 1)))
        return P(*([None] * len(leaf.shape)))

    return jax.tree.map(one, batch_template)


def serve_state_specs(state_template, cfg: ModelConfig, par: ParallelConfig,
                      mesh: Mesh):
    """ServeState sharding: caches via cache rules; lengths/extras batch-major."""
    mesh_axes = tuple(mesh.axis_names)
    daxes = data_axes_of(mesh_axes)
    dsize = int(np.prod([dict(mesh.shape)[a] for a in daxes])) if daxes else 1

    caches = cache_specs(state_template.caches, cfg, par, mesh)
    lengths = (P(daxes) if daxes and state_template.lengths.shape[0] % dsize == 0
               else P(None))

    def extra_spec(leaf):
        out = [None] * len(leaf.shape)
        if daxes and leaf.shape and leaf.shape[0] % dsize == 0:
            out[0] = daxes
        msize = dict(mesh.shape).get("model", 1)
        if len(leaf.shape) >= 2 and msize > 1 and leaf.shape[-1] % msize == 0:
            out[-1] = "model"
        return P(*out)

    extras = jax.tree.map(extra_spec, state_template.extras)
    return type(state_template)(caches=caches, lengths=lengths, extras=extras)
