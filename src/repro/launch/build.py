"""Assemble the jit-able step function + shardings for one (run, mesh) cell.

Shared by dryrun.py (lower/compile only), the benchmarks, and the real
launchers.  ``build_step`` returns everything needed to call
``jax.jit(fn, in_shardings=..., out_shardings=..., donate_argnums=...)
.lower(*abstract_args)``.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.registry import input_specs
from repro.launch.mesh import (
    batch_shardings, params_shardings, serve_shardings, state_shardings)
from repro.models.model import build_model
from repro.train.optimizer import make_optimizer
from repro.train.serve_step import make_decode_step, make_prefill_step
from repro.train.train_step import init_train_state, make_train_step
from repro.utils.config import RunConfig


class StepBundle(NamedTuple):
    fn: Callable                     # the function to jit
    abstract_args: Tuple[Any, ...]   # ShapeDtypeStruct pytrees for .lower()
    in_shardings: Tuple[Any, ...]
    out_shardings: Any
    donate_argnums: Tuple[int, ...]
    kind: str


def _replicated_like(tree, mesh: Mesh):
    return jax.tree.map(lambda _: NamedSharding(mesh, P()), tree)


def build_step(run: RunConfig, mesh: Mesh) -> StepBundle:
    cfg = run.model
    model = build_model(cfg, run.parallel)
    specs = input_specs(run)
    kind = run.shape.kind

    if kind == "train":
        optimizer = make_optimizer(run.train)
        train_step = make_train_step(model, run, optimizer)

        def init_state():
            return init_train_state(model, run, optimizer,
                                    jax.random.PRNGKey(run.train.seed))

        state_t = jax.eval_shape(init_state)
        batch_t = specs["batch"]
        state_sh = state_shardings(state_t, run, mesh)
        batch_sh = batch_shardings(batch_t, mesh)
        out_t = jax.eval_shape(train_step, state_t, batch_t)
        out_sh = (state_sh, _replicated_like(out_t[1], mesh))
        return StepBundle(
            fn=train_step,
            abstract_args=(state_t, batch_t),
            in_shardings=(state_sh, batch_sh),
            out_shardings=out_sh,
            donate_argnums=(0,),
            kind=kind,
        )

    params_t = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(run.train.seed)))
    params_sh = params_shardings(params_t, run, mesh)

    if kind == "prefill":
        prefill = make_prefill_step(model, run)
        batch_t = specs["batch"]
        batch_sh = batch_shardings(batch_t, mesh)
        out_t = jax.eval_shape(prefill, params_t, batch_t)
        state_sh = serve_shardings(out_t[0], run, mesh)
        logits_sh = _logits_sharding(out_t[1], mesh)
        return StepBundle(
            fn=prefill,
            abstract_args=(params_t, batch_t),
            in_shardings=(params_sh, batch_sh),
            out_shardings=(state_sh, logits_sh),
            donate_argnums=(),
            kind=kind,
        )

    assert kind == "decode"
    decode = make_decode_step(model, run)
    state_t, tokens_t = specs["state"], specs["tokens"]
    state_sh = serve_shardings(state_t, run, mesh)
    tokens_sh = batch_shardings(tokens_t, mesh)
    out_t = jax.eval_shape(decode, params_t, state_t, tokens_t)
    logits_sh = _logits_sharding(out_t[1], mesh)
    return StepBundle(
        fn=decode,
        abstract_args=(params_t, state_t, tokens_t),
        in_shardings=(params_sh, state_sh, tokens_sh),
        out_shardings=(state_sh, logits_sh),
        donate_argnums=(1,),  # decode state is consumed each step
        kind=kind,
    )


def _logits_sharding(logits_t, mesh: Mesh):
    from repro.sharding.specs import data_axes_of
    import numpy as np

    daxes = data_axes_of(tuple(mesh.axis_names))
    dsize = int(np.prod([dict(mesh.shape)[a] for a in daxes])) if daxes else 1
    msize = dict(mesh.shape).get("model", 1)
    spec = [None] * len(logits_t.shape)
    if daxes and logits_t.shape[0] % dsize == 0:
        spec[0] = daxes
    if msize > 1 and logits_t.shape[-1] % msize == 0:
        spec[-1] = "model"
    return NamedSharding(mesh, P(*spec))


def lower_step(run: RunConfig, mesh: Mesh):
    """jit + lower (no compile). Returns (bundle, lowered).

    ``jax.set_mesh`` so the active mesh is visible during tracing —
    activation sharding constraints (``sharding.specs.activation_sharding``)
    are no-ops otherwise and XLA then replicates the layer-scan AD residuals
    across the batch axis.
    """
    b = build_step(run, mesh)
    with jax.set_mesh(mesh):
        jitted = jax.jit(b.fn, in_shardings=b.in_shardings,
                         out_shardings=b.out_shardings,
                         donate_argnums=b.donate_argnums)
        lowered = jitted.lower(*b.abstract_args)
    return b, lowered
