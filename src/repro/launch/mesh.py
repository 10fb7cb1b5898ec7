"""Mesh construction + sharding assembly for the production meshes.

``make_production_mesh`` is a FUNCTION (never a module constant) so importing
this module never touches jax device state — required because only
``dryrun.py`` runs under the 512-device XLA flag.
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import compat
from repro.sharding.specs import (
    batch_specs, cache_specs, param_specs, serve_state_specs,
    train_state_specs)
from repro.utils.config import MeshConfig, RunConfig


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return compat.make_mesh(shape, axes)


def make_mesh(cfg: MeshConfig) -> Mesh:
    n = cfg.num_devices
    avail = jax.devices()
    if len(avail) < n:
        raise RuntimeError(
            f"mesh {cfg.shape} needs {n} devices, have {len(avail)} "
            "(dryrun.py sets --xla_force_host_platform_device_count=512)")
    return compat.make_mesh(cfg.shape, cfg.axes, devices=avail[:n])


def _as_named(tree_specs, mesh: Mesh):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), tree_specs,
        is_leaf=lambda x: isinstance(x, P))


def state_shardings(state_template, run: RunConfig, mesh: Mesh):
    """NamedShardings for a TrainState template (params/opt/step/error_buf)."""
    return _as_named(
        train_state_specs(state_template, run.model, run.parallel, mesh), mesh)


def serve_shardings(state_template, run: RunConfig, mesh: Mesh):
    return _as_named(
        serve_state_specs(state_template, run.model, run.parallel, mesh), mesh)


def params_shardings(params_template, run: RunConfig, mesh: Mesh):
    return _as_named(
        param_specs(params_template, run.model, run.parallel, mesh), mesh)


def batch_shardings(batch_template, mesh: Mesh):
    return _as_named(batch_specs(batch_template, mesh), mesh)
