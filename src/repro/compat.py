"""The jax surfaces this repo wraps, written for the installed jax (0.9.0,
with libtpu 0.0.34 for the TPU v5e target).

Nothing outside this module may touch ``jax.experimental.pallas.tpu``
attributes or version-gated ``jax.sharding`` lookups directly — kernels go
through :mod:`repro.kernels.dispatch`, which in turn goes through here.

Wrapped surfaces
----------------
- pallas TPU: :func:`tpu_compiler_params`, :func:`vmem`,
  :func:`prefetch_scalar_grid_spec`.  ``HAS_PALLAS_TPU`` says whether the
  Mosaic lowering imported; :func:`pallas_tpu` raises an actionable error
  instead of an AttributeError mid-kernel.  Unknown compiler parameters
  raise: a misspelt ``dimension_semantics`` must not vanish.
- meshes: :func:`make_mesh` builds every concrete mesh with ``Auto`` axes,
  which ``with_sharding_constraint`` under the repo's sharding rules needs
  (jax 0.9 defaults ``jax.make_mesh`` to ``Explicit`` axes);
  :func:`get_abstract_mesh` reads the mesh installed by ``jax.set_mesh``.
"""

from __future__ import annotations

from typing import Any, Optional, Sequence, Tuple

import jax
from jax.sharding import AxisType

try:  # the TPU lowering ships with jax; a stripped jaxlib may lack it
    from jax.experimental import pallas as pl  # noqa: F401
    from jax.experimental.pallas import tpu as _pltpu

    HAS_PALLAS_TPU = True
except ImportError:  # pragma: no cover - exercised on stripped builds only
    _pltpu = None
    HAS_PALLAS_TPU = False


# --------------------------------------------------------------------------
# pallas TPU surface
# --------------------------------------------------------------------------

def pallas_tpu():
    """The ``jax.experimental.pallas.tpu`` module, or a clear error."""
    if _pltpu is None:
        raise ImportError(
            "jax.experimental.pallas.tpu is unavailable in this jaxlib "
            "build; run kernels in 'ref' mode (REPRO_KERNEL_MODE=ref)")
    return _pltpu


def tpu_compiler_params(**kwargs) -> Any:
    """``pltpu.CompilerParams(**kwargs)``; an unknown keyword raises."""
    return pallas_tpu().CompilerParams(**kwargs)


def vmem(shape: Tuple[int, ...], dtype) -> Any:
    """A VMEM scratch-shape allocation request."""
    return pallas_tpu().VMEM(shape, dtype)


def prefetch_scalar_grid_spec(*, num_scalar_prefetch: int, grid, in_specs,
                              out_specs, scratch_shapes=()) -> Any:
    return pallas_tpu().PrefetchScalarGridSpec(
        num_scalar_prefetch=num_scalar_prefetch, grid=grid,
        in_specs=in_specs, out_specs=out_specs,
        scratch_shapes=list(scratch_shapes))


# --------------------------------------------------------------------------
# meshes
# --------------------------------------------------------------------------

def make_mesh(axis_sizes: Sequence[int], axis_names: Sequence[str], *,
              devices: Optional[Sequence[Any]] = None) -> Any:
    """A concrete ``Mesh`` whose axes are all ``AxisType.Auto``."""
    return jax.make_mesh(tuple(axis_sizes), tuple(axis_names),
                         axis_types=(AxisType.Auto,) * len(axis_names),
                         devices=devices)


def get_abstract_mesh() -> Optional[Any]:
    """The mesh installed by ``jax.set_mesh``, or None outside one."""
    m = jax.sharding.get_abstract_mesh()
    return None if m is None or m.empty else m
