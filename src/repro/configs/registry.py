"""Architecture registry: the assigned archs, shape cells, run assembly,
and ShapeDtypeStruct input factories for the dry-run.

``--arch <id>`` everywhere resolves through ``get_model_config``.
"""

from __future__ import annotations

import importlib
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.shapes import SHAPES, SMOKE_SHAPES, shape_runs_for
from repro.utils.config import (
    MeshConfig, ModelConfig, ParallelConfig, RunConfig, ShapeConfig,
    TrainConfig)

_ARCH_MODULES: Dict[str, str] = {
    "falcon-mamba-7b": "repro.configs.falcon_mamba_7b",
    "zamba2-2.7b": "repro.configs.zamba2_2p7b",
    "nemotron-4-15b": "repro.configs.nemotron_4_15b",
    "llama3.2-1b": "repro.configs.llama3p2_1b",
    "command-r-35b": "repro.configs.command_r_35b",
    "h2o-danube-1.8b": "repro.configs.h2o_danube_1p8b",
    "deepseek-v3-671b": "repro.configs.deepseek_v3_671b",
    "llama4-maverick-400b-a17b": "repro.configs.llama4_maverick_400b",
    "llama-3.2-vision-11b": "repro.configs.llama3p2_vision_11b",
    "whisper-large-v3": "repro.configs.whisper_large_v3",
    "jamba2-3b": "repro.configs.jamba2_3b",
}

# archs whose second-moment state must be factored to fit HBM at 512 chips
_ADAFACTOR_ARCHS = {"deepseek-v3-671b", "llama4-maverick-400b-a17b",
                    "command-r-35b", "nemotron-4-15b"}


def list_archs():
    return list(_ARCH_MODULES)


def _module(arch: str):
    if arch not in _ARCH_MODULES:
        raise KeyError(f"unknown arch {arch!r}; known: {list(_ARCH_MODULES)}")
    return importlib.import_module(_ARCH_MODULES[arch])


def get_model_config(arch: str) -> ModelConfig:
    return _module(arch).CONFIG


def get_smoke_config(arch: str) -> ModelConfig:
    return _module(arch).SMOKE


def default_parallel(arch: str, kind: str) -> ParallelConfig:
    return _module(arch).default_parallel(kind)


def arch_shapes(arch: str) -> Dict[str, ShapeConfig]:
    return shape_runs_for(get_model_config(arch).sub_quadratic)


def default_train_config(arch: str) -> TrainConfig:
    opt = "adafactor" if arch in _ADAFACTOR_ARCHS else "adamw"
    return TrainConfig(optimizer=opt)


def make_run(arch: str, shape: str, *, multi_pod: bool = False,
             parallel: Optional[ParallelConfig] = None,
             train: Optional[TrainConfig] = None,
             smoke: bool = False) -> RunConfig:
    shapes = SMOKE_SHAPES if smoke else SHAPES
    if shape not in shapes:
        raise KeyError(f"unknown shape {shape!r}; known: {list(shapes)}")
    shape_cfg = shapes[shape]
    model = get_smoke_config(arch) if smoke else get_model_config(arch)
    if shape_cfg.name == "long_500k" and not model.sub_quadratic:
        raise ValueError(
            f"{arch} is pure full-attention; long_500k is a documented skip")
    mesh = (MeshConfig(shape=(2, 16, 16), axes=("pod", "data", "model"))
            if multi_pod else MeshConfig(shape=(16, 16), axes=("data", "model")))
    if smoke:
        mesh = MeshConfig(shape=(1,), axes=("data",))
        parallel = parallel or ParallelConfig()
    par = parallel or default_parallel(arch, shape_cfg.kind)
    tc = train or default_train_config(arch)
    run = RunConfig(model=model, shape=shape_cfg, mesh=mesh, parallel=par, train=tc)
    run.validate()
    return run


# --------------------------------------------------------------------------
# input specs (ShapeDtypeStruct stand-ins, no allocation)
# --------------------------------------------------------------------------

def _i32(*shape) -> jax.ShapeDtypeStruct:
    return jax.ShapeDtypeStruct(shape, jnp.int32)


def _f(shape, dtype) -> jax.ShapeDtypeStruct:
    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype))


def _modal_extras(cfg: ModelConfig, b: int) -> Dict[str, jax.ShapeDtypeStruct]:
    out: Dict[str, jax.ShapeDtypeStruct] = {}
    if cfg.family == "vlm":
        out["vision_embeds"] = _f((b, cfg.vision_seq, cfg.vision_dim), cfg.dtype)
    if cfg.family == "audio":
        out["frames"] = _f((b, cfg.encoder_seq, cfg.d_model), cfg.dtype)
    return out


def input_specs(run: RunConfig) -> Dict[str, Any]:
    """Abstract inputs for the step function this shape cell lowers.

    train  -> {"batch": {inputs, targets, [modal]}}
    prefill-> {"batch": {tokens, [modal]}}
    decode -> {"state": ServeState, "tokens": (B, 1)}
    """
    cfg, shape = run.model, run.shape
    b, s = shape.global_batch, shape.seq_len
    if shape.kind == "train":
        batch = {"inputs": _i32(b, s), "targets": _i32(b, s)}
        batch.update(_modal_extras(cfg, b))
        return {"batch": batch}
    if shape.kind == "prefill":
        batch = {"tokens": _i32(b, s)}
        batch.update(_modal_extras(cfg, b))
        return {"batch": batch}
    assert shape.kind == "decode", shape.kind
    from repro.models.model import build_model
    from repro.train.serve_step import ServeState

    model = build_model(cfg, run.parallel)
    caches = jax.eval_shape(lambda: model.init_decode_state(b, s))
    extras: Dict[str, jax.ShapeDtypeStruct] = {}
    if cfg.family == "vlm":
        extras["vision_embeds"] = _f((b, cfg.vision_seq, cfg.vision_dim), cfg.dtype)
    if cfg.family == "audio":
        extras["enc_out"] = _f((b, cfg.encoder_seq, cfg.d_model), cfg.dtype)
    state = ServeState(caches=caches, lengths=_i32(b), extras=extras)
    return {"state": state, "tokens": _i32(b, 1)}


def all_cells() -> Tuple[Tuple[str, str], ...]:
    """All 40 assigned (arch, shape) cells, including documented skips."""
    cells = []
    for arch in list_archs():
        for shape in SHAPES:
            cells.append((arch, shape))
    return tuple(cells)


def runnable_cells() -> Tuple[Tuple[str, str], ...]:
    """Cells that compile (excludes full-attention long_500k skips)."""
    out = []
    for arch in list_archs():
        for shape in arch_shapes(arch):
            out.append((arch, shape))
    return tuple(out)
