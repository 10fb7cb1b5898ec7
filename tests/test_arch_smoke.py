"""Per-architecture smoke tests: the REDUCED config of each assigned family
runs one forward and one train step on CPU, asserting output shapes and the
absence of NaNs (per the task spec)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_smoke_config, list_archs
from repro.data.pipeline import make_data
from repro.models.model import build_model
from repro.train.optimizer import make_optimizer
from repro.train.train_step import init_train_state, make_train_step
from repro.utils.config import MeshConfig, RunConfig, ShapeConfig, TrainConfig

ARCHS = list_archs()


def _smoke_run(arch):
    cfg = get_smoke_config(arch)
    shape = ShapeConfig("train_smoke", seq_len=32, global_batch=2, kind="train")
    return RunConfig(model=cfg, shape=shape,
                     mesh=MeshConfig(shape=(1,), axes=("data",)),
                     train=TrainConfig(total_steps=4, warmup_steps=1))


def _batch_for(run):
    data = make_data(run.model, run.shape, seed=0)
    return {k: jnp.asarray(v) for k, v in data.batch_at(0).items()}


@pytest.mark.parametrize("arch", ARCHS)
def test_forward_shapes_and_finiteness(arch):
    run = _smoke_run(arch)
    model = build_model(run.model, run.parallel)
    params = model.init(jax.random.PRNGKey(0))
    batch = _batch_for(run)
    fkw = {}
    if run.model.family == "vlm":
        fkw["vision_embeds"] = batch["vision_embeds"]
    if run.model.family == "audio":
        fkw["frames"] = batch["frames"]
    logits, _, aux = model.forward(params, batch["inputs"], **fkw)
    b, s = batch["inputs"].shape
    assert logits.shape == (b, s, run.model.vocab_size)
    assert bool(jnp.isfinite(logits).all()), f"{arch}: non-finite logits"
    assert bool(jnp.isfinite(aux)), f"{arch}: non-finite aux loss"


@pytest.mark.parametrize("arch", ARCHS)
def test_one_train_step(arch):
    run = _smoke_run(arch)
    model = build_model(run.model, run.parallel)
    optimizer = make_optimizer(run.train)
    step_fn = jax.jit(make_train_step(model, run, optimizer))
    state = init_train_state(model, run, optimizer, jax.random.PRNGKey(0))
    batch = _batch_for(run)
    new_state, metrics = step_fn(state, batch)
    assert int(new_state.step) == 1
    assert np.isfinite(float(metrics["loss"])), f"{arch}: NaN loss"
    assert np.isfinite(float(metrics["grad_norm"])), f"{arch}: NaN grads"
    # params actually changed
    before = jax.tree.leaves(state.params)[1]
    after = jax.tree.leaves(new_state.params)[1]
    assert not np.allclose(np.asarray(before), np.asarray(after))


@pytest.mark.parametrize("arch", ARCHS)
def test_param_count_positive_and_moe_active_smaller(arch):
    cfg = get_smoke_config(arch)
    n = cfg.param_count()
    assert n > 0
    if cfg.is_moe:
        assert cfg.active_param_count() < n


def test_full_config_param_counts_match_public_scale():
    """Full (non-smoke) configs land in the right parameter ballpark."""
    from repro.configs.registry import get_model_config

    expect = {
        "falcon-mamba-7b": (6e9, 9e9),
        "zamba2-2.7b": (2.0e9, 3.5e9),
        "nemotron-4-15b": (12e9, 18e9),
        "llama3.2-1b": (1.0e9, 1.6e9),
        "command-r-35b": (30e9, 40e9),
        "h2o-danube-1.8b": (1.4e9, 2.2e9),
        "deepseek-v3-671b": (600e9, 750e9),
        "llama4-maverick-400b-a17b": (350e9, 450e9),
        "llama-3.2-vision-11b": (8e9, 13e9),
        "whisper-large-v3": (1.2e9, 2.2e9),
        "jamba2-3b": (2.9e9, 3.2e9),
    }
    for arch, (lo, hi) in expect.items():
        n = get_model_config(arch).param_count()
        assert lo <= n <= hi, f"{arch}: {n/1e9:.2f}B not in [{lo/1e9}, {hi/1e9}]B"
