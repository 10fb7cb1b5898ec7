"""Production training launcher.

Selects an assigned architecture (``--arch``), builds the mesh from the
available devices, assembles the sharded train step, and runs the
fault-tolerant driver with checkpointing.  On this CPU container it runs the
smoke-scale config end-to-end; on a real TPU slice the same entry point runs
the full config (the mesh adapts to ``jax.device_count()``).

    PYTHONPATH=src python -m repro.launch.train --arch llama3.2-1b --steps 50

``--tune-launch N`` closes the CAMEO loop before training (mirroring
serve): a transfer-tuning run (analytic source, ``--measure-backend``
target) over the kernel-launch space picks block sizes / chunk lengths for
this training shape, and the winning configuration is baked into the jitted
train step.
"""

from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp

from repro.checkpoint.manager import CheckpointManager
from repro.configs.registry import get_smoke_config, get_model_config, list_archs
from repro.data.pipeline import make_data
from repro.launch.mesh import make_mesh, state_shardings, batch_shardings
from repro.launch.tune import measure_backend_arg, tune_launch_config
from repro.models.model import build_model
from repro.runtime.driver import TrainDriver
from repro.runtime.elastic import adjust_run_for_devices
from repro.train.optimizer import make_optimizer
from repro.train.train_step import init_train_state, make_train_step
from repro.utils.compile_cache import enable_compile_cache
from repro.utils.config import (MeshConfig, ParallelConfig, RunConfig,
                                ShapeConfig, TrainConfig)
from repro.utils.logging import MetricsLogger


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list_archs(), default="llama3.2-1b")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--full-config", action="store_true",
                    help="use the full (not smoke) architecture config; "
                         "requires a real accelerator slice")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_launch_train")
    ap.add_argument("--tune-launch", type=int, default=0, metavar="BUDGET",
                    help="intervention budget for a kernel-launch tuning run "
                         "before training (0 = train with registry defaults)")
    ap.add_argument("--measure-backend", type=measure_backend_arg,
                    default=None,
                    help="target measurement backend for --tune-launch: "
                         "analytic, wallclock, or shifted:<kind> "
                         "(default: REPRO_MEASURE_BACKEND, then analytic)")
    ap.add_argument("--query-batch", type=int, default=1, metavar="K",
                    help="measurements per ask/tell tuning round for "
                         "--tune-launch (1 = sequential)")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = (get_model_config(args.arch) if args.full_config
           else get_smoke_config(args.arch))
    ndev = jax.device_count()
    run = RunConfig(
        model=cfg,
        shape=ShapeConfig("train_cli", args.seq, args.batch, "train"),
        mesh=MeshConfig(shape=(ndev,), axes=("data",)),
        parallel=ParallelConfig(),
        train=TrainConfig(lr=1e-3, warmup_steps=10, total_steps=args.steps),
        checkpoint_dir=args.ckpt_dir, checkpoint_every=25, log_every=5,
    )
    run = adjust_run_for_devices(run, ndev) if ndev > 1 else run
    run.validate()
    print(f"[train] {cfg.name}: {cfg.param_count()/1e6:.1f}M params on "
          f"{ndev} device(s)")

    model = build_model(cfg, run.parallel)
    optimizer = make_optimizer(run.train)
    mesh = make_mesh(run.mesh)

    launch_config = None
    if args.tune_launch > 0:
        launch_config = tune_launch_config(cfg, args.batch, args.seq,
                                           args.tune_launch,
                                           args.measure_backend, kind="train",
                                           query_batch=args.query_batch)

    def init_state():
        return init_train_state(model, run, optimizer,
                                jax.random.PRNGKey(run.train.seed))

    with jax.set_mesh(mesh), \
            MetricsLogger(name=f"train-{args.arch}") as logger:
        state_t = jax.eval_shape(init_state)
        step_fn = jax.jit(
            make_train_step(model, run, optimizer,
                            launch_config=launch_config),
            in_shardings=(state_shardings(state_t, run, mesh), None),
            donate_argnums=(0,))
        driver = TrainDriver(
            run, step_fn, init_state, make_data(cfg, run.shape, seed=0),
            CheckpointManager(run.checkpoint_dir, keep=run.keep_checkpoints),
            logger=logger)
        state = driver.run_steps(args.steps)
    print(f"[train] finished at step {int(state.step)}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
