"""Production serving launcher: batched prefill + decode for an assigned
architecture.

    PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b \
        --batch 4 --prompt-len 64 --gen 32

``--tune-launch N`` closes the CAMEO loop before serving: a transfer-tuning
run (analytic source, ``--measure-backend`` target) over the kernel-launch
space picks block sizes / chunk lengths for this serving shape, and the
winning configuration is baked into the jitted prefill/decode steps.

``--workload <spec>`` switches to trace-driven continuous batching: a
seeded request trace (``repro.workloads`` grammar, e.g.
``bursty:rate=2000``) is replayed through the real ``ContinuousBatcher``.
With ``--tune-serving N`` the full serving stack — scheduler knobs AND
kernel launch geometry — is transfer-tuned against that trace in the
workload simulator first, and the winning plan + launch config drive the
batcher:

    PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b \
        --workload "bursty:rate=2000,horizon=0.03" --tune-serving 10

``--sim2real-eval`` additionally prices the deployed plan in the simulator
and prints sim-predicted vs replayed-actual — the single-deployment view of
the gap ``benchmarks/sim2real_bench.py`` sweeps.
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.registry import get_smoke_config, get_model_config, list_archs
from repro.data.pipeline import make_data
from repro.launch.tune import (measure_backend_arg, tune_launch_config,
                               tune_serving_config)
from repro.models.model import build_model
from repro.obs import trace as obs_trace
from repro.train.serve_step import jitted_steps, sample_token
from repro.utils.compile_cache import enable_compile_cache
from repro.utils.config import MeshConfig, RunConfig, ShapeConfig


def serve_workload(model, run, params, workload_spec: str, *,
                   tune_budget: int = 0, seed: int = 0,
                   ticks_per_s=None, method: str = "cameo",
                   query_batch: int = 1, sim2real_eval: bool = False):
    """Trace-driven serving: generate the trace, optionally transfer-tune
    the serving stack against it in the simulator, then replay it through
    the real ``ContinuousBatcher`` under the tuned plan.  Returns
    ``(plan, launch_config, replay_report)`` so callers (and tests) can
    audit exactly what was deployed.  ``sim2real_eval`` additionally prices
    the deployed configuration in the simulator and prints sim-predicted vs
    replayed-actual — the per-deployment view of the sim-to-real gap the
    ``sim2real`` benchmark sweeps."""
    from repro.envs.serving_env import ServingEnv
    from repro.launch.tune import predicted_serving_report
    from repro.serving.replay import replay_trace
    from repro.serving.scheduler import ContinuousBatcher
    from repro.workloads import ServingPlan, make_workload

    workload = make_workload(workload_spec)
    trace = workload.generate(seed)
    print(f"[serve] workload {workload.spec}: {len(trace)} requests, "
          f"max context {trace.max_context}, "
          f"~{trace.mean_rate():.0f} req/s modeled")

    launch_config = None
    best_config = None
    plan = ServingPlan()
    if tune_budget > 0:
        result = tune_serving_config(model.cfg, workload_spec, tune_budget,
                                     method=method, query_batch=query_batch,
                                     seed=seed)
        best_config = result.best_config or {}
        plan = ServingPlan.from_config(best_config)
        launch_config = result.launch_config
    batcher = ContinuousBatcher(model, run, params,
                                num_slots=plan.num_slots,
                                cache_len=plan.cache_len,
                                interleave=plan.interleave,
                                launch_config=launch_config)
    report = replay_trace(batcher, trace, admit_chunk=plan.admit_chunk,
                          ticks_per_s=ticks_per_s, seed=seed)
    print(f"[serve] replay: {report.completed} completed "
          f"({report.rejected} rejected), {report.ticks} ticks, "
          f"{report.tokens} tokens in {report.wall_s:.2f}s wall, "
          f"occupancy {report.mean_occupancy:.2f}, "
          f"latency p50={report.p50_latency_ms:.1f} ms "
          f"p99={report.p99_latency_ms:.1f} ms")
    if sim2real_eval:
        from repro.serving.scheduler import DrainStall

        try:
            pred = predicted_serving_report(model.cfg, trace, best_config)
        except DrainStall as e:
            # the replay above already drained — a simulator that cannot is
            # itself a sim-to-real finding, not a crash
            print(f"[serve] sim2real: simulator stalled pricing the "
                  f"deployed plan ({e}) while the replay drained — a "
                  f"fidelity gap worth investigating")
            return plan, launch_config, report
        if not pred.feasible:
            print(f"[serve] sim2real: simulator calls the deployed plan "
                  f"infeasible ({pred.reason}) — the replay measured it "
                  f"anyway, a fidelity gap worth investigating")
        else:
            print(f"[serve] sim2real: sim-predicted p99="
                  f"{pred.p99_latency_us:.0f} us modeled, occupancy "
                  f"{pred.occupancy_mean:.2f}, queue depth "
                  f"{pred.queue_depth_mean:.2f} | replayed-actual p99="
                  f"{report.p99_latency_ms:.1f} ms wall, occupancy "
                  f"{report.mean_occupancy:.2f}, queue depth "
                  f"{report.queue_depth_mean:.2f}")
    return plan, launch_config, report


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list_archs(), default="llama3.2-1b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--full-config", action="store_true")
    ap.add_argument("--tune-launch", type=int, default=0, metavar="BUDGET",
                    help="intervention budget for a kernel-launch tuning run "
                         "before serving (0 = serve with registry defaults)")
    ap.add_argument("--measure-backend", type=measure_backend_arg,
                    default=None,
                    help="target measurement backend for --tune-launch: "
                         "analytic, wallclock, or shifted:<kind> "
                         "(default: REPRO_MEASURE_BACKEND, then analytic)")
    ap.add_argument("--workload", default=None, metavar="SPEC",
                    help="request-trace spec (repro.workloads grammar, e.g. "
                         "'bursty:rate=2000'): replay it through the real "
                         "continuous batcher instead of a fixed batch")
    ap.add_argument("--tune-serving", type=int, default=0, metavar="BUDGET",
                    help="with --workload: intervention budget for a "
                         "serving-stack tuning run in the workload simulator "
                         "(0 = serve with the default plan)")
    ap.add_argument("--query-batch", type=int, default=1, metavar="K",
                    help="measurements per ask/tell tuning round for "
                         "--tune-launch / --tune-serving (1 = sequential)")
    ap.add_argument("--sim2real-eval", action="store_true",
                    help="with --workload: after the replay, price the "
                         "deployed configuration in the simulator too and "
                         "report sim-predicted vs replayed-actual")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="export a Chrome trace-event JSON of the run "
                         "(request lifecycle, tuner rounds, kernel dispatch) "
                         "— inspect with `python -m repro.obs.report PATH` "
                         "or chrome://tracing / Perfetto")
    args = ap.parse_args()
    enable_compile_cache()

    if args.trace_out:
        with obs_trace.trace_to(args.trace_out):
            rc = _run(args)
        print(f"[serve] trace written to {args.trace_out}")
        return rc
    return _run(args)


def _run(args) -> int:

    cfg = (get_model_config(args.arch) if args.full_config
           else get_smoke_config(args.arch))
    cache_len = args.prompt_len + args.gen
    run = RunConfig(model=cfg,
                    shape=ShapeConfig("serve_cli", cache_len, args.batch,
                                      "decode"),
                    mesh=MeshConfig(shape=(1,), axes=("data",)))
    model = build_model(cfg, run.parallel)
    params = model.init(jax.random.PRNGKey(0))
    print(f"[serve] {cfg.name}: {cfg.param_count()/1e6:.1f}M params, "
          f"batch={args.batch}")

    if args.workload:
        serve_workload(model, run, params, args.workload,
                       tune_budget=args.tune_serving,
                       query_batch=args.query_batch,
                       sim2real_eval=args.sim2real_eval)
        return 0

    data = make_data(cfg, run.shape, seed=0)
    raw = data.batch_at(0)
    batch = {"tokens": jnp.asarray(raw["inputs"][:args.batch,
                                                 :args.prompt_len])}
    if cfg.family == "vlm":
        batch["vision_embeds"] = jnp.asarray(raw["vision_embeds"][:args.batch])
    if cfg.family == "audio":
        batch["frames"] = jnp.asarray(raw["frames"][:args.batch])

    launch_config = None
    if args.tune_launch > 0:
        launch_config = tune_launch_config(cfg, args.batch, cache_len,
                                           args.tune_launch,
                                           args.measure_backend,
                                           query_batch=args.query_batch)
    prefill, decode = jitted_steps(model, run, cache_len=cache_len,
                                   launch_config=launch_config)

    # repro: ignore[wall-clock] -- serve-CLI latency printout; not part of the seeded tuning path
    t0 = time.perf_counter()
    state, logits = prefill(params, batch)
    jax.block_until_ready(logits)
    print(f"[serve] prefill {args.batch}x{args.prompt_len}: "
          # repro: ignore[wall-clock] -- serve-CLI latency printout; not part of the seeded tuning path
          f"{(time.perf_counter()-t0)*1000:.1f} ms")

    tok = sample_token(logits, jax.random.PRNGKey(1), args.temperature)
    lats = []
    outs = [tok]
    for i in range(args.gen - 1):
        # repro: ignore[wall-clock] -- serve-CLI latency printout; not part of the seeded tuning path
        t1 = time.perf_counter()
        state, logits = decode(params, state, tok[:, None])
        jax.block_until_ready(logits)
        # repro: ignore[wall-clock] -- serve-CLI latency printout; not part of the seeded tuning path
        lats.append(time.perf_counter() - t1)
        tok = sample_token(logits, jax.random.PRNGKey(2 + i),
                           args.temperature)
        outs.append(tok)
    lat = np.asarray(lats[1:]) * 1000
    print(f"[serve] decode p50={np.percentile(lat, 50):.2f} ms "
          f"p99={np.percentile(lat, 99):.2f} ms "
          f"({args.batch/np.mean(lat)*1000:.0f} tok/s)")
    print("[serve] sample:", np.asarray(jnp.stack(outs, 1))[0][:16])
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
