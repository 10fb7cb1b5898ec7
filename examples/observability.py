"""Trace a tuned sim-to-real replay run end to end with the obs subsystem.

Runs the minimal sim-to-real loop (tune in the simulator, spend the budget
on real replays) with request-lifecycle tracing enabled, exports a Chrome
trace-event JSON you can open in chrome://tracing or Perfetto, then
replays the *winning* configuration once more under a fresh tracer and
prints its queue / prefill / decode time breakdown plus the tuner-round
trajectory.

    PYTHONPATH=src python examples/observability.py
    PYTHONPATH=src python examples/observability.py \
        --trace-out /tmp/tuned_replay_trace.json --budget 4

Inspect the exported file with the report CLI:

    PYTHONPATH=src python -m repro.obs.report /tmp/tuned_replay_trace.json
"""

import argparse

from repro.envs.replay_env import make_sim2real_pair
from repro.obs import report as obs_report
from repro.obs import trace as obs_trace
from repro.tuner.runner import transfer_tune

DEFAULT_WORKLOAD = ("poisson:rate=1500,horizon=0.004,mean_prompt=6,"
                    "mean_output=4,max_len=16")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default=DEFAULT_WORKLOAD)
    ap.add_argument("--budget", type=int, default=3,
                    help="real-replay intervention budget")
    ap.add_argument("--n-source", type=int, default=24,
                    help="cheap simulator observations")
    ap.add_argument("--trace-out", default="/tmp/tuned_replay_trace.json",
                    help="Chrome trace-event JSON for the full tuned run")
    args = ap.parse_args()

    src, tgt = make_sim2real_pair(args.workload, seed=0, repeats=1)
    print(f"trace: {len(tgt.trace)} requests ({tgt.workload_spec})")

    # 1. the full tuned run, traced end to end: simulator observations,
    #    tuner ask/tell rounds, warmup, and every real replay lifecycle
    with obs_trace.trace_to(args.trace_out):
        res = transfer_tune("cameo", src, tgt, budget=args.budget,
                            n_source=args.n_source, n_target_init=2,
                            query_text=tgt.query_text, seed=0)
        tuner_rounds = list(obs_trace.active().tuner_rounds)
    print(f"\ntuned: best replayed p99={res.best_y:.1f} ms wall "
          f"({res.wall_s:.1f}s); full trace -> {args.trace_out}")

    print(f"\ntuner trajectory ({len(tuner_rounds)} events):")
    for ev in tuner_rounds:
        kind = ev.get("kind")
        rnd = ev.get("round")
        if kind == "ask":
            print(f"  round {rnd}: ask k={ev.get('k')} "
                  f"eps={ev.get('eps')} kinds={ev.get('kinds')} "
                  f"candidates={ev.get('n_candidates')}")
        else:
            by = ev.get("best_y")
            print(f"  round {rnd}: tell told={ev.get('told')} "
                  f"best_y={f'{by:.1f}' if by is not None else 'n/a'} "
                  f"graph_refreshed={ev.get('graph_refreshed')}")

    # 2. replay ONLY the winning configuration under a fresh tracer and
    #    break its wall time down by lifecycle stage
    winner = res.best_config or tgt.space.default_config()
    tracer = obs_trace.start()
    try:
        _, y_win = tgt.intervene(winner)
    finally:
        events = tracer.events()
        obs_trace.stop()
    stats = obs_report.span_stats(events)
    print(f"\nwinning config replayed at {y_win:.1f} ms wall; "
          f"lifecycle breakdown:")
    for name in ("serve.queue", "serve.prefill", "prefill_chunk",
                 "serve.decode"):
        s = stats.get(name)
        if s is None:
            continue
        print(f"  {name:14s} n={s['count']:4d} total={s['total_us']/1e3:9.2f} ms "
              f"mean={s['mean_us']/1e3:7.3f} ms max={s['max_us']/1e3:7.3f} ms")
    lats = obs_report.request_latencies(events)
    if lats:
        lat_ms = sorted(v / 1e3 for v in lats.values())
        print(f"  {len(lat_ms)} completed requests, "
              f"p50={lat_ms[len(lat_ms) // 2]:.2f} ms "
              f"max={lat_ms[-1]:.2f} ms")


if __name__ == "__main__":
    main()
