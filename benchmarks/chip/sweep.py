#!/usr/bin/env python3
"""Find the highest rate a serving cell sustains: one process, one set-up,
a window at each offered rate.  The cell's rate is then fixed at about four
fifths of it in the cell's traffic file; this script is how that number was
found, not part of a run.

    python3 benchmarks/chip/sweep.py --workload <cell> --seed <n> \\
        --seconds <s> --rates 0.3,0.4,0.5

For each rate it prints one JSON line: offered and delivered output tokens
per second, time to first token (p50, p90) of the requests due in each half
of the window, and how many requests due in the window had no first token
when it closed.  A rate is sustained while the second half's TTFT does not
grow past the first's and that backlog stays near zero.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    if not harness.import_program():
        print("no program (src/repro) in this checkout", file=sys.stderr)
        return 2
    import jax
    import numpy as np

    import serve_driver

    if jax.devices()[0].platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 1
    serve_driver.enable_cache()
    bench = harness.Bench.from_repo()
    cell = bench.cell(args.workload)
    cfg_file = bench.config(cell["config"])
    mix = bench.traffic(cell["traffic"])
    kind = bench.traffic_kind(mix["kind"])
    system = serve_driver.System(cfg_file, args.seed)
    for rate in [float(r) for r in args.rates.split(",")]:
        m = dict(mix, rate_per_s=rate)
        reqs = kind.generate(m, args.seed, args.seconds, system.mc.vocab_size)
        b = system.batcher()
        serve_driver.warm(system, b, reqs)
        t0 = time.perf_counter()
        win = serve_driver.serve_window(b, reqs, args.seconds)
        half = args.seconds / 2
        due = [s for s in win["served"] if s.due_s < args.seconds]
        ttft = lambda ss: [((s.token_s[0] if s.token_s else win["window_s"])
                            - s.due_s) * 1e3 for s in ss]
        first = ttft([s for s in due if s.due_s < half])
        second = ttft([s for s in due if s.due_s >= half])
        offered = sum(r.max_new_tokens for r in reqs) / args.seconds
        print(json.dumps({
            "rate_per_s": rate, "requests": len(due),
            "offered_tok_s": offered,
            "output_tok_s": win["output_tok_s"],
            "ttft_p50_ms": [float(np.percentile(first, 50)),
                            float(np.percentile(second, 50))],
            "ttft_p90_ms": [float(np.percentile(first, 90)),
                            float(np.percentile(second, 90))],
            "itl_p95_ms": float(np.percentile(win["itl_ms"], 95)),
            "no_first_token": sum(1 for s in due if not s.token_s),
            "occupancy": win["occupancy_sum"] / max(win["batcher_ticks"], 1),
            "wall_s": time.perf_counter() - t0}), flush=True)
        del b
    return 0


if __name__ == "__main__":
    sys.exit(main())
