#!/usr/bin/env python3
"""Readings from which a serving cell's ``correct`` limit is set: for each
seed, the widest logit gap of what the program served (the number a run
compares) and the widest gap of the control, the float32 reference with its
projections rounded to float8 put in the program's place over the same
prompts and tokens (``correct.py``).  A limit has to sit above every sound
reading of the program and below every reading of the control.

    python3 benchmarks/chip/control.py --workload <cell> \\
        --seeds 101,102,103 --seconds 45

One process, one compile: each seed makes its own weights and traffic, runs
a window at the cell's own load, and prints one JSON line.  The benchmark's
runs do not run this.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import harness  # noqa: E402


def readings_for_seed(bench, cell, seed: int, seconds: float,
                      quant: str = "fp8", kernel_mode: str = "pallas") -> dict:
    """One seed: a window of the program at the cell's load, then the
    reference's gaps of the served tokens and of the control's picks."""
    import numpy as np

    import correct
    import serve_driver

    cfg_file = bench.config(cell["config"])
    mix = bench.traffic(cell["traffic"])
    system = serve_driver.System(cfg_file, seed, kernel_mode)
    reqs = bench.traffic_kind(mix["kind"]).generate(
        mix, seed, seconds, system.mc.vocab_size)
    b = system.batcher()
    serve_driver.warm(system, b, reqs)
    win = serve_driver.serve_window(b, reqs, seconds)
    del b
    gc.collect()
    spec = cfg_file["correct"]
    sample = correct.sample(win["served"], seed, spec["sample_requests"])
    ref = bench.reference(cfg_file["reference"])
    got = correct.readings(ref, system.params, cfg_file["model"], sample,
                           spec["reference_len"], spec["reference_batch"],
                           quant=quant)
    return {"seed": seed, "requests": len(sample),
            "tokens": int(len(got["served"])),
            "program_gap": float(np.max(got["served"])),
            "control_gap": float(np.max(got["control"])),
            "program_top1": float(np.mean(got["served"] == 0.0)),
            "control_top1": float(np.mean(got["control"] == 0.0)),
            "limit": float(spec["max_logit_gap"])}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    if not harness.import_program():
        print("no program (src/repro) in this checkout", file=sys.stderr)
        return 2
    import jax

    import serve_driver

    if jax.devices()[0].platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 1
    serve_driver.enable_cache()
    bench = harness.Bench.from_repo()
    cell = bench.cell(args.workload)
    for seed in [int(s) for s in args.seeds.split(",")]:
        t0 = time.perf_counter()
        out = readings_for_seed(bench, cell, seed, args.seconds)
        out["wall_s"] = time.perf_counter() - t0
        print(json.dumps(out), flush=True)
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
