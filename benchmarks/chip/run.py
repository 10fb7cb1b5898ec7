#!/usr/bin/env python3
"""Run one cell of the chip benchmark once.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``; its
configuration, traffic mix and per-layer metrics are files under this
directory, found by name (see ``harness.py``).  The run holds the chip in
this one process: set-up (weights from the seed, compiles or cache loads,
warm-up), a window of ``--seconds`` on the wall clock, then the check of
what was served against the plain reference.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1``
also ``breakdown``, and last ``compared``: each number checked with its
limit.  The same numbers are the last lines of standard error.  Without a
TPU, with fewer chips than the cell asks for, or outside a checkout that
holds the program (``src/repro``), it exits non-zero and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import harness  # noqa: E402


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def check_chips(cell, devices) -> str:
    """Empty where the devices can run the cell, else why not."""
    if devices[0].platform != "tpu":
        return f"no TPU: JAX found {devices[0].platform!r}"
    if len(devices) < cell["chips"]:
        return (f"cell {cell['name']} needs {cell['chips']} chips, "
                f"JAX found {len(devices)}")
    return ""


def run(args) -> dict:
    """One run; returns the result object (the last line's fields)."""
    import jax

    import serve_driver

    bench = harness.Bench.from_repo()
    cell = bench.cell(args.workload)
    devices = jax.devices()[:cell["chips"]]
    why = check_chips(cell, jax.devices())
    if why:
        raise SystemExit(why)
    harness.say(f"compile cache: {serve_driver.enable_cache()}")
    harness.say(f"{cell['name']}: {len(devices)} x "
                f"{devices[0].device_kind}, seed {args.seed}, "
                f"{args.seconds}s, trace {args.trace}")
    res = serve_driver.run_cell(bench, cell, args.seed, args.seconds,
                                bool(args.trace), T_START)
    order = ["correct", "attempted", "failed", "metrics", "device",
             "breakdown", "compared"]
    return {k: res[k] for k in order if k in res}


def main(argv=None) -> int:
    args = parse(argv)
    if not os.path.isfile(os.path.join(harness.REPO, "BENCHMARK.json")):
        print("no BENCHMARK.json at the root of this checkout",
              file=sys.stderr)
        return 2
    if not harness.import_program():
        print("this checkout does not hold the program (src/repro)",
              file=sys.stderr)
        return 2
    try:
        result = run(args)
    except SystemExit as e:
        print(e, file=sys.stderr)
        return 1
    for name, c in result["compared"].items():
        print(f"compared {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
