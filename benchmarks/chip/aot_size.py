#!/usr/bin/env python3
"""Compile a serving configuration's decode step and its longest prefill
for one v5e chip and print what XLA's memory analysis says of each: the
recorded source of each configuration's slot and page-pool sizes.

    python3 benchmarks/chip/aot_size.py --config mamba-2.8b
    python3 benchmarks/chip/aot_size.py --config mamba-2.8b --num-slots 32

On a machine with a TPU it compiles for the attached chip; elsewhere for a
described ``v5e:2x2`` topology's first chip (nothing runs, so no time is
measured either way).  Shapes only: no weight is made.  The optional
geometry replaces the file's.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import harness  # noqa: E402

FIELDS = ("argument_size_in_bytes", "output_size_in_bytes",
          "temp_size_in_bytes", "alias_size_in_bytes",
          "generated_code_size_in_bytes", "peak_memory_in_bytes")


def _device():
    import jax
    if jax.devices()[0].platform == "tpu":
        return jax.devices()[0]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    return topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2").devices[0]


def analyse(cfg_file, prefill_len: int) -> dict:
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from repro.kernels import dispatch
    from repro.models.model import build_model
    from repro.serving.paging import PagedPlan
    from repro.train.serve_step import ServeState, jitted_steps
    from repro.utils.config import RunConfig, ShapeConfig

    os.environ.setdefault(dispatch.KERNEL_MODE_ENV, dispatch.PALLAS)
    geo = cfg_file["serving"]
    mc = harness.model_config(cfg_file)
    plan = PagedPlan(paging=True, pool_pages=geo["pool_pages"],
                     page_size=geo["page_size"],
                     pages_per_slot_max=geo["pages_per_slot_max"])
    slots = geo["num_slots"]
    run = RunConfig(model=mc, shape=ShapeConfig(
        "chipbench", plan.slot_capacity, slots, "decode"))
    model = build_model(mc, run.parallel)
    prefill, decode = jitted_steps(model, run, cache_len=plan.slot_capacity)
    one = SingleDeviceSharding(_device())
    place = lambda t: jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one), t)
    params = place(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    caches = place(jax.eval_shape(
        lambda: model.init_paged_decode_state(
            slots, plan.pool_pages, plan.page_size, plan.pages_per_slot_max)))
    state = ServeState(caches, jax.ShapeDtypeStruct((slots,), jnp.int32,
                                                    sharding=one), {})
    toks = lambda shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one)
    out = {"geometry": geo}
    for name, lowered in (
            ("decode", decode.lower(params, state, toks((slots, 1)))),
            (f"prefill_{prefill_len}",
             prefill.lower(params, {"tokens": toks((1, prefill_len))}))):
        ma = lowered.compile().memory_analysis()
        out[name] = {f: int(getattr(ma, f)) for f in FIELDS}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--prefill-len", type=int, default=2048)
    for k in ("num_slots", "page_size", "pages_per_slot_max", "pool_pages"):
        ap.add_argument("--" + k.replace("_", "-"), type=int)
    args = ap.parse_args(argv)
    if not harness.import_program():
        print("no program (src/repro) in this checkout", file=sys.stderr)
        return 2
    bench = harness.Bench({"workloads": []})
    cfg_file = bench.config(args.config)
    for k in ("num_slots", "page_size", "pages_per_slot_max", "pool_pages"):
        if getattr(args, k) is not None:
            cfg_file["serving"][k] = getattr(args, k)
    print(json.dumps(analyse(cfg_file, args.prefill_len)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
