import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", ""))

"""Multi-pod dry-run: lower + compile every (arch x shape) cell on the
production meshes, with zero real allocation (ShapeDtypeStruct inputs).

The lines above MUST run before any other import (jax picks its platform and
locks the device count on first init) — which is why this flag lives here
and nowhere else; smoke tests and benches see 1 device.  The dry run lowers
on 512 virtual CPU devices, so it pins itself to the CPU: on a machine with
a chip it must neither take the chip nor get the TPU backend.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch llama3.2-1b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all               # 40-cell sweep
    PYTHONPATH=src python -m repro.launch.dryrun --all --multi-pod   # 2x16x16

Per cell this records to artifacts/dryrun/:
    memory_analysis (proves the cell fits 16 GB/chip),
    cost_analysis (XLA's numbers, unscaled),
    hlo_analysis (our while-scaled per-chip FLOPs / bytes / collective bytes),
    the collective schedule head.
"""

import argparse
import json
import time
import traceback
from typing import Dict, Optional

import jax

from repro.configs.registry import (
    all_cells, arch_shapes, default_parallel, input_specs, list_archs,
    make_run)
from repro.launch.build import lower_step
from repro.launch.hlo_analysis import analyze_hlo, collective_schedule
from repro.launch.mesh import make_mesh
from repro.utils.config import ParallelConfig

ARTIFACT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                            "artifacts", "dryrun")


def parallel_overrides(par: ParallelConfig, kv: Optional[str]) -> ParallelConfig:
    if not kv:
        return par
    out = {}
    for item in kv.split(","):
        k, v = item.split("=", 1)
        cur = getattr(par, k)
        if isinstance(cur, bool):
            out[k] = v.lower() in ("1", "true", "yes")
        elif isinstance(cur, int):
            out[k] = int(v)
        else:
            out[k] = v
    return par.replace(**out)


def dryrun_cell(arch: str, shape: str, *, multi_pod: bool = False,
                par_kv: Optional[str] = None, save: bool = True,
                tag: str = "", mesh_kv: Optional[str] = None) -> Dict:
    t0 = time.time()
    run = make_run(arch, shape, multi_pod=multi_pod)
    run = run.replace(parallel=parallel_overrides(run.parallel, par_kv))
    if mesh_kv:
        # logical re-mesh of the same chips, e.g. "64x4" -> data=64, model=4
        from repro.utils.config import MeshConfig
        dims = tuple(int(x) for x in mesh_kv.split("x"))
        axes = (("pod", "data", "model") if len(dims) == 3
                else ("data", "model"))
        run = run.replace(mesh=MeshConfig(shape=dims, axes=axes))
    run.validate()
    mesh = make_mesh(run.mesh)
    chips = run.mesh.num_devices

    bundle, lowered = lower_step(run, mesh)
    t_lower = time.time() - t0
    compiled = lowered.compile()
    t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    hlo_text = compiled.as_text()
    costs = analyze_hlo(hlo_text)
    sched = collective_schedule(hlo_text, limit=24)

    rec = {
        "arch": arch,
        "shape": shape,
        "kind": bundle.kind,
        "mesh": {"shape": list(run.mesh.shape), "axes": list(run.mesh.axes)},
        "chips": chips,
        "parallel": run.parallel.to_dict(),
        "status": "ok",
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "memory_analysis": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "peak_bytes": mem.peak_memory_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
        },
        "xla_cost_analysis": {
            "flops": cost.get("flops", 0.0),
            "bytes_accessed": cost.get("bytes accessed", 0.0),
        },
        "hlo_analysis": {
            "flops_per_chip": costs.flops,
            "bytes_per_chip": costs.bytes_accessed,
            "collective_bytes_per_chip": costs.collective_bytes,
            "collective_count": costs.collective_count,
            "total_collective_bytes_per_chip": costs.total_collective_bytes,
        },
        "collective_schedule_head": sched,
    }
    print(f"[dryrun] {arch} x {shape} ({'2x16x16' if multi_pod else '16x16'}"
          f"{' ' + tag if tag else ''}): OK  "
          f"flops/chip={costs.flops:.3e}  bytes/chip={costs.bytes_accessed:.3e}  "
          f"coll/chip={costs.total_collective_bytes:.3e}  "
          f"args+temp={(mem.argument_size_in_bytes + mem.temp_size_in_bytes)/2**30:.2f}GiB "
          f"(lower {t_lower:.0f}s compile {t_compile:.0f}s)")
    if save:
        os.makedirs(ARTIFACT_DIR, exist_ok=True)
        name = f"{arch}__{shape}__{'multipod' if multi_pod else 'pod'}"
        if tag:
            name += f"__{tag}"
        with open(os.path.join(ARTIFACT_DIR, name + ".json"), "w") as f:
            json.dump(rec, f, indent=1)
    return rec


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", choices=list_archs())
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true", help="sweep all cells")
    ap.add_argument("--parallel", help="comma list of ParallelConfig overrides, "
                                       "e.g. tp=8,remat=dots,microbatch=2")
    ap.add_argument("--mesh", help="logical re-mesh of the same chips, "
                                   "e.g. 64x4 (data x model)")
    ap.add_argument("--tag", default="", help="artifact suffix for perf iters")
    args = ap.parse_args()

    failures = []
    if args.all:
        for arch in list_archs():
            for shape in arch_shapes(arch):
                try:
                    dryrun_cell(arch, shape, multi_pod=args.multi_pod,
                                par_kv=args.parallel, tag=args.tag)
                except (ValueError, KeyError, TypeError,
                        RuntimeError) as e:
                    # RuntimeError covers jax's XlaRuntimeError (compile /
                    # lowering failures); the rest are config-cell bugs.
                    # Recorded on the report and surfaced via exit code —
                    # anything else (KeyboardInterrupt, MemoryError)
                    # propagates and kills the sweep.
                    failures.append((arch, shape, repr(e)))
                    print(f"[dryrun] {arch} x {shape}: FAIL {e}")
                    traceback.print_exc()
        print(f"[dryrun] sweep done, {len(failures)} failures")
        for f in failures:
            print("  FAIL:", f)
        return 1 if failures else 0

    if not args.arch or not args.shape:
        ap.error("--arch and --shape required (or --all)")
    dryrun_cell(args.arch, args.shape, multi_pod=args.multi_pod,
                par_kv=args.parallel, tag=args.tag, mesh_kv=args.mesh)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
