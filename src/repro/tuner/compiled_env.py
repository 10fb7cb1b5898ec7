"""CompiledPerfEnv — the ground-truth tuning backend.

``intervene(config)`` lowers + compiles the actual train/serve step for one
(arch x shape) cell under the chosen parallel plan (in a subprocess, because
the 512-device XLA flag must be set before jax initializes) and returns the
three-term roofline estimate from the compiled HLO as the objective, with
the roofline terms as system-event counters.

This is exactly the paper's "production environment is expensive to query"
setting: one intervention costs a full XLA compile (tens of seconds), which
is why CAMEO warm-starts from the cheap AnalyticTPUEnv source.

The child runs on the CPU (``JAX_PLATFORMS=cpu``), never on a chip the
parent may hold.  A child that fails on a sharding or divisibility error
marks the configuration infeasible (``inf``); any other failure is a crash
and raises, instead of passing for an infeasible configuration.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile
from typing import Any, Dict, Optional, Tuple

import numpy as np

from repro.core.spaces import ConfigSpace
from repro.envs.base import PooledEnv
from repro.tuner.space import config_to_parallel_kv, framework_space
from repro.utils.hardware import TPU_V5E, HardwareSpec

_REPO_SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))

# what a dry run that failed because the configuration cannot be laid out on
# the mesh prints; anything else in a failed child is a crash
_INFEASIBLE_MARKERS = ("divisible", "sharding", "Sharding", "mesh",
                       "RESOURCE_EXHAUSTED")


class DryRunCrash(RuntimeError):
    """The dry-run child failed for a reason other than an infeasible
    configuration."""


def _failure_is_infeasible(stderr: str) -> bool:
    lines = [ln for ln in stderr.strip().splitlines() if ln.strip()]
    return bool(lines) and any(m in lines[-1] for m in _INFEASIBLE_MARKERS)


def make_aligned_source(arch: str = "llama3.2-1b", seed: int = 0):
    """An AnalyticTPUEnv whose option vocabulary matches the framework's
    (``seq_parallel`` -> ``sp``, ``int8`` -> ``int8_ef``), so its
    observational dataset transfers onto ``framework_space`` by name."""
    from repro.core.spaces import ConfigSpace, Option
    from repro.envs.analytic import AnalyticTPUEnv, TPUEnvSpec

    rename = {"seq_parallel": "sp"}
    value_map = {"grad_compression": {"int8": "int8_ef"}}

    class AlignedAnalyticEnv(AnalyticTPUEnv):
        def __init__(self):
            base_arch = arch if arch in ("llama3.2-1b", "nemotron-4-15b",
                                         "command-r-35b", "falcon-mamba-7b",
                                         "deepseek-v3-671b") else "llama3.2-1b"
            super().__init__(TPUEnvSpec(arch=base_arch), seed=seed)
            opts = []
            for o in self.space.options:
                name = rename.get(o.name, o.name)
                vals = tuple(value_map.get(o.name, {}).get(v, v)
                             for v in o.values)
                dflt = value_map.get(o.name, {}).get(o.default, o.default)
                opts.append(Option(name, vals, default=dflt, kind=o.kind))
            self.space = ConfigSpace(opts)

        def _measure(self, config):
            inner = {}
            inv_rename = {v: k for k, v in rename.items()}
            for k, v in config.items():
                ik = inv_rename.get(k, k)
                if ik in value_map:
                    inv_vals = {nv: ov for ov, nv in value_map[ik].items()}
                    v = inv_vals.get(v, v)
                inner[ik] = v
            return super()._measure(inner)

    return AlignedAnalyticEnv()


class CompiledPerfEnv(PooledEnv):
    counter_names = ("compute_s", "memory_s", "collective_s",
                     "flops_per_chip", "hbm_bytes", "collective_bytes",
                     "peak_mem_gb")

    def __init__(self, arch: str, shape: str, *, multi_pod: bool = False,
                 hardware: HardwareSpec = TPU_V5E, seed: int = 0,
                 timeout_s: int = 1200, cache_dir: Optional[str] = None):
        from repro.configs.registry import get_model_config

        self.arch = arch
        self.shape_name = shape
        self.multi_pod = multi_pod
        self.hw = hardware
        self.timeout_s = timeout_s
        cfg = get_model_config(arch)
        kind = "train" if shape.startswith("train") else (
            "prefill" if shape.startswith("prefill") else "decode")
        space = framework_space(cfg, kind)
        super().__init__(space, self.counter_names, seed=seed, pool_size=64)
        self.cache_dir = cache_dir or os.path.join(
            tempfile.gettempdir(), "repro_compiled_env")
        os.makedirs(self.cache_dir, exist_ok=True)

    def _cache_key(self, kv: str) -> str:
        safe = kv.replace("=", "-").replace(",", "_") or "default"
        return os.path.join(
            self.cache_dir,
            f"{self.arch}__{self.shape_name}__{safe}.json")

    def _measure(self, config) -> Tuple[Dict[str, float], float]:
        kv = config_to_parallel_kv(config)
        cache = self._cache_key(kv)
        if os.path.exists(cache):
            with open(cache) as f:
                rec = json.load(f)
        else:
            cmd = [sys.executable, "-m", "repro.launch.dryrun",
                   "--arch", self.arch, "--shape", self.shape_name,
                   "--tag", "tuner"]
            if kv:
                cmd += ["--parallel", kv]
            if self.multi_pod:
                cmd += ["--multi-pod"]
            env = dict(os.environ, PYTHONPATH=_REPO_SRC, JAX_PLATFORMS="cpu")
            try:
                proc = subprocess.run(cmd, capture_output=True, text=True,
                                      timeout=self.timeout_s, env=env)
            except subprocess.TimeoutExpired:
                return {n: 0.0 for n in self.counter_names}, float("inf")
            if proc.returncode != 0:
                if not _failure_is_infeasible(proc.stderr):
                    raise DryRunCrash(
                        f"dry run of {self.arch} x {self.shape_name} "
                        f"[{kv or 'default'}] crashed (exit "
                        f"{proc.returncode}):\n{proc.stderr[-4000:]}")
                return {n: 0.0 for n in self.counter_names}, float("inf")
            art = os.path.join(_REPO_SRC, "..", "artifacts", "dryrun",
                               f"{self.arch}__{self.shape_name}__"
                               f"{'multipod' if self.multi_pod else 'pod'}__tuner.json")
            with open(art) as f:
                rec = json.load(f)
            with open(cache, "w") as f:
                json.dump(rec, f)

        h = rec["hlo_analysis"]
        compute_s = h["flops_per_chip"] / self.hw.peak_flops_bf16
        memory_s = h["bytes_per_chip"] / self.hw.hbm_bandwidth
        coll_s = h["total_collective_bytes_per_chip"] / self.hw.ici_bandwidth
        peak_gb = (rec["memory_analysis"]["argument_bytes"]
                   + rec["memory_analysis"]["temp_bytes"]) / rec["chips"] / 2**30
        step = max(compute_s, memory_s, coll_s)  # no-overlap roofline bound
        counters = {
            "compute_s": compute_s, "memory_s": memory_s,
            "collective_s": coll_s,
            "flops_per_chip": h["flops_per_chip"],
            "hbm_bytes": h["bytes_per_chip"],
            "collective_bytes": h["total_collective_bytes_per_chip"],
            "peak_mem_gb": peak_gb,
        }
        if peak_gb > self.hw.hbm_capacity / 2**30:
            return counters, float("inf")
        return counters, float(step)
