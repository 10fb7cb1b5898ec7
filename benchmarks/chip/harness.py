"""What the chip benchmark shares: where its files are, how it finds a
configuration, a traffic mix, a per-layer metric or a kernel's counts by
name, and how it turns ``--seed`` into keys.

Everything one configuration, traffic mix, metric or kernel needs sits in a
file of its own under this directory; the harness loads it by the name that
``BENCHMARK.json`` gives, so adding one is adding a file:

    configs/<config>.json        sizes as run, serving geometry, limits
    reference/<module>.py        plain float32 reference a config names
    traffic/<traffic>.json       one mix: a generator kind and its parameters
    traffic/kinds/<kind>.py      a seeded generator: ``generate(mix, seed,
                                 seconds, vocab) -> [TimedRequest]``
    metrics/<metric>.py          ``read(run) -> float | None``
    kernels/<family>.py          ``cost(...) -> (flops, bytes)`` from shapes

``root`` defaults to this directory; tests pass a copy to show that a new
file needs no edit elsewhere.
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys
from types import ModuleType
from typing import Any, Dict, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))


def import_program() -> bool:
    """Put the program under test (``src/repro``) on the path; False where
    this checkout does not hold it."""
    src = os.path.join(REPO, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        return False
    if src not in sys.path:
        sys.path.insert(0, src)
    return True


def load_json(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def load_module(path: str) -> ModuleType:
    """Import one file by path under a private name (file names here may
    hold ``-`` and ``.``, as the benchmark's names do)."""
    if not os.path.isfile(path):
        raise FileNotFoundError(path)
    name = "chipbench_" + os.path.relpath(path, HERE).replace(
        os.sep, "_").replace(".", "_").replace("-", "_")
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    try:
        spec.loader.exec_module(mod)
    except BaseException:
        del sys.modules[name]
        raise
    return mod


class Bench:
    """``BENCHMARK.json`` and the files its names point at."""

    def __init__(self, spec: Dict[str, Any], root: str = HERE):
        self.spec = spec
        self.root = root

    @classmethod
    def from_repo(cls, repo: str = REPO, root: str = HERE) -> "Bench":
        return cls(load_json(os.path.join(repo, "BENCHMARK.json")), root)

    def cell(self, name: str) -> Dict[str, Any]:
        for w in self.spec["workloads"]:
            if w["name"] == name:
                return w
        known = [w["name"] for w in self.spec["workloads"]]
        raise KeyError(f"no workload {name!r}; known: {known}")

    def config(self, name: str) -> Dict[str, Any]:
        return load_json(os.path.join(self.root, "configs", name + ".json"))

    def reference(self, module: str) -> ModuleType:
        refdir = os.path.join(self.root, "reference")
        if refdir not in sys.path:
            sys.path.insert(0, refdir)
        return load_module(os.path.join(refdir, module + ".py"))

    def traffic(self, name: str) -> Dict[str, Any]:
        return load_json(os.path.join(self.root, "traffic", name + ".json"))

    def traffic_kind(self, kind: str) -> ModuleType:
        return load_module(os.path.join(self.root, "traffic", "kinds",
                                        kind + ".py"))

    def kernel(self, family: str) -> ModuleType:
        return load_module(os.path.join(self.root, "kernels", family + ".py"))

    def metric_reader(self, name: str) -> ModuleType:
        return load_module(os.path.join(self.root, "metrics", name + ".py"))

    def metrics_for(self, section: str, cell: str) -> list:
        """The ``end_to_end`` or ``per_layer`` entries that ``cell`` reports."""
        out = []
        for m in self.spec[section]:
            if "workloads" not in m or cell in m["workloads"]:
                out.append(m)
        return out

    def per_layer_metrics(self, cell: str, ctx: Any) -> Dict[str, Any]:
        """Run each per-layer reader the cell reports; a reader that finds
        nothing returns None and its metric is left out."""
        out = {}
        for m in self.metrics_for("per_layer", cell):
            value = self.metric_reader(m["name"]).read(ctx)
            if value is not None:
                out[m["name"]] = {"value": float(value), "unit": m["unit"]}
        return out


def seed_words(seed: int, n: int = 2):
    """``--seed`` (any whole number, also past 2**32) as ``n`` uint32 words."""
    import numpy as np
    return np.random.SeedSequence(abs(int(seed))).generate_state(n)


def jax_key(seed: int, salt: int = 0):
    """A JAX key from ``--seed``: the same seed gives the same key."""
    import jax
    import numpy as np
    w = seed_words(seed, 2 + salt)[-2:]
    return jax.random.wrap_key_data(np.asarray(w, np.uint32),
                                    impl="threefry2x32")


def numpy_rng(seed: int, salt: int = 0):
    import numpy as np
    return np.random.default_rng([abs(int(seed)), salt])


def model_config(cfg_file: Dict[str, Any]):
    """The program's ``ModelConfig`` from a config file's ``model`` block."""
    from repro.utils.config import ModelConfig
    return ModelConfig(**cfg_file["model"])


def say(msg: str) -> None:
    print(f"[chipbench] {msg}", file=sys.stderr, flush=True)


def device_info(devices, peak_bytes: Optional[int]) -> Dict[str, Any]:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices), "memory_peak_bytes": peak_bytes}


def peak_bytes(devices) -> Optional[int]:
    """Peak bytes in use on the fullest chip, where the backend reports it."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None
