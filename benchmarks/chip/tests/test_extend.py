"""A later change adds a configuration, a traffic kind and mix, a per-layer
metric and a kernel's counts as new files, and names them in
``BENCHMARK.json``; no file the benchmark already has is edited."""

from __future__ import annotations

import hashlib
import json
import os
import time

from chipfixtures import interpret, tiny_bench  # noqa: F401
import serve_driver

KIND = '''
import numpy as np
from open_lognormal_copy import TimedRequest


def generate(mix, seed, seconds, vocab):
    rng = np.random.default_rng(seed)
    n = int(mix["rate_per_s"] * seconds)
    return [TimedRequest(i, i / mix["rate_per_s"],
                         rng.integers(0, vocab, mix["prompt"], dtype=np.int32),
                         mix["output"]) for i in range(n)]
'''

KERNEL = '''
def cost(rows, width, itemsize=2):
    return 4.0 * rows * width, 2.0 * rows * width * itemsize + 4.0 * width
'''

METRIC = '''
def read(run):
    rows = sum(len(t.decode_lens) for t in run.window["tick_records"])
    flops, _ = run.bench.kernel("rmsnorm").cost(rows, run.model["d_model"])
    return flops / 1e6
'''


def _digests(root):
    out = {}
    for d, _, files in os.walk(root):
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


def test_new_files_need_no_edit(tiny_bench, interpret, monkeypatch):
    root = tiny_bench.root
    before = _digests(root)
    kinds = os.path.join(root, "traffic", "kinds")
    with open(os.path.join(kinds, "open_lognormal.py")) as f:
        src = f.read()
    # the new kind keeps its own copy of what it shares
    with open(os.path.join(kinds, "open_lognormal_copy.py"), "w") as f:
        f.write(src)
    monkeypatch.syspath_prepend(kinds)
    with open(os.path.join(kinds, "steady.py"), "w") as f:
        f.write(KIND)
    with open(os.path.join(root, "traffic", "steady-short.json"), "w") as f:
        json.dump({"kind": "steady", "rate_per_s": 3.0, "prompt": 16,
                   "output": 4}, f)
    with open(os.path.join(root, "kernels", "rmsnorm.py"), "w") as f:
        f.write(KERNEL)
    with open(os.path.join(root, "metrics", "decode_norm_mflop.py"),
              "w") as f:
        f.write(METRIC)
    spec = tiny_bench.spec
    spec["workloads"].append({"name": "tiny-ssm-steady", "config": "tiny-ssm",
                              "traffic": "steady-short", "chips": 1,
                              "why": "test"})
    spec["per_layer"].append({"name": "decode_norm_mflop", "unit": "MFLOP",
                              "better": "higher", "source": "program_counter",
                              "layer": "kernels", "moves": "itl_p95_ms",
                              "workloads": ["tiny-ssm-steady"]})
    res = serve_driver.run_cell(tiny_bench, tiny_bench.cell("tiny-ssm-steady"),
                                seed=5, seconds=3.0, trace=True,
                                t_start=time.perf_counter(),
                                kernel_mode=interpret)
    assert res["metrics"]["decode_norm_mflop"]["value"] > 0
    assert res["metrics"]["decode_norm_mflop"]["unit"] == "MFLOP"
    assert "mamba_scan_roofline" not in res["metrics"]   # not its cell
    after = _digests(root)
    assert {k: v for k, v in after.items() if k in before} == before
