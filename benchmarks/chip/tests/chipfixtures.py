"""Fixtures of the chip benchmark's CPU tests, imported by each test file
(a ``conftest.py`` here would shadow the one under ``tests/``): the
benchmark's modules on the path, and a copy of the benchmark with a tiny
Mamba-1 configuration and a short chat mix added as files of their own."""

from __future__ import annotations

import copy
import json
import os
import shutil
import sys

import pytest

CHIP = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(os.path.dirname(CHIP))
for p in (CHIP, os.path.join(REPO, "src")):
    if p not in sys.path:
        sys.path.append(p)

INTERPRET = "pallas_interpret"

TINY_MIX = {
    "kind": "open_lognormal", "rate_per_s": 12.0,
    "prompt_median": 24, "prompt_sigma": 0.5, "prompt_min": 8,
    "prompt_max": 48, "prompt_round": 8, "order_block": 4,
    "schedule_seed": 5,
    "output_median": 16, "output_sigma": 0.5, "output_min": 4,
    "output_max": 32,
}

TINY_MODELS = {
    "tiny-ssm": dict(
        name="tiny-ssm", family="ssm", num_layers=2, d_model=64, num_heads=0,
        num_kv_heads=0, d_ff=0, vocab_size=128, attn_type="none",
        ssm_state=4, ssm_conv=4, ssm_expand=2, ssm_chunk=16,
        tie_embeddings=True, dtype="bfloat16"),
}
REFERENCE = {"tiny-ssm": "mamba1_lm"}
# widest logit gap, seeds 777-780 at this size and load (pallas_interpret on
# the CPU): program 0-0.0036, float8 control 0.0110-0.0234.  The limit sits
# between the two, nearer the control.
LIMIT = {"tiny-ssm": 0.007}


def tiny_config(name: str) -> dict:
    from repro.utils.config import ModelConfig
    return {
        "name": name, "source": "test", "reduced": [],
        "model": ModelConfig(**TINY_MODELS[name]).to_dict(),
        "serving": {"num_slots": 2, "page_size": 16,
                    "pages_per_slot_max": 5, "pool_pages": 10},
        "reference": REFERENCE[name],
        "correct": {"sample_requests": 8, "reference_len": 80,
                    "reference_batch": 2, "max_logit_gap": LIMIT[name]},
    }


def tiny_spec(spec: dict) -> dict:
    """``spec`` with a tiny cell on each tiny configuration."""
    spec = copy.deepcopy(spec)
    for name in TINY_MODELS:
        spec["configs"].append({"name": name, "source": "test",
                                "file": f"configs/{name}.json",
                                "reduced": [], "why": "CPU test"})
        spec["workloads"].append({"name": f"{name}-chat", "config": name,
                                  "traffic": "tiny-chat", "chips": 1,
                                  "why": "CPU test"})
    return spec


@pytest.fixture
def tiny_bench(tmp_path):
    """A copy of the benchmark with the tiny configurations and mix added."""
    import harness

    root = tmp_path / "chip"
    shutil.copytree(CHIP, root, ignore=shutil.ignore_patterns(
        "tests", "__pycache__"))
    for name in TINY_MODELS:
        (root / "configs" / f"{name}.json").write_text(
            json.dumps(tiny_config(name)))
    (root / "traffic" / "tiny-chat.json").write_text(json.dumps(TINY_MIX))
    spec = harness.load_json(os.path.join(REPO, "BENCHMARK.json"))
    return harness.Bench(tiny_spec(spec), str(root))


@pytest.fixture
def interpret(monkeypatch):
    """Pallas kernels in interpret mode, and a stand-in peak for the CPU:
    rooflines and utilizations need one, and a CPU run reports no device
    number.  Gives the kernel mode each served forward has to resolve to."""
    import peaks
    monkeypatch.setenv("REPRO_KERNEL_MODE", INTERPRET)
    monkeypatch.setitem(peaks.PEAKS, "cpu", dict(peaks.PEAKS["TPU v5 lite"]))
    return INTERPRET
