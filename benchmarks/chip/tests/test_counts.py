"""Kernel operation and byte counts follow from shapes and the model's
math alone, and the reference's FLOP counts from widths that give the
program's exact parameter count."""

from __future__ import annotations

import pytest

import chipfixtures  # noqa: F401  (the benchmark on the path)
import harness
from repro.configs.registry import get_model_config
from repro.models.model import count_params_analytic
from repro.utils.config import ModelConfig

BENCH = harness.Bench({"workloads": []})


def test_kernel_counts_ignore_launch_options():
    from repro.kernels import dispatch

    counts = {}
    for chunk in (64, 128, 256):
        with dispatch.use_launch_config({"mamba_scan.chunk": chunk}):
            counts[chunk] = BENCH.kernel("mamba_scan").cost(1024, 5120, 16)
    assert len(set(counts.values())) == 1
    f, b = counts[64]
    assert f == 1024 * 5120 * (6.0 * 16 + 3.0)
    # bytes grow with the sequence, not with the chunking
    assert BENCH.kernel("mamba_scan").cost(2048, 5120, 16)[1] > b


@pytest.mark.parametrize("layers", [None, 2])
def test_param_count_matches_program(layers):
    cfg = get_model_config("falcon-mamba-7b")
    if layers:
        cfg = cfg.replace(num_layers=layers)
    mod = BENCH.reference("mamba1_lm")
    assert mod.param_count(cfg.to_dict()) == count_params_analytic(cfg)


def test_bench_config_files_match_param_count():
    bench = harness.Bench.from_repo()
    for c in bench.spec["configs"]:
        f = bench.config(c["name"])
        mc = ModelConfig(**f["model"])
        assert bench.reference(f["reference"]).param_count(f["model"]) \
            == count_params_analytic(mc)


def test_mamba_2p8b_is_the_published_size():
    f = harness.Bench.from_repo().config("mamba-2.8b")
    n = BENCH.reference("mamba1_lm").param_count(f["model"])
    assert 2.76e9 < n < 2.78e9           # "2.8b": 2.77 B with a tied head
    pub, m = f["published"], f["model"]
    assert (m["d_model"], m["num_layers"], m["vocab_size"], m["ssm_state"],
            m["ssm_conv"], m["ssm_expand"] * m["d_model"]) == (
        pub["hidden_size"], pub["num_hidden_layers"], pub["vocab_size"],
        pub["state_size"], pub["conv_kernel"], pub["intermediate_size"])


def test_flops_per_token():
    cfg = harness.Bench.from_repo().config("mamba-2.8b")["model"]
    mod = BENCH.reference("mamba1_lm")
    d, v, layers = cfg["d_model"], cfg["vocab_size"], cfg["num_layers"]
    # every matrix of a layer once, the tied head once, and the scan
    matmul = mod.param_count(cfg) - v * d
    per_token = mod.decode_flops(cfg, 0)
    assert 2 * 0.99 * matmul < per_token - 2 * d * v < 2 * 1.01 * matmul
    scan = layers * BENCH.kernel("mamba_scan").cost(
        1, cfg["ssm_expand"] * d, cfg["ssm_state"])[0]
    assert per_token - 2 * d * v > scan
    # no attention: a prefill is its tokens' work, whatever their number
    assert mod.prefill_flops(cfg, 1024) == 1024 * per_token
