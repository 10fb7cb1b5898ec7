"""Integration: the launch/build path lowers + compiles smoke-scale cells on
the single CPU device (the production-mesh version is exercised by
``repro.launch.dryrun`` under its 512-device flag)."""

import jax
import pytest

from repro.configs.registry import (arch_shapes, input_specs, list_archs,
                                    make_run)
from repro.launch.build import lower_step
from repro.launch.hlo_analysis import analyze_hlo
from repro.launch.mesh import make_mesh


@pytest.mark.parametrize("shape", ["train_smoke", "prefill_smoke",
                                   "decode_smoke"])
def test_lower_compile_smoke_cells(shape):
    run = make_run("llama3.2-1b", shape, smoke=True)
    mesh = make_mesh(run.mesh)
    bundle, lowered = lower_step(run, mesh)
    compiled = lowered.compile()
    costs = analyze_hlo(compiled.as_text())
    assert costs.flops > 0
    assert costs.bytes_accessed > 0


@pytest.mark.parametrize("arch", ["falcon-mamba-7b", "deepseek-v3-671b",
                                  "whisper-large-v3", "zamba2-2.7b"])
def test_lower_compile_other_families(arch):
    run = make_run(arch, "train_smoke", smoke=True)
    mesh = make_mesh(run.mesh)
    _, lowered = lower_step(run, mesh)
    lowered.compile()


def test_input_specs_cover_all_cells():
    for arch in list_archs():
        for shape in arch_shapes(arch):
            run = make_run(arch, shape)
            specs = input_specs(run)
            if run.shape.kind == "train":
                assert specs["batch"]["inputs"].shape == (
                    run.shape.global_batch, run.shape.seq_len)
            elif run.shape.kind == "decode":
                assert specs["tokens"].shape == (run.shape.global_batch, 1)
                leaves = jax.tree.leaves(specs["state"])
                assert all(hasattr(l, "shape") for l in leaves)


def test_long_500k_skips_are_exactly_the_full_attention_archs():
    skipped = [a for a in list_archs() if "long_500k" not in arch_shapes(a)]
    assert sorted(skipped) == sorted([
        "nemotron-4-15b", "llama3.2-1b", "command-r-35b", "deepseek-v3-671b",
        "llama4-maverick-400b-a17b", "llama-3.2-vision-11b",
        "whisper-large-v3"])
    runnable = [a for a in list_archs() if "long_500k" in arch_shapes(a)]
    assert sorted(runnable) == sorted(
        ["falcon-mamba-7b", "zamba2-2.7b", "h2o-danube-1.8b", "jamba2-3b"])


def test_make_run_rejects_long500k_for_full_attention():
    with pytest.raises(ValueError):
        make_run("llama3.2-1b", "long_500k")


def test_tuner_space_round_trips_parallel_config():
    from repro.configs.registry import get_model_config
    from repro.tuner.space import (apply_config, config_to_parallel_kv,
                                   framework_space)
    from repro.utils.config import ParallelConfig

    cfg = get_model_config("llama3.2-1b")
    space = framework_space(cfg, "train")
    c = space.default_config()
    c["remat"] = "full"
    c["microbatch"] = 4
    par = apply_config(ParallelConfig(), c)
    assert par.remat == "full" and par.microbatch == 4
    kv = config_to_parallel_kv(c)
    assert "remat=full" in kv and "microbatch=4" in kv
