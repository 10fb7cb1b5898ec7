"""Unified kernel dispatch: backend detection, mode resolution, interpret
fallback, launch-parameter ConfigSpace round-trips, and CAMEO tuning the
launch space end-to-end on the kernel-launch environment."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.cameo import Cameo
from repro.core.query import Query
from repro.envs.kernel_launch import KernelLaunchEnv, KernelWorkload
from repro.kernels import dispatch, ops
from repro.kernels.flash_attention import ref as aref
from repro.kernels.rmsnorm import ref as rref

RNG = np.random.default_rng(0)


def rand(*shape):
    return jnp.asarray(RNG.normal(size=shape).astype(np.float32))


# --------------------------------------------------------------------------
# backend detection / mode resolution
# --------------------------------------------------------------------------

def test_detect_backend_and_default_mode():
    assert dispatch.detect_backend() == "cpu"  # this container has no TPU
    assert dispatch.default_mode() == dispatch.REF
    assert dispatch.default_mode(backend="gpu") == dispatch.REF
    assert ops.kernel_mode() == dispatch.REF


def test_mode_env_override(monkeypatch):
    monkeypatch.setenv(dispatch.KERNEL_MODE_ENV, "pallas_interpret")
    assert dispatch.default_mode() == dispatch.PALLAS_INTERPRET
    monkeypatch.setenv(dispatch.KERNEL_MODE_ENV, "bogus")
    with pytest.raises(ValueError):
        dispatch.default_mode()


def test_all_families_registered():
    assert dispatch.families() == ["flash_attention", "mamba_scan",
                                   "paged_attention", "rmsnorm", "ssd"]
    for name in dispatch.families():
        fam = dispatch.get_family(name)
        assert fam.launch_options, name
        assert callable(dispatch.ref_fn(name))
        assert callable(dispatch.pallas_fn(name))


# --------------------------------------------------------------------------
# interpret-mode fallback through the generic router
# --------------------------------------------------------------------------

def test_generic_dispatch_rmsnorm_interpret_matches_ref():
    x, w = rand(6, 64), rand(64)
    ref = dispatch.dispatch("rmsnorm", x, w, mode="ref", eps=1e-5)
    np.testing.assert_allclose(ref, rref.rmsnorm_ref(x, w, eps=1e-5),
                               atol=1e-6)
    out = dispatch.dispatch("rmsnorm", x, w, mode="pallas_interpret",
                            launch={"row_block": 8}, eps=1e-5)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=1e-4)


def test_generic_dispatch_attention_and_decode_variant():
    q, k, v = rand(1, 32, 4, 16), rand(1, 32, 2, 16), rand(1, 32, 2, 16)
    ref = aref.attention_ref(q, k, v, causal=True)
    out = dispatch.dispatch("flash_attention", q, k, v,
                            mode="pallas_interpret",
                            launch={"q_block": 16, "kv_block": 16},
                            causal=True)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=1e-4)

    qd = rand(2, 1, 8, 32)
    kc, vc = rand(2, 2, 80, 32), rand(2, 2, 80, 32)  # heads-major cache
    clen = jnp.asarray([13, 77], jnp.int32)
    refd = aref.decode_attention_ref(qd, kc, vc, clen)
    # ref mode drops the kv_block launch param (the oracle has no blocking)
    outd_ref = dispatch.dispatch("flash_attention", qd, kc, vc, clen,
                                 variant="decode", mode="ref",
                                 launch={"kv_block": 32})
    np.testing.assert_allclose(outd_ref, refd, atol=2e-5, rtol=1e-4)
    outd = dispatch.dispatch("flash_attention", qd, kc, vc, clen,
                             variant="decode", mode="pallas_interpret",
                             launch={"kv_block": 32})
    np.testing.assert_allclose(outd, refd, atol=2e-5, rtol=1e-4)


def test_ops_entry_points_in_interpret_mode(monkeypatch):
    monkeypatch.setenv(dispatch.KERNEL_MODE_ENV, "pallas_interpret")
    x, w = rand(4, 7, 32), rand(32)
    np.testing.assert_allclose(ops.rmsnorm(x, w),
                               rref.rmsnorm_ref(x, w), atol=2e-5, rtol=1e-4)
    q, k, v = rand(1, 24, 4, 16), rand(1, 24, 2, 16), rand(1, 24, 2, 16)
    np.testing.assert_allclose(
        ops.flash_attention(q, k, v, causal=True, q_block=8, kv_block=8),
        aref.attention_ref(q, k, v, causal=True), atol=2e-5, rtol=1e-4)


# --------------------------------------------------------------------------
# launch parameters: precedence + ConfigSpace round-trip
# --------------------------------------------------------------------------

def test_launch_param_precedence_and_validation():
    assert dispatch.launch_params("rmsnorm")["row_block"] == 256
    assert dispatch.launch_params("rmsnorm", row_block=64)["row_block"] == 64
    # None means "unspecified", not an override
    assert dispatch.launch_params("rmsnorm", row_block=None)["row_block"] == 256
    with dispatch.use_launch_config({"rmsnorm.row_block": 128}):
        # an active tuned config outranks the call site
        assert dispatch.launch_params("rmsnorm", row_block=64)["row_block"] == 128
        with dispatch.use_launch_config({"flash_attention": {"q_block": 256}}):
            # nested contexts merge
            assert dispatch.launch_params("rmsnorm")["row_block"] == 128
            assert dispatch.launch_params("flash_attention")["q_block"] == 256
    assert dispatch.launch_params("rmsnorm")["row_block"] == 256

    with pytest.raises(KeyError):
        dispatch.split_launch_config({"bogus.q_block": 128})
    with pytest.raises(KeyError):
        dispatch.split_launch_config({"rmsnorm.bogus": 128})
    with pytest.raises(KeyError):
        dispatch.launch_params("rmsnorm", bogus=1)


def test_launch_space_roundtrips_through_configspace():
    space = dispatch.launch_space()
    assert set(space.names) == {
        "flash_attention.q_block", "flash_attention.kv_block",
        "mamba_scan.chunk", "mamba_scan.c_block", "ssd.chunk",
        "rmsnorm.row_block", "paged_attention.page_size",
        "paged_attention.pages_per_slot_max",
        "paged_attention.prefill_chunk"}
    rng = np.random.default_rng(3)
    for cfg in [space.default_config()] + space.sample(rng, 25):
        assert space.decode(space.encode(cfg)) == cfg
        nested = dispatch.split_launch_config(cfg)
        with dispatch.use_launch_config(cfg):
            for fam, params in nested.items():
                resolved = dispatch.launch_params(fam)
                for pname, v in params.items():
                    assert resolved[pname] == v


def test_tuned_config_drives_real_kernel():
    x, w = rand(10, 32), rand(32)
    with dispatch.use_launch_config({"rmsnorm.row_block": 2}):
        res = dispatch.resolve("rmsnorm", mode="pallas_interpret")
        assert res.launch["row_block"] == 2
        out = ops.rmsnorm(x, w)  # still ref mode outside env var — numeric
        np.testing.assert_allclose(out, rref.rmsnorm_ref(x, w),
                                   atol=2e-5, rtol=1e-4)
        out_i = dispatch.dispatch("rmsnorm", x, w, mode="pallas_interpret")
        np.testing.assert_allclose(out_i, rref.rmsnorm_ref(x, w),
                                   atol=2e-5, rtol=1e-4)


# --------------------------------------------------------------------------
# CAMEO optimizes the launch space end-to-end
# --------------------------------------------------------------------------

def test_cameo_tunes_launch_space_end_to_end():
    # source: cheap training-shape environment with plentiful observations
    src = KernelLaunchEnv(KernelWorkload(name="train-2k", batch=16,
                                         seq_len=2048), seed=1)
    # target: serving shape with higher launch overhead — effects shift
    tgt = KernelLaunchEnv(KernelWorkload(name="serve-8k", batch=4,
                                         seq_len=8192,
                                         launch_overhead_us=3.0), seed=2)
    source_data = src.dataset(48, seed=3)
    cam = Cameo(tgt.space, Query(objective="step_time"), source_data,
                counter_names=tgt.counter_names, seed=0)
    cam.seed_target(tgt.dataset(6, seed=4))
    best_cfg, best_y = cam.run(tgt, budget=10)

    assert np.isfinite(best_y)
    assert set(best_cfg) <= set(tgt.space.names)
    # the optimum must be feasible under the VMEM constraint model
    counters, y_check = tgt.intervene(best_cfg)
    assert np.isfinite(y_check)
    assert counters["vmem_peak_bytes"] <= tgt.workload.vmem_limit

    # end of the loop IS deployment: the tuned optimum installs onto the
    # dispatch registry and every kernel resolves with the tuned params
    with tgt.apply(best_cfg):
        for fam, params in dispatch.split_launch_config(best_cfg).items():
            resolved = dispatch.launch_params(fam)
            for pname, v in params.items():
                assert resolved[pname] == v
