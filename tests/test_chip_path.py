"""CPU checks of what the chip path relies on: Pallas kernels that carry
recurrent state, a backward for every kernel op (recomputed through the
reference and recorded as such), no silent fallback to the reference, the
mesh and compiler-parameter wrappers for the installed jax, the compile
cache's placement, and ``chip_smoke.py`` rehearsed at a tiny size.

The kernels run in the Pallas interpreter here; ``test_tpu_compile.py``
compiles them for the chip."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType

from repro import compat
from repro.kernels import dispatch, ops
from repro.kernels.flash_attention import ref as aref
from repro.kernels.mamba_scan import ref as sref
from repro.kernels.mamba_scan.kernel import selective_scan_pallas
from repro.kernels.paged_attention import ref as pref
from repro.kernels.rmsnorm import ref as rref
from repro.kernels.ssd import ref as ssdref
from repro.kernels.ssd.kernel import ssd_pallas

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RNG = np.random.default_rng(11)


def rand(*shape):
    return jnp.asarray(RNG.normal(size=shape).astype(np.float32))


def _ssd_inputs(b=2, l=40, h=4, p=8, g=2, n=4):
    return (rand(b, l, h, p), jnp.abs(rand(b, l, h)) * 0.1,
            -jnp.abs(rand(h)), rand(b, l, g, n), rand(b, l, g, n), rand(h))


def _scan_inputs(b=2, l=24, c=16, n=4):
    return (rand(b, l, c), jnp.abs(rand(b, l, c)) * 0.1, -jnp.abs(rand(c, n)),
            rand(b, l, n), rand(b, l, n), rand(c))


# --------------------------------------------------------------------------
# kernels that carry state
# --------------------------------------------------------------------------

@pytest.mark.parametrize("l,chunk", [(40, 8), (30, 16)])  # 30: pad path
@pytest.mark.parametrize("with_init", [False, True])
def test_ssd_pallas_state_matches_ref(l, chunk, with_init):
    x, dt, A, Bm, Cm, D = _ssd_inputs(l=l)
    s0 = rand(2, 4, 4, 8) if with_init else None
    y_ref, s_ref = ssdref.ssd_ref(x, dt, A, Bm, Cm, D, chunk=chunk,
                                  init_state=s0, return_state=True)
    y, s = ssd_pallas(x, dt, A, Bm, Cm, D, chunk=chunk, init_state=s0,
                      return_state=True, interpret=True)
    np.testing.assert_allclose(y, y_ref, atol=1e-4, rtol=1e-3)
    np.testing.assert_allclose(s, s_ref, atol=1e-4, rtol=1e-3)


def test_ssd_pallas_state_continues_a_split_sequence():
    # prefill in two pieces, the second seeded with the first's final state,
    # reproduces one prefill over the whole sequence
    x, dt, A, Bm, Cm, D = _ssd_inputs(l=32)
    y_all, s_all = ssd_pallas(x, dt, A, Bm, Cm, D, chunk=8,
                              return_state=True, interpret=True)
    cut = lambda t, a, b: t[:, a:b]
    y1, s1 = ssd_pallas(*(cut(t, 0, 16) for t in (x, dt)), A,
                        *(cut(t, 0, 16) for t in (Bm, Cm)), D, chunk=8,
                        return_state=True, interpret=True)
    y2, s2 = ssd_pallas(*(cut(t, 16, 32) for t in (x, dt)), A,
                        *(cut(t, 16, 32) for t in (Bm, Cm)), D, chunk=8,
                        init_state=s1, return_state=True, interpret=True)
    np.testing.assert_allclose(jnp.concatenate([y1, y2], 1), y_all,
                               atol=1e-4, rtol=1e-3)
    np.testing.assert_allclose(s2, s_all, atol=1e-4, rtol=1e-3)


@pytest.mark.parametrize("l,chunk", [(24, 8), (21, 8)])
def test_selective_scan_pallas_final_state_matches_ref(l, chunk):
    args = _scan_inputs(l=l)
    y_ref, h_ref = sref.selective_scan_chunked_ref(*args, chunk=chunk,
                                                   return_state=True)
    y, h = selective_scan_pallas(*args, chunk=chunk, c_block=16,
                                 return_state=True, interpret=True)
    np.testing.assert_allclose(y, y_ref, atol=1e-4, rtol=1e-3)
    np.testing.assert_allclose(h, h_ref, atol=1e-4, rtol=1e-3)


# --------------------------------------------------------------------------
# every op trains with kernels on, and says so
# --------------------------------------------------------------------------

def _attn():
    return (rand(2, 24, 4, 16), rand(2, 24, 2, 16), rand(2, 24, 2, 16))


def _decode():
    return (rand(2, 1, 4, 16), rand(2, 2, 32, 16), rand(2, 2, 32, 16),
            jnp.asarray([9, 30], jnp.int32))


def _paged():
    table = jnp.asarray([[3, 0, 5, 1], [2, 4, 6, 7]], jnp.int32)
    return (rand(2, 1, 4, 16), rand(8, 2, 8, 16), rand(8, 2, 8, 16), table,
            jnp.asarray([13, 27], jnp.int32))


# (name, op call, reference call, inputs, float-argument positions)
OPS = {
    "flash_attention": (
        lambda q, k, v: ops.flash_attention(q, k, v, q_block=8, kv_block=8),
        lambda q, k, v: aref.attention_ref(q, k, v, causal=True),
        _attn, (0, 1, 2)),
    "decode_attention": (
        lambda q, k, v, n: ops.decode_attention(q, k, v, n, kv_block=8),
        aref.decode_attention_ref, _decode, (0, 1, 2)),
    "paged_decode_attention": (
        ops.paged_decode_attention, pref.paged_decode_attention_ref, _paged,
        (0, 1, 2)),
    "selective_scan": (
        lambda *a: ops.selective_scan(*a, chunk=8, c_block=16),
        sref.selective_scan_ref, _scan_inputs, tuple(range(6))),
    "selective_scan_state": (
        lambda *a: ops.selective_scan(*a, chunk=8, c_block=16,
                                      return_state=True),
        lambda *a: sref.selective_scan_chunked_ref(*a, chunk=8,
                                                   return_state=True),
        _scan_inputs, tuple(range(6))),
    "ssd": (
        lambda *a: ops.ssd(*a, chunk=8),
        lambda *a: ssdref.ssd_ref(*a, chunk=8), _ssd_inputs, tuple(range(6))),
    "ssd_state": (
        lambda *a: ops.ssd(*a[:6], chunk=8, init_state=a[6],
                           return_state=True),
        lambda *a: ssdref.ssd_ref(*a[:6], chunk=8, init_state=a[6],
                                  return_state=True),
        lambda: _ssd_inputs() + (rand(2, 4, 4, 8),), tuple(range(7))),
    "rmsnorm": (
        lambda x, w: ops.rmsnorm(x, w, row_block=8),
        lambda x, w: rref.rmsnorm_ref(x, w), lambda: (rand(3, 7, 32), rand(32)),
        (0, 1)),
    "rmsnorm_residual": (
        lambda x, w, r: ops.rmsnorm(x, w, residual=r, row_block=8),
        lambda x, w, r: rref.rmsnorm_ref(x, w, residual=r),
        lambda: (rand(3, 7, 32), rand(32), rand(3, 7, 32)), (0, 1, 2)),
}


def _sq_sum(out):
    return sum(jnp.sum(jnp.square(o.astype(jnp.float32)))
               for o in jax.tree.leaves(out))


@pytest.mark.parametrize("name", sorted(OPS))
def test_grad_with_kernels_on_matches_ref(name, monkeypatch):
    monkeypatch.setenv(dispatch.KERNEL_MODE_ENV, dispatch.PALLAS_INTERPRET)
    op, ref, make, argnums = OPS[name]
    args = make()
    with dispatch.record_resolutions() as rec:
        out = op(*args)
        g_op = jax.grad(lambda *a: _sq_sum(op(*a)), argnums=argnums)(*args)
    np.testing.assert_allclose(
        np.concatenate([np.ravel(o) for o in jax.tree.leaves(out)]),
        np.concatenate([np.ravel(o) for o in jax.tree.leaves(ref(*args))]),
        atol=1e-4, rtol=1e-3)
    g_ref = jax.grad(lambda *a: _sq_sum(ref(*a)), argnums=argnums)(*args)
    for a, b in zip(g_op, g_ref):
        np.testing.assert_allclose(a, b, atol=5e-4, rtol=2e-3)
    # forward: the interpreted kernel; backward: the reference, marked so
    fwd = [r for r in rec if not r.backward]
    bwd = [r for r in rec if r.backward]
    assert fwd and all(r.mode == dispatch.PALLAS_INTERPRET and r.interpret
                       for r in fwd)
    assert bwd and all(r.mode == dispatch.REF and not r.interpret
                       for r in bwd)
    assert {r.family for r in bwd} == {r.family for r in fwd}


def test_backward_keeps_the_forward_launch_params(monkeypatch):
    monkeypatch.setenv(dispatch.KERNEL_MODE_ENV, dispatch.PALLAS_INTERPRET)
    q, k, v = _attn()
    f = lambda q: jnp.sum(ops.flash_attention(q, k, v, q_block=8,
                                              kv_block=16))
    with dispatch.record_resolutions() as rec:
        jax.grad(f)(q)
    (bwd,) = [r for r in rec if r.backward]
    assert bwd.launch["q_block"] == 8 and bwd.launch["kv_block"] == 16


def test_kernels_run_per_data_shard_under_a_mesh(monkeypatch):
    # a Mosaic kernel cannot be partitioned by XLA: under a mesh the ops
    # wrap it in shard_map over the data axes — same numbers as without
    monkeypatch.setenv(dispatch.KERNEL_MODE_ENV, dispatch.PALLAS_INTERPRET)
    q, k, v = _attn()
    x, w = rand(2, 5, 32), rand(32)
    plain = (ops.flash_attention(q, k, v, q_block=8, kv_block=8),
             ops.rmsnorm(x, w, row_block=8))
    mesh = compat.make_mesh((1,), ("data",), devices=jax.devices()[:1])
    with jax.set_mesh(mesh):
        fn = jax.jit(lambda q, k, v, x, w: (
            ops.flash_attention(q, k, v, q_block=8, kv_block=8),
            ops.rmsnorm(x, w, row_block=8)))
        jaxpr = str(jax.make_jaxpr(fn)(q, k, v, x, w))
        meshed = fn(q, k, v, x, w)
    assert jaxpr.count("shard_map") == 2
    for a, b in zip(meshed, plain):
        np.testing.assert_allclose(a, b, atol=1e-6, rtol=1e-6)


# --------------------------------------------------------------------------
# no silent fallbacks
# --------------------------------------------------------------------------

def test_tpu_without_pallas_tpu_raises(monkeypatch):
    monkeypatch.delenv(dispatch.KERNEL_MODE_ENV, raising=False)
    assert dispatch.default_mode(backend="tpu") == dispatch.PALLAS
    monkeypatch.setattr(compat, "HAS_PALLAS_TPU", False)
    with pytest.raises(RuntimeError, match="REPRO_KERNEL_MODE=ref"):
        dispatch.default_mode(backend="tpu")
    assert dispatch.default_mode(backend="cpu") == dispatch.REF
    monkeypatch.setenv(dispatch.KERNEL_MODE_ENV, dispatch.REF)
    assert dispatch.default_mode(backend="tpu") == dispatch.REF


def test_ssm_prefill_with_state_runs_the_kernel(monkeypatch):
    # the serving prefill asks for the final state: it must run (and
    # record) the kernel, not the reference behind a 'pallas' record
    monkeypatch.setenv(dispatch.KERNEL_MODE_ENV, dispatch.PALLAS_INTERPRET)
    x, dt, A, Bm, Cm, D = _ssd_inputs()
    with dispatch.record_resolutions() as rec:
        jaxpr = str(jax.make_jaxpr(lambda *a: ops.ssd(
            *a, chunk=8, return_state=True))(x, dt, A, Bm, Cm, D))
    assert [(r.family, r.mode) for r in rec] == [("ssd", "pallas_interpret")]
    assert "pallas_call" in jaxpr


def test_compiler_params_reject_unknown_keywords():
    p = compat.tpu_compiler_params(dimension_semantics=("parallel",))
    assert tuple(p.dimension_semantics) == ("parallel",)
    with pytest.raises(TypeError):
        compat.tpu_compiler_params(dimension_semantic=("parallel",))


def test_meshes_have_auto_axes():
    mesh = compat.make_mesh((1, 1), ("data", "model"),
                            devices=jax.devices()[:1])
    assert tuple(mesh.axis_types) == (AxisType.Auto, AxisType.Auto)
    from repro.launch.mesh import make_mesh
    from repro.utils.config import MeshConfig
    assert all(t == AxisType.Auto for t in
               make_mesh(MeshConfig(shape=(1,), axes=("data",))).axis_types)


def test_compiled_steps_key_on_kernel_mode(monkeypatch):
    from conftest import tiny_model_config
    from repro.models.model import build_model
    from repro.train.serve_step import jitted_steps
    from repro.utils.config import RunConfig, ShapeConfig

    cfg = tiny_model_config()
    run = RunConfig(model=cfg, shape=ShapeConfig("s", 16, 1, "decode"))
    model = build_model(cfg)
    monkeypatch.setenv(dispatch.KERNEL_MODE_ENV, dispatch.REF)
    ref_steps = jitted_steps(model, run, cache_len=16)
    assert jitted_steps(model, run, cache_len=16) is ref_steps
    monkeypatch.setenv(dispatch.KERNEL_MODE_ENV, dispatch.PALLAS_INTERPRET)
    assert jitted_steps(model, run, cache_len=16)[0] is not ref_steps[0]


# --------------------------------------------------------------------------
# compile cache and child processes
# --------------------------------------------------------------------------

def test_compile_cache_placement(monkeypatch):
    from repro.utils import compile_cache

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(compile_cache.ENV_VAR, "/placed/from/outside")
    try:
        assert compile_cache.enable_compile_cache() == "/placed/from/outside"
        assert jax.config.jax_compilation_cache_dir == before  # left to jax
        monkeypatch.delenv(compile_cache.ENV_VAR)
        path = compile_cache.enable_compile_cache()
        assert path == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        assert compile_cache.enable_compile_cache() == path  # fixed, not fresh
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    ignored = open(os.path.join(REPO, ".gitignore")).read().split()
    assert ".jax_cache/" in ignored


class _Proc:
    def __init__(self, returncode, stderr):
        self.returncode, self.stderr, self.stdout = returncode, stderr, ""


@pytest.mark.parametrize("stderr,infeasible", [
    ("Traceback ...\nValueError: 5120 is not divisible by 3\n", True),
    ("Traceback ...\nValueError: sharding of dim 1 does not fit the mesh\n",
     True),
    ("Traceback ...\nModuleNotFoundError: No module named 'repro'\n", False),
    ("Traceback ...\nRuntimeError: Unable to initialize backend 'tpu'\n",
     False),
])
def test_compiled_env_tells_crash_from_infeasible(monkeypatch, tmp_path,
                                                  stderr, infeasible):
    from repro.tuner import compiled_env

    seen = {}

    def fake_run(cmd, **kw):
        seen.update(kw["env"])
        return _Proc(1, stderr)

    monkeypatch.setattr(compiled_env.subprocess, "run", fake_run)
    env = compiled_env.CompiledPerfEnv("llama3.2-1b", "train_4k",
                                       cache_dir=str(tmp_path))
    config = env.space.default_config()
    if infeasible:
        counters, y = env._measure(config)
        assert y == float("inf")
    else:
        with pytest.raises(compiled_env.DryRunCrash, match="crashed"):
            env._measure(config)
    assert seen["JAX_PLATFORMS"] == "cpu"


# --------------------------------------------------------------------------
# chip_smoke.py: contract off the chip, and a tiny rehearsal
# --------------------------------------------------------------------------

def _run_script(path, cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, path], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_chip_smoke_refuses_without_a_tpu():
    proc = _run_script(os.path.join(REPO, "chip_smoke.py"), REPO)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "no TPU" in proc.stderr


def test_chip_smoke_refuses_outside_the_checkout(tmp_path):
    lone = tmp_path / "chip_smoke.py"
    lone.write_text(open(os.path.join(REPO, "chip_smoke.py")).read())
    proc = _run_script(str(lone), str(tmp_path))
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout


@pytest.fixture
def chip_smoke(monkeypatch):
    monkeypatch.syspath_prepend(REPO)
    import chip_smoke as mod
    return mod


def test_chip_smoke_serve_check_rehearsal(chip_smoke, monkeypatch):
    from repro.configs.registry import get_smoke_config

    monkeypatch.setenv(dispatch.KERNEL_MODE_ENV, dispatch.PALLAS_INTERPRET)
    out = chip_smoke.serve_check(get_smoke_config("zamba2-2.7b"),
                                 prompt_lens=(16, 40), n_requests=4,
                                 new_tokens=3, compare_steps=2)
    assert out["requests_completed"] == 4 and out["tokens_generated"] == 12
    assert max([out["logit_rel_err"]["prefill"]]
               + out["logit_rel_err"]["decode"]) < 1e-4  # float32 smoke
    assert set(out["modes"]) == {"flash_attention", "paged_attention",
                                 "rmsnorm", "ssd"}
    json.dumps(out)


def test_chip_smoke_train_check_rehearsal(chip_smoke, monkeypatch):
    from repro.configs.registry import get_smoke_config

    monkeypatch.setenv(dispatch.KERNEL_MODE_ENV, dispatch.PALLAS_INTERPRET)
    cfg = get_smoke_config("zamba2-2.7b")
    out = chip_smoke.train_check(cfg.replace(num_layers=2), jax.devices(),
                                 global_batch=2, seq_len=16, steps=2)
    assert out["losses_data_parallel"] == out["losses_one_device"]
    assert any("[backward]" in k for m in out["modes"].values() for k in m)
