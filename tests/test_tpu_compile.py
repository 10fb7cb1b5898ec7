"""AOT compiles of the main-path kernels for a described TPU v5e chip.

Nothing here runs on a chip: each test lowers one Pallas kernel at the
published widths of a registry model and compiles it for one device of a
described ``v5e:2x2`` topology with the TPU compiler that ships with jax.
What the chip's compiler refuses — a block whose last two dimensions are
neither (8, 128)-aligned nor whole, an op with no Mosaic lowering, a kernel
over the VMEM budget — fails here at no chip time.  A pass proves the kernel
compiles, not that it is fast or correct on the chip.

The topology is described inside a module-scoped fixture, never at import:
only one process may hold the TPU library at a time, and every test worker
imports this file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention.kernel import (decode_attention_pallas,
                                                  flash_attention_pallas)
from repro.kernels.mamba_scan.kernel import selective_scan_pallas
from repro.kernels.paged_attention.kernel import paged_decode_attention_pallas
from repro.kernels.rmsnorm.kernel import rmsnorm_pallas
from repro.kernels.ssd.kernel import ssd_pallas

BF16, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of these compiles
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _compile_text(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _assert_kernel(text):
    assert "tpu_custom_call" in text


# zamba2-2.7b prefill: 32 heads (kv 32) of 80; h2o-danube-1.8b: 32 | 8 heads
@pytest.mark.parametrize("b,s,hq,hkv,window", [
    (1, 512, 32, 32, 0),       # zamba2-2.7b shared attention
    (1, 1024, 32, 8, 0),       # h2o-danube-1.8b, no window
    (1, 1024, 32, 8, 4096),    # h2o-danube-1.8b, its 4096 sliding window
])
def test_flash_prefill_compiles(one_chip, b, s, hq, hkv, window):
    fn = lambda q, k, v: flash_attention_pallas(q, k, v, causal=True,
                                                sliding_window=window)
    _assert_kernel(_compile_text(fn, one_chip, ((b, s, hq, 80), BF16),
                                 ((b, s, hkv, 80), BF16),
                                 ((b, s, hkv, 80), BF16)))


def test_flash_prefill_one_kv_head_compiles(one_chip):
    # jamba2-3b: a 12288-token prompt, 20 query heads of 128 over 1 KV head
    fn = lambda q, k, v: flash_attention_pallas(q, k, v, causal=True)
    _assert_kernel(_compile_text(fn, one_chip, ((1, 12288, 20, 128), BF16),
                                 ((1, 12288, 1, 128), BF16),
                                 ((1, 12288, 1, 128), BF16)))


def test_dense_decode_compiles(one_chip):
    # h2o-danube-1.8b: 8 slots, GQA 32/8, a 1024-token heads-major cache
    fn = lambda q, k, v, n: decode_attention_pallas(q, k, v, n)
    _assert_kernel(_compile_text(fn, one_chip, ((8, 1, 32, 80), BF16),
                                 ((8, 8, 1024, 80), BF16),
                                 ((8, 8, 1024, 80), BF16), ((8,), I32)))


def test_paged_decode_compiles(one_chip):
    # zamba2-2.7b: 8 slots, 128 pool pages + scratch, 64-token pages
    fn = lambda q, k, v, t, n: paged_decode_attention_pallas(q, k, v, t, n)
    _assert_kernel(_compile_text(fn, one_chip, ((8, 1, 32, 80), BF16),
                                 ((129, 32, 64, 80), BF16),
                                 ((129, 32, 64, 80), BF16),
                                 ((8, 8), I32), ((8,), I32)))


def test_paged_decode_group_of_twenty_compiles(one_chip):
    # jamba2-3b: 32 slots x 104 pages of 128, 3328 pool pages + scratch,
    # 20 query heads over 1 KV head of 128
    fn = lambda q, k, v, t, n: paged_decode_attention_pallas(q, k, v, t, n)
    _assert_kernel(_compile_text(fn, one_chip, ((32, 1, 20, 128), BF16),
                                 ((3329, 1, 128, 128), BF16),
                                 ((3329, 1, 128, 128), BF16),
                                 ((32, 104), I32), ((32,), I32)))


@pytest.mark.parametrize("with_state", [False, True])
def test_ssd_compiles(one_chip, with_state):
    # zamba2-2.7b: 80 heads of 64, state 64, one group, chunk 64
    shapes = [((1, 512, 80, 64), BF16), ((1, 512, 80), BF16), ((80,), F32),
              ((1, 512, 1, 64), BF16), ((1, 512, 1, 64), BF16), ((80,), F32)]
    if with_state:
        shapes.append(((1, 80, 64, 64), F32))
        fn = lambda x, dt, a, b, c, d, s0: ssd_pallas(
            x, dt, a, b, c, d, chunk=64, init_state=s0, return_state=True)
    else:
        fn = lambda *a: ssd_pallas(*a, chunk=64)
    _assert_kernel(_compile_text(fn, one_chip, *shapes))


@pytest.mark.parametrize("with_state", [False, True])
def test_mamba_scan_compiles(one_chip, with_state):
    # falcon-mamba-7b: d_inner 8192, state 16
    fn = lambda *a: selective_scan_pallas(*a, return_state=with_state)
    _assert_kernel(_compile_text(
        fn, one_chip, ((1, 512, 8192), BF16), ((1, 512, 8192), BF16),
        ((8192, 16), F32), ((1, 512, 16), BF16), ((1, 512, 16), BF16),
        ((8192,), F32)))


@pytest.mark.parametrize("shape", [(512, 2560), (8, 1, 2560)])
def test_rmsnorm_compiles(one_chip, shape):
    fn = lambda x, w: rmsnorm_pallas(x, w)
    _assert_kernel(_compile_text(fn, one_chip, (shape, BF16),
                                 ((2560,), BF16)))
