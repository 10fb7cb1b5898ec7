"""Per-kernel validation: Pallas (interpret=True) vs the pure-jnp oracle,
swept over shapes and dtypes, plus gradient checks for the custom-VJP ops.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.kernels.flash_attention import ref as aref
from repro.kernels.flash_attention.kernel import (
    decode_attention_pallas, flash_attention_pallas)
from repro.kernels.mamba_scan import ref as sref
from repro.kernels.mamba_scan.kernel import selective_scan_pallas
from repro.kernels.paged_attention import ref as pref
from repro.kernels.paged_attention.kernel import paged_decode_attention_pallas
from repro.kernels.rmsnorm import ref as rref
from repro.kernels.rmsnorm.kernel import rmsnorm_pallas
from repro.kernels.ssd import ref as ssdref
from repro.kernels.ssd.kernel import ssd_pallas

RNG = np.random.default_rng(0)


def rand(*shape, dtype=np.float32):
    return jnp.asarray(RNG.normal(size=shape).astype(dtype))


# --------------------------------------------------------------------------
# flash attention
# --------------------------------------------------------------------------

@pytest.mark.parametrize("b,sq,skv,hq,hkv,d", [
    (1, 16, 16, 2, 2, 8),       # MHA, tiny
    (2, 96, 96, 8, 2, 32),      # GQA g=4, unaligned seq
    (1, 33, 65, 4, 1, 16),      # MQA, prime-ish seq (padding path)
])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_matches_oracle(b, sq, skv, hq, hkv, d, causal):
    q, k, v = rand(b, sq, hq, d), rand(b, skv, hkv, d), rand(b, skv, hkv, d)
    ref = aref.attention_ref(q, k, v, causal=causal)
    out = flash_attention_pallas(q, k, v, causal=causal, q_block=16,
                                 kv_block=16, interpret=True)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=1e-4)


@pytest.mark.parametrize("sw,cap", [(0, 0.0), (7, 0.0), (0, 20.0), (9, 30.0)])
def test_flash_attention_window_softcap(sw, cap):
    q, k, v = rand(2, 48, 4, 16), rand(2, 48, 2, 16), rand(2, 48, 2, 16)
    ref = aref.attention_ref(q, k, v, causal=True, sliding_window=sw,
                             logit_softcap=cap)
    out = flash_attention_pallas(q, k, v, causal=True, sliding_window=sw,
                                 logit_softcap=cap, q_block=16, kv_block=16,
                                 interpret=True)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=1e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_dtypes(dtype):
    q = rand(1, 32, 4, 16).astype(dtype)
    k = rand(1, 32, 2, 16).astype(dtype)
    v = rand(1, 32, 2, 16).astype(dtype)
    ref = aref.attention_ref(q, k, v, causal=True)
    out = flash_attention_pallas(q, k, v, causal=True, q_block=16,
                                 kv_block=16, interpret=True)
    assert out.dtype == dtype
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(out.astype(np.float32),
                               ref.astype(np.float32), atol=tol, rtol=tol)


def test_blockwise_ref_matches_plain():
    q, k, v = rand(2, 40, 4, 16), rand(2, 40, 2, 16), rand(2, 40, 2, 16)
    for kvb in (8, 16, 64):
        out = aref.attention_blockwise_ref(q, k, v, causal=True, kv_block=kvb)
        ref = aref.attention_ref(q, k, v, causal=True)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=1e-4)


@pytest.mark.parametrize("sw", [0, 9])
def test_decode_attention_matches_oracle(sw):
    b, skv, hq, hkv, d = 2, 80, 8, 2, 32
    q = rand(b, 1, hq, d)
    kc, vc = rand(b, hkv, skv, d), rand(b, hkv, skv, d)  # heads-major
    clen = jnp.asarray([13, 77], jnp.int32)
    ref = aref.decode_attention_ref(q, kc, vc, clen, sliding_window=sw)
    out = decode_attention_pallas(q, kc, vc, clen, sliding_window=sw,
                                  kv_block=32, interpret=True)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=1e-4)


def test_paged_decode_five_heads_over_one_kv_head():
    # jamba's group at smoke size: 5 query heads over 1 KV head, a (5, D)
    # query block that is the whole head axis; pages scattered over the pool
    b, hq, d, ps, n_pages = 3, 5, 128, 8, 4
    q = rand(b, 1, hq, d)
    k_pages, v_pages = rand(13, 1, ps, d), rand(13, 1, ps, d)
    table = jnp.asarray(RNG.permutation(13)[:b * n_pages].reshape(b, n_pages),
                        jnp.int32)
    clen = jnp.asarray([1, 17, 32], jnp.int32)
    ref = pref.paged_decode_attention_ref(q, k_pages, v_pages, table, clen)
    out = paged_decode_attention_pallas(q, k_pages, v_pages, table, clen,
                                        interpret=True)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=1e-4)


def test_attention_grads_match_plain_ref():
    q, k, v = rand(2, 24, 4, 16), rand(2, 24, 2, 16), rand(2, 24, 2, 16)
    f_op = lambda q, k, v: (ops.flash_attention(q, k, v, causal=True,
                                                kv_block=8) ** 2).sum()
    f_ref = lambda q, k, v: (aref.attention_ref(q, k, v, causal=True) ** 2).sum()
    g1 = jax.grad(f_op, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(a, b, atol=5e-5, rtol=1e-3)


# --------------------------------------------------------------------------
# mamba selective scan
# --------------------------------------------------------------------------

@pytest.mark.parametrize("b,l,c,n,chunk,cblk", [
    (1, 16, 8, 4, 8, 8),
    (2, 72, 48, 8, 16, 16),
    (1, 50, 24, 16, 32, 8),   # pad path
])
def test_selective_scan_matches_oracle(b, l, c, n, chunk, cblk):
    x, dt = rand(b, l, c), jnp.abs(rand(b, l, c)) * 0.1
    A = -jnp.abs(rand(c, n))
    Bm, Cm, D = rand(b, l, n), rand(b, l, n), rand(c)
    ref = sref.selective_scan_ref(x, dt, A, Bm, Cm, D)
    out = selective_scan_pallas(x, dt, A, Bm, Cm, D, chunk=chunk,
                                c_block=cblk, interpret=True)
    np.testing.assert_allclose(out, ref, atol=1e-4, rtol=1e-3)


def test_selective_scan_chunked_equals_unchunked():
    x, dt = rand(2, 40, 12), jnp.abs(rand(2, 40, 12)) * 0.1
    A = -jnp.abs(rand(12, 4))
    Bm, Cm, D = rand(2, 40, 4), rand(2, 40, 4), rand(12)
    ref = sref.selective_scan_ref(x, dt, A, Bm, Cm, D)
    for chunk in (5, 8, 40):
        out = sref.selective_scan_chunked_ref(x, dt, A, Bm, Cm, D, chunk=chunk)
        np.testing.assert_allclose(out, ref, atol=1e-4, rtol=1e-3)


def test_selective_scan_final_state_consistent_with_steps():
    b, l, c, n = 1, 12, 6, 4
    x, dt = rand(b, l, c), jnp.abs(rand(b, l, c)) * 0.1
    A = -jnp.abs(rand(c, n))
    Bm, Cm, D = rand(b, l, n), rand(b, l, n), rand(c)
    _, h_final = sref.selective_scan_chunked_ref(x, dt, A, Bm, Cm, D, chunk=4,
                                                 return_state=True)
    h = jnp.zeros((b, c, n))
    for t in range(l):
        h, _ = sref.selective_scan_step_ref(h, x[:, t], dt[:, t], A,
                                            Bm[:, t], Cm[:, t], D)
    np.testing.assert_allclose(h_final, h, atol=1e-4, rtol=1e-3)


def test_selective_scan_grads():
    x, dt = rand(2, 32, 8), jnp.abs(rand(2, 32, 8)) * 0.1
    A = -jnp.abs(rand(8, 4))
    Bm, Cm, D = rand(2, 32, 4), rand(2, 32, 4), rand(8)
    f_op = lambda *a: (ops.selective_scan(*a, chunk=8) ** 2).sum()
    f_ref = lambda *a: (sref.selective_scan_ref(*a) ** 2).sum()
    g1 = jax.grad(f_op, argnums=tuple(range(6)))(x, dt, A, Bm, Cm, D)
    g2 = jax.grad(f_ref, argnums=tuple(range(6)))(x, dt, A, Bm, Cm, D)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(a, b, atol=2e-4, rtol=1e-3)


# --------------------------------------------------------------------------
# SSD
# --------------------------------------------------------------------------

@pytest.mark.parametrize("b,l,h,p,g,n,chunk", [
    (1, 16, 2, 8, 1, 4, 8),
    (2, 48, 4, 16, 2, 8, 16),
    (1, 30, 4, 8, 4, 4, 16),   # pad path
])
def test_ssd_matches_oracle(b, l, h, p, g, n, chunk):
    x, dt = rand(b, l, h, p), jnp.abs(rand(b, l, h)) * 0.1
    A = -jnp.abs(rand(h))
    Bm, Cm, D = rand(b, l, g, n), rand(b, l, g, n), rand(h)
    ref = ssdref.ssd_ref(x, dt, A, Bm, Cm, D, chunk=chunk)
    out = ssd_pallas(x, dt, A, Bm, Cm, D, chunk=chunk, interpret=True)
    np.testing.assert_allclose(out, ref, atol=1e-4, rtol=1e-3)


def test_ssd_matches_naive_recurrence():
    b, l, h, p, g, n = 1, 10, 2, 4, 1, 3
    x, dt = rand(b, l, h, p), jnp.abs(rand(b, l, h)) * 0.1
    A = -jnp.abs(rand(h))
    Bm, Cm, D = rand(b, l, g, n), rand(b, l, g, n), rand(h)
    out = ssdref.ssd_ref(x, dt, A, Bm, Cm, D, chunk=5)
    state = jnp.zeros((b, h, n, p))
    ys = []
    for t in range(l):
        state, y = ssdref.ssd_step_ref(state, x[:, t], dt[:, t], A,
                                       Bm[:, t], Cm[:, t], D)
        ys.append(y)
    naive = jnp.stack(ys, axis=1)
    np.testing.assert_allclose(out, naive, atol=1e-4, rtol=1e-3)


def test_ssd_final_state():
    b, l, h, p, g, n = 1, 12, 2, 4, 1, 3
    x, dt = rand(b, l, h, p), jnp.abs(rand(b, l, h)) * 0.1
    A = -jnp.abs(rand(h))
    Bm, Cm, D = rand(b, l, g, n), rand(b, l, g, n), rand(h)
    _, s_final = ssdref.ssd_ref(x, dt, A, Bm, Cm, D, chunk=4,
                                return_state=True)
    state = jnp.zeros((b, h, n, p))
    for t in range(l):
        state, _ = ssdref.ssd_step_ref(state, x[:, t], dt[:, t], A,
                                       Bm[:, t], Cm[:, t], D)
    np.testing.assert_allclose(s_final, state, atol=1e-4, rtol=1e-3)


# --------------------------------------------------------------------------
# rmsnorm
# --------------------------------------------------------------------------

@pytest.mark.parametrize("shape", [(4, 64), (3, 17, 64), (2, 5, 7, 32)])
@pytest.mark.parametrize("residual", [False, True])
def test_rmsnorm_matches_oracle(shape, residual):
    x = rand(*shape)
    w = rand(shape[-1])
    r = rand(*shape) if residual else None
    ref = rref.rmsnorm_ref(x, w, eps=1e-5, residual=r)
    out = rmsnorm_pallas(x, w, eps=1e-5, residual=r, row_block=8,
                         interpret=True)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=1e-4)
