"""Pallas TPU kernel for the Mamba-2 SSD chunked algorithm.

TPU adaptation notes
--------------------
The SSD decomposition is MXU-native: per (batch, head, chunk) the work is
three small matmuls — the (chunk x chunk) intra-chunk score matrix, the
(chunk x N) @ (N x P) inter-chunk output, and the (N x chunk) @ (chunk x P)
state update.  We grid over (B, H, chunks) with the chunk dimension
sequential, carrying the (N, P) recurrent state in VMEM scratch.  The chunk
size is the tuning knob trading quadratic intra-chunk FLOPs against the
length of the sequential inter-chunk dependency.

The scratch state is loaded from ``init_state`` at the first chunk and, with
``return_state``, written out after the last, so serving prefill (which needs
the final state) runs this kernel too.

Every block's last two dimensions are (8, 128)-aligned or whole, as the TPU
tiling rule asks: heads sit ahead of positions, the per-step ``dt`` rides as
a (chunk, 1) column and the per-head scalars ``A``/``D`` as whole (1, 1)
blocks.  Cumulative decays are computed as triangular matmuls on the column,
so the kernel never needs a row copy of it.

Layouts: the wrapper takes x (B, L, H, P); dt (B, L, H); A (H,);
Bmat/Cmat (B, L, G, N); D (H,); init_state (B, H, N, P) and returns
y (B, L, H, P) [, final_state (B, H, N, P) float32].  The kernel itself sees
x (B, H, L, P), dt (B, H, L, 1) and Bmat/Cmat (B, G, L, N).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro import compat

_HIGHEST = jax.lax.Precision.HIGHEST


def _mm(a, b, contract=((1,), (0,))):
    return jax.lax.dot_general(a, b, (contract, ((), ())),
                               preferred_element_type=jnp.float32,
                               precision=_HIGHEST)


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, s0_ref, y_ref,
                sf_ref, state_ref, *, chunk: int, n_chunks: int):
    it = pl.program_id(2)

    @pl.when(it == 0)
    def _init():
        state_ref[...] = s0_ref[0, 0].astype(jnp.float32)

    xc = x_ref[0, 0].astype(jnp.float32)              # (chunk, P)
    dtc = dt_ref[0, 0].astype(jnp.float32)            # (chunk, 1)
    a = a_ref[0]                                      # (1, 1)
    Bc = b_ref[0, 0].astype(jnp.float32)              # (chunk, N)
    Cc = c_ref[0, 0].astype(jnp.float32)              # (chunk, N)
    Dh = d_ref[0]                                     # (1, 1)

    log_a = dtc * a                                   # (chunk, 1) <= 0
    row = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    incl = (row >= col).astype(jnp.float32)           # k <= t
    cum = _mm(incl, log_a)                            # (chunk, 1) sum_{k<=t}
    total = jnp.sum(log_a, axis=0, keepdims=True)     # (1, 1)
    xdt = xc * dtc                                    # (chunk, P)

    # intra-chunk quadratic term: L[t,s] = exp(sum_{s<k<=t} log_a[k]), s <= t
    after = (row > col).astype(jnp.float32)          # [k > s] as (k, s)
    seg = _mm(incl, log_a * after)                    # (chunk, chunk)
    Lm = jnp.where(row >= col, jnp.exp(seg), 0.0)
    scores = _mm(Cc, Bc, ((1,), (1,)))                # (chunk, chunk)
    y = _mm(scores * Lm, xdt)                         # (chunk, P)

    # inter-chunk contribution from the carried state
    a_start = jnp.exp(cum)                            # decay start->t inclusive
    y = y + _mm(Cc * a_start, state_ref[...])

    # state update: S <- a_chunk * S + B^T (a_end * xdt)
    a_end = jnp.exp(total - cum)                      # (chunk, 1)
    state_ref[...] = (jnp.exp(total) * state_ref[...]
                      + _mm(Bc, xdt * a_end, ((0,), (0,))))

    y = y + Dh * xc
    y_ref[0, 0] = y.astype(y_ref.dtype)

    @pl.when(it == n_chunks - 1)
    def _final():
        sf_ref[0, 0] = state_ref[...]


def ssd_pallas(
    x: jax.Array,     # (B, L, H, P)
    dt: jax.Array,    # (B, L, H)
    A: jax.Array,     # (H,)
    Bmat: jax.Array,  # (B, L, G, N)
    Cmat: jax.Array,  # (B, L, G, N)
    D: jax.Array,     # (H,)
    *,
    chunk: int = 64,
    init_state=None,  # (B, H, N, P)
    return_state: bool = False,
    interpret: bool = False,
):
    b, l, h, p = x.shape
    g, n = Bmat.shape[2], Bmat.shape[3]
    rep = h // g
    orig_l = l
    chunk = max(8, min(chunk, l))
    if l % chunk != 0:
        # zero-padded steps have dt = 0: no decay and no input, so the
        # carried state (and the returned final state) is unchanged
        pad = chunk - l % chunk
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bmat = jnp.pad(Bmat, ((0, 0), (0, pad), (0, 0), (0, 0)))
        Cmat = jnp.pad(Cmat, ((0, 0), (0, pad), (0, 0), (0, 0)))
        l = x.shape[1]
    n_chunks = l // chunk
    if init_state is None:
        init_state = jnp.zeros((b, h, n, p), jnp.float32)

    kernel = functools.partial(_ssd_kernel, chunk=chunk, n_chunks=n_chunks)
    head = lambda ib, ih, it: (ib, ih, it, 0)
    group = lambda ib, ih, it: (ib, ih // rep, it, 0)
    scalar = lambda ib, ih, it: (ih, 0, 0)
    state = lambda ib, ih, it: (ib, ih, 0, 0)
    y, s_final = pl.pallas_call(
        kernel,
        grid=(b, h, n_chunks),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, p), head),
            pl.BlockSpec((1, 1, chunk, 1), head),
            pl.BlockSpec((1, 1, 1), scalar),
            pl.BlockSpec((1, 1, chunk, n), group),
            pl.BlockSpec((1, 1, chunk, n), group),
            pl.BlockSpec((1, 1, 1), scalar),
            pl.BlockSpec((1, 1, n, p), state),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, p), head),
            pl.BlockSpec((1, 1, n, p), state),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, l, p), x.dtype),
            jax.ShapeDtypeStruct((b, h, n, p), jnp.float32),
        ],
        scratch_shapes=[compat.vmem((n, p), jnp.float32)],
        compiler_params=compat.tpu_compiler_params(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x.transpose(0, 2, 1, 3), dt.transpose(0, 2, 1)[..., None],
      A.astype(jnp.float32).reshape(h, 1, 1),
      Bmat.transpose(0, 2, 1, 3), Cmat.transpose(0, 2, 1, 3),
      D.astype(jnp.float32).reshape(h, 1, 1), init_state)
    y = y.transpose(0, 2, 1, 3)[:, :orig_l]
    if return_state:
        return y, s_final
    return y
