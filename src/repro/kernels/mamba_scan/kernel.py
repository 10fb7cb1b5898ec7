"""Pallas TPU kernel for the Mamba-1 selective scan.

TPU adaptation notes
--------------------
The CUDA selective-scan kernel parallelizes over channels within a thread
block and keeps state in registers.  On TPU we tile channels into VMEM blocks
(``c_block`` lanes) and keep the (N, c_block) recurrent state in VMEM
scratch, channels on lanes.  The sequence is processed in ``chunk``-sized
HBM->VMEM blocks (the sequential "arbitrary" grid dimension); inside a chunk
the recurrence runs as a ``fori_loop`` over time.  Each step reads its row
straight from a VMEM ref — never by slicing a loaded value, which the TPU
lowering does not support — and the per-step B/C vectors arrive as (N, 1)
columns on a leading time axis, so the update is a pure (N, c_block) VPU op
plus a sublane reduction.

Layouts: x/dt (B, L, C); A (C, N); Bmat/Cmat (B, L, N); D (C,); y (B, L, C);
the optional final state is (B, C, N) float32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro import compat


def _scan_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, y_ref, hf_ref,
                 h_ref, xs_ref, dts_ref, ys_ref, *, chunk: int, n_chunks: int):
    it = pl.program_id(2)

    @pl.when(it == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    xs_ref[...] = x_ref[0].astype(jnp.float32)        # (chunk, Cb)
    dts_ref[...] = dt_ref[0].astype(jnp.float32)      # (chunk, Cb)
    At = a_ref[...]                                   # (N, Cb)

    def step(t, h):
        x_t = xs_ref[pl.ds(t, 1), :]                  # (1, Cb)
        dt_t = dts_ref[pl.ds(t, 1), :]                # (1, Cb)
        h = jnp.exp(dt_t * At) * h + b_ref[0, t] * (dt_t * x_t)
        ys_ref[pl.ds(t, 1), :] = jnp.sum(h * c_ref[0, t], axis=0,
                                         keepdims=True)
        return h

    h_ref[...] = jax.lax.fori_loop(0, chunk, step, h_ref[...])
    y_ref[0] = (ys_ref[...] + d_ref[...] * xs_ref[...]).astype(y_ref.dtype)

    @pl.when(it == n_chunks - 1)
    def _final():
        hf_ref[0] = h_ref[...]


def selective_scan_pallas(
    x: jax.Array,     # (B, L, C)
    dt: jax.Array,    # (B, L, C)
    A: jax.Array,     # (C, N)
    Bmat: jax.Array,  # (B, L, N)
    Cmat: jax.Array,  # (B, L, N)
    D: jax.Array,     # (C,)
    *,
    chunk: int = 256,
    c_block: int = 512,
    return_state: bool = False,
    interpret: bool = False,
):
    b, l, c = x.shape
    n = A.shape[1]
    orig_l = l
    chunk = max(8, min(chunk, l))
    if l % chunk != 0:
        # zero-padded steps have dt = 0: they leave the state unchanged
        pad = chunk - l % chunk
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bmat = jnp.pad(Bmat, ((0, 0), (0, pad), (0, 0)))
        Cmat = jnp.pad(Cmat, ((0, 0), (0, pad), (0, 0)))
        l = x.shape[1]
    c_block = min(c_block, c)
    while c % c_block != 0:
        c_block //= 2
    n_cb = c // c_block
    n_chunks = l // chunk

    kernel = functools.partial(_scan_kernel, chunk=chunk, n_chunks=n_chunks)
    seq = lambda ib, ic, it: (ib, it, ic)
    col = lambda ib, ic, it: (ib, it, 0, 0)
    y, h_final = pl.pallas_call(
        kernel,
        grid=(b, n_cb, n_chunks),
        in_specs=[
            pl.BlockSpec((1, chunk, c_block), seq),
            pl.BlockSpec((1, chunk, c_block), seq),
            pl.BlockSpec((n, c_block), lambda ib, ic, it: (0, ic)),
            pl.BlockSpec((1, chunk, n, 1), col),
            pl.BlockSpec((1, chunk, n, 1), col),
            pl.BlockSpec((1, c_block), lambda ib, ic, it: (0, ic)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, c_block), seq),
            pl.BlockSpec((1, n, c_block), lambda ib, ic, it: (ib, 0, ic)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, l, c), x.dtype),
            jax.ShapeDtypeStruct((b, n, c), jnp.float32),
        ],
        scratch_shapes=[compat.vmem((n, c_block), jnp.float32),
                        compat.vmem((chunk, c_block), jnp.float32),
                        compat.vmem((chunk, c_block), jnp.float32),
                        compat.vmem((chunk, c_block), jnp.float32)],
        compiler_params=compat.tpu_compiler_params(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, dt, A.astype(jnp.float32).T,
      Bmat.astype(jnp.float32)[..., None], Cmat.astype(jnp.float32)[..., None],
      D.astype(jnp.float32)[None, :])
    y = y[:, :orig_l]
    if return_state:
        return y, h_final.transpose(0, 2, 1)
    return y
