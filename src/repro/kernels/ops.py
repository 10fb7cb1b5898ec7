"""Public kernel entry points.

Each op routes through the unified dispatch registry
(:mod:`repro.kernels.dispatch`):

- on TPU backends the Pallas kernel is used;
- on CPU (this container) the oracle is used for model execution and XLA cost
  analysis, and the Pallas kernels are exercised in ``interpret=True`` mode by
  the tests;
- ``REPRO_KERNEL_MODE`` env var overrides: ``ref`` | ``pallas`` |
  ``pallas_interpret``.

Whatever the mode, the forward runs exactly the implementation the recorded
resolution names, and every op is differentiable: the backward recomputes
through the family's reference (see :func:`_vjp_op`) and records itself as a
``ref`` resolution.  Under a mesh with data axes a Pallas forward runs
inside ``shard_map`` over the batch (see :func:`_batch_parallel`): XLA
cannot partition a Mosaic kernel by itself.

Launch parameters (block sizes, chunk lengths) left as ``None`` resolve
through the registry: an active tuned configuration installed with
``dispatch.use_launch_config`` wins, then the registry defaults.  Explicit
call-site values (e.g. ``par.attn_q_block`` from the parallelism plan) are
honored unless a tuned configuration is active.
"""

from __future__ import annotations

import functools
from typing import Any, Optional, Tuple

import jax
import numpy as np
from jax.sharding import PartitionSpec as P

from repro import compat
from repro.kernels import dispatch
from repro.kernels.mamba_scan import ref as _scan_ref
from repro.kernels.ssd import ref as _ssd_ref
from repro.sharding.specs import data_axes_of


def kernel_mode() -> str:
    return dispatch.default_mode()


# Every op body is wrapped in this named scope.  The HLO analyzer treats ops
# carrying the scope as the interior of ONE Pallas kernel: FLOPs count,
# intermediate HBM round-trips do not (they live in VMEM on the TPU target)
# — only boundary reads/writes are charged.  This is what makes the dry-run
# roofline reflect the TPU kernels rather than the CPU oracle.
KERNEL_SCOPE = "repro_kernel"


def _scoped(name: str):
    return jax.named_scope(f"{KERNEL_SCOPE}.{name}")


def _vjp_op(family: str, name: str, fwd, ref, launch: Tuple):
    """custom_vjp with a flash-attention-style backward contract: save only
    the op INPUTS, recompute the family's reference inside the backward and
    differentiate there.  The forward is ``fwd`` — the Pallas kernel or the
    reference itself — so a kernel without a backward kernel still trains,
    and recomputing kills jax's per-iteration residual stacking through the
    scanned references (which would re-materialize the S^2 / (L,C,N)
    intermediates the kernels exist to avoid).  The backward resolves the
    family in ``ref`` mode with the forward's launch parameters, so the
    dispatch record says what ran."""

    @jax.custom_vjp
    def op(*args):
        with _scoped(name):
            return fwd(*args)

    def fwd_rule(*args):
        with _scoped(name):
            return fwd(*args), args

    def bwd_rule(args, dy):
        dispatch.resolve(family, mode=dispatch.REF, backward=True,
                         **dict(launch))
        with _scoped(name + "_bwd"):
            _, vjp = jax.vjp(ref, *args)
            return vjp(dy)

    op.defvjp(fwd_rule, bwd_rule)
    return op


def _batch_parallel(fn, batched: Tuple[bool, ...]):
    """Run ``fn`` per data shard when a mesh with data axes is active.

    Every kernel here is independent along the leading batch axis of the
    arguments flagged in ``batched`` (the rest — weights, per-head scalars,
    a shared page pool — are replicated), so ``shard_map`` over the data
    axes is exact.  A batch the data axes do not divide runs replicated."""

    def call(*args):
        mesh = compat.get_abstract_mesh()
        daxes = data_axes_of(tuple(mesh.axis_names)) if mesh else ()
        if not daxes:
            return fn(*args)
        dsize = int(np.prod([mesh.shape[a] for a in daxes]))
        split = all(a.shape[0] % dsize == 0
                    for a, b in zip(args, batched) if b)
        spec = P(daxes) if split else P()
        in_specs = tuple(spec if b else P() for b in batched)
        return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                             out_specs=spec, check_vma=False)(*args)
    return call


@functools.lru_cache(maxsize=256)
def _op(family: str, variant: Optional[str], name: str, mode: str,
        launch: Tuple, kw: Tuple, pallas_kw: Tuple,
        tail: Tuple[str, ...], batched: Tuple[bool, ...]):
    """The differentiable op for one (family, mode, static arguments) key.

    ``kw`` are static keywords both implementations take, ``pallas_kw`` the
    kernel's own launch keywords, ``tail`` names trailing positional arrays
    that the implementations take by keyword (``init_state``, ``residual``)
    and ``batched`` flags the positional arrays with a leading batch axis."""

    def bind(fn, extra):
        def call(*args):
            n = len(args) - len(tail)
            return fn(*args[:n], **dict(zip(tail, args[n:])), **extra)
        return call

    ref = bind(dispatch.ref_fn(family, variant), dict(kw))
    if mode == dispatch.REF:
        fwd = ref
    else:
        fwd = _batch_parallel(
            bind(dispatch.pallas_fn(family, variant),
                 dict(kw + pallas_kw,
                      interpret=(mode == dispatch.PALLAS_INTERPRET))),
            batched)
    return _vjp_op(family, name, fwd, ref, launch)


def _run(res: dispatch.Resolution, name: str, args, batched, *,
         variant: Optional[str] = None, kw: Tuple = (),
         pallas_kw: Tuple = (), tail: Tuple[str, ...] = ()) -> Any:
    op = _op(res.family, variant, name, res.mode,
             tuple(sorted(res.launch.items())), kw, pallas_kw, tail,
             tuple(batched[:len(args)]))
    return op(*args)


# --------------------------------------------------------------------------
# attention
# --------------------------------------------------------------------------

def flash_attention(q, k, v, *, causal=True, sliding_window=0, logit_softcap=0.0,
                    scale=None, q_offset=0, q_block=None, kv_block=None):
    res = dispatch.resolve("flash_attention", q_block=q_block,
                           kv_block=kv_block)
    # the reference is the blockwise online softmax: its HLO mirrors the
    # kernel's streaming structure
    return _run(res, "flash_attention", (q, k, v), (True,) * 3,
                kw=(("causal", causal), ("sliding_window", sliding_window),
                    ("logit_softcap", logit_softcap), ("scale", scale),
                    ("q_offset", q_offset),
                    ("kv_block", res.launch["kv_block"])),
                pallas_kw=(("q_block", res.launch["q_block"]),))


def decode_attention(q, k_cache, v_cache, cache_len, *, sliding_window=0,
                     logit_softcap=0.0, scale=None, kv_block=None):
    """Single-token decode over a heads-major (B, Hkv, S, D) cache."""
    res = dispatch.resolve("flash_attention", kv_block=kv_block)
    return _run(res, "decode_attention", (q, k_cache, v_cache, cache_len),
                (True,) * 4, variant="decode",
                kw=(("sliding_window", sliding_window),
                    ("logit_softcap", logit_softcap), ("scale", scale)),
                pallas_kw=(("kv_block", res.launch["kv_block"]),))


def paged_decode_attention(q, k_pages, v_pages, page_table, cache_len, *,
                           logit_softcap=0.0, scale=None):
    """Single-token decode over a block-paged (P, Hkv, page_size, D) pool.

    The family's launch options (``page_size``, ``pages_per_slot_max``,
    ``prefill_chunk``) shape the pool the caller built, not this call — the
    kernel reads its geometry off the arrays.  Resolving the family here
    still records the decision (mode + launch) for the dispatch audit.
    """
    res = dispatch.resolve("paged_attention")
    return _run(res, "paged_decode_attention",
                (q, k_pages, v_pages, page_table, cache_len),
                (True, False, False, True, True), kw=(("logit_softcap", logit_softcap), ("scale", scale)))


# --------------------------------------------------------------------------
# mamba-1 selective scan
# --------------------------------------------------------------------------

def selective_scan(x, dt, A, Bmat, Cmat, D, *, chunk=None, c_block=None,
                   return_state=False):
    res = dispatch.resolve("mamba_scan", chunk=chunk, c_block=c_block)
    return _run(res, "selective_scan", (x, dt, A, Bmat, Cmat, D),
                (True, True, False, True, True, False),
                kw=(("chunk", res.launch["chunk"]),
                    ("return_state", return_state)),
                pallas_kw=(("c_block", res.launch["c_block"]),))


def selective_scan_step(h, x_t, dt_t, A, B_t, C_t, D):
    with _scoped("selective_scan_step"):
        return _scan_ref.selective_scan_step_ref(h, x_t, dt_t, A, B_t, C_t, D)


# --------------------------------------------------------------------------
# mamba-2 SSD
# --------------------------------------------------------------------------

def ssd(x, dt, A, Bmat, Cmat, D, *, chunk=None, init_state=None,
        return_state=False):
    res = dispatch.resolve("ssd", chunk=chunk)
    args = (x, dt, A, Bmat, Cmat, D)
    if init_state is not None:
        args += (init_state,)
    return _run(res, "ssd", args,
                (True, True, False, True, True, False, True),
                kw=(("chunk", res.launch["chunk"]),
                    ("return_state", return_state)),
                tail=("init_state",) if init_state is not None else ())


def ssd_step(state, x_t, dt_t, A, B_t, C_t, D):
    with _scoped("ssd_step"):
        return _ssd_ref.ssd_step_ref(state, x_t, dt_t, A, B_t, C_t, D)


# --------------------------------------------------------------------------
# rmsnorm
# --------------------------------------------------------------------------

def rmsnorm(x, weight, *, eps=1e-5, residual=None, row_block=None):
    res = dispatch.resolve("rmsnorm", row_block=row_block)
    args = (x, weight) if residual is None else (x, weight, residual)
    return _run(res, "rmsnorm", args, (True, False, True), kw=(("eps", eps),),
                pallas_kw=(("row_block", res.launch["row_block"]),),
                tail=("residual",) if residual is not None else ())
