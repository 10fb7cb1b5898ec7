"""Serve-step factories: prefill / decode / generate.

``make_prefill_step`` runs the full prompt through the model, filling the KV
caches (attention) or computing the final recurrent state (SSM), and returns
the last-position logits.  ``make_decode_step`` advances one token per batch
element against the cached state — this is the function the ``decode_*`` and
``long_*`` dry-run shapes lower.

State layout follows the training-side scan: caches are stacked over
super-blocks so decode lowers to a single ``lax.scan`` over layers.

Both factories take an optional ``launch_config`` (flat ``family.param`` or
nested dict, e.g. ``TuneResult.launch_config`` from a kernel-launch tuning
run): the step body runs under an *exclusive* ``dispatch.use_launch_config``
so exactly the tuned block sizes / chunk lengths are baked into the trace —
an ambient installed config cannot leak in, which is what lets
:func:`jitted_steps` cache compiled (prefill, decode) pairs per
(model, run, cache_len, launch_config) soundly (jax traces lazily, whenever
the first call happens).  To deploy a tuned optimum to a step, pass it here;
``use_launch_config`` alone cannot reach an already-compiled step.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import dispatch
from repro.models import encdec
from repro.models.model import Model
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.utils.config import RunConfig


def freeze_launch_config(launch_config: Optional[Dict[str, Any]]
                         ) -> Tuple[Tuple[str, Tuple[Tuple[str, Any], ...]], ...]:
    """Hashable canonical form of a launch config (flat or nested) — the jit
    cache key component, so equivalent spellings share one compilation."""
    if not launch_config:
        return ()
    nested = dispatch.split_launch_config(launch_config)
    return tuple((f, tuple(sorted(p.items()))) for f, p in sorted(nested.items()))


class ServeState(NamedTuple):
    caches: Any           # stacked per-super-block decode caches
    lengths: jax.Array    # (B,) int32 tokens consumed so far
    extras: Dict[str, jax.Array]  # enc_out / vision_embeds, static per request


def make_prefill_step(model: Model, run: RunConfig,
                      cache_len: Optional[int] = None,
                      launch_config: Optional[Dict[str, Any]] = None
                      ) -> Callable[..., Tuple[ServeState, jax.Array]]:
    """Returns prefill(params, batch) -> (ServeState, last_logits (B, V))."""
    cfg = model.cfg
    max_len = cache_len or run.shape.seq_len
    dispatch.split_launch_config(launch_config or {})  # eager validation

    def prefill_step(params, batch: Dict) -> Tuple[ServeState, jax.Array]:
      # exclusive: the trace depends only on launch_config, never on an
      # ambient use_launch_config active when jax happens to trace — that
      # determinism is what makes the jitted_steps cache sound
      with dispatch.use_launch_config(launch_config, exclusive=True):
        tokens = batch["tokens"]
        b, s = tokens.shape
        caches = model.init_decode_state(b, max_len)
        extras: Dict[str, jax.Array] = {}
        if cfg.family == "audio":
            par = run.parallel
            enc_out = encdec.encode(params, cfg, par, batch["frames"])
            extras["enc_out"] = enc_out
            logits, new_caches = encdec.decode_forward(
                params, cfg, par, tokens, enc_out, decode_state=caches,
                decode=False)
        else:
            fkw = {}
            if cfg.family == "vlm":
                extras["vision_embeds"] = batch["vision_embeds"]
                fkw["vision_embeds"] = batch["vision_embeds"]
            logits, new_caches, _ = model.forward(
                params, tokens, decode_state=caches, decode=False, **fkw)
        lengths = jnp.full((b,), s, jnp.int32)
        return ServeState(new_caches, lengths, extras), logits[:, -1]

    return prefill_step


def make_decode_step(model: Model, run: RunConfig,
                     launch_config: Optional[Dict[str, Any]] = None
                     ) -> Callable[..., Tuple[ServeState, jax.Array]]:
    """Returns decode(params, state, tokens (B,1)) -> (state', logits (B, V))."""
    cfg = model.cfg
    dispatch.split_launch_config(launch_config or {})  # eager validation

    def decode_step(params, state: ServeState, tokens: jax.Array
                    ) -> Tuple[ServeState, jax.Array]:
      with dispatch.use_launch_config(launch_config, exclusive=True):
        positions = state.lengths[:, None]  # (B, 1) per-request positions
        if cfg.family == "audio":
            logits, new_caches = encdec.decode_forward(
                params, cfg, run.parallel, tokens, state.extras["enc_out"],
                positions=positions, decode_state=state.caches, decode=True)
        else:
            fkw = {}
            if cfg.family == "vlm":
                fkw["vision_embeds"] = state.extras["vision_embeds"]
            logits, new_caches, _ = model.forward(
                params, tokens, positions=positions, decode_state=state.caches,
                decode=True, **fkw)
        new_state = ServeState(new_caches, state.lengths + 1, state.extras)
        return new_state, logits[:, -1]

    return decode_step


# --------------------------------------------------------------------------
# compiled-step cache
# --------------------------------------------------------------------------

@functools.lru_cache(maxsize=32)
def _jitted_steps_cached(model: Model, run: RunConfig,
                         cache_len: Optional[int], frozen_launch: Tuple,
                         kernel_mode: str) -> Tuple[Callable, Callable]:
    del kernel_mode  # a key only: the mode is read again at trace time
    launch_config = {f: dict(p) for f, p in frozen_launch}
    return (jax.jit(make_prefill_step(model, run, cache_len=cache_len,
                                      launch_config=launch_config)),
            jax.jit(make_decode_step(model, run,
                                     launch_config=launch_config)))


def jitted_steps(model: Model, run: RunConfig,
                 cache_len: Optional[int] = None,
                 launch_config: Optional[Dict[str, Any]] = None
                 ) -> Tuple[Callable, Callable]:
    """Cached jit-compiled ``(prefill, decode)`` for this serving setup.

    Keyed on (model, run, cache_len, canonical launch config, kernel mode)
    — ``Model`` is a NamedTuple of config + closures, hashable by identity
    of those closures — so repeated :func:`generate` calls and serving loops
    reuse compilations instead of retracing, while a *different* tuned
    launch config or kernel mode (``REPRO_KERNEL_MODE``) correctly gets a
    fresh trace (both are baked in at trace time).  LRU-bounded so
    long-lived processes cycling through many models do not pin every
    compilation.
    """
    key = (model, run, cache_len, freeze_launch_config(launch_config),
           dispatch.default_mode())
    if not obs_trace.enabled():
        return _jitted_steps_cached(*key)
    before = _jitted_steps_cached.cache_info()
    steps = _jitted_steps_cached(*key)
    after = _jitted_steps_cached.cache_info()
    hit = after.hits > before.hits
    obs_metrics.REGISTRY.inc(
        "jit_cache_hits" if hit else "jit_cache_misses")
    obs_trace.instant("jit_cache_hit" if hit else "jit_cache_miss",
                      cat="jit_cache", track=obs_trace.TRACK_KERNEL,
                      cache_len=cache_len if cache_len is not None else -1,
                      currsize=after.currsize)
    return steps


# --------------------------------------------------------------------------
# generation loop (examples / integration tests)
# --------------------------------------------------------------------------

def sample_token(logits: jax.Array, key: jax.Array,
                 temperature: Any = 0.0) -> jax.Array:
    """logits (B, V) -> (B,) int32. temperature 0 = greedy.

    A scalar temperature applies to every row; an array of shape (B,) samples
    each row at its own temperature (0 rows decode greedily) — the mixed
    temperature case a continuous batcher hits when requests with different
    sampling settings share one decode step."""
    if jnp.ndim(temperature) == 0:
        if temperature <= 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return jax.random.categorical(key, logits / temperature,
                                      axis=-1).astype(jnp.int32)
    temps = jnp.asarray(temperature, logits.dtype)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    safe = jnp.where(temps > 0.0, temps, 1.0)
    sampled = jax.random.categorical(key, logits / safe[:, None],
                                     axis=-1).astype(jnp.int32)
    return jnp.where(temps > 0.0, sampled, greedy)


def generate(model: Model, run: RunConfig, params, batch: Dict, *,
             num_steps: int, temperature: float = 0.0, seed: int = 0,
             cache_len: Optional[int] = None,
             launch_config: Optional[Dict[str, Any]] = None) -> jax.Array:
    """Prefill + autoregressive decode. Returns generated tokens (B, steps).

    Steps come from :func:`jitted_steps`, so repeated generation with the
    same shapes/config reuses the compiled prefill/decode instead of
    retracing on every call."""
    prompt = batch["tokens"]
    b = prompt.shape[0]
    cache_len = cache_len or (prompt.shape[1] + num_steps)
    prefill, decode = jitted_steps(model, run, cache_len=cache_len,
                                   launch_config=launch_config)

    state, logits = prefill(params, batch)
    key = jax.random.PRNGKey(seed)

    toks = []
    tok = sample_token(logits, key, temperature)
    toks.append(tok)
    for i in range(num_steps - 1):
        key, sub = jax.random.split(key)
        state, logits = decode(params, state, tok[:, None])
        tok = sample_token(logits, sub, temperature)
        toks.append(tok)
    return jnp.stack(toks, axis=1)
