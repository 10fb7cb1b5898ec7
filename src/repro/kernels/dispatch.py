"""Unified kernel dispatch: one registry routing every kernel family to the
Pallas TPU kernel, the Pallas interpreter, or the pure-jax reference.

Why a registry
--------------
The serve/train/benchmark surfaces all need the same decision — "which
implementation of flash_attention/mamba_scan/ssd/rmsnorm runs here?" — and
the answer depends on the detected backend, an env-var override, and (for
the Pallas paths) launch parameters.  Centralizing it means:

- a TPU (v5e, with the installed jax 0.9.0 and libtpu) runs every Pallas
  kernel compiled; CPU hosts (tests, CI) run the reference or the Pallas
  interpreter, without any call-site branching;
- kernel *launch parameters* (block sizes, chunk lengths) become first-class
  configuration options: :func:`launch_space` exposes them as a
  ``repro.core.spaces.ConfigSpace`` so CAMEO tunes them exactly like the
  paper tunes cpu_frequency or swappiness, and :func:`use_launch_config`
  installs a tuned configuration for everything dispatched underneath it.

Modes
-----
``ref`` | ``pallas`` | ``pallas_interpret``; the ``REPRO_KERNEL_MODE`` env
var overrides, otherwise TPU backends get ``pallas`` and everything else
gets ``ref``.  A TPU backend without the Pallas TPU lowering is an error,
never a silent fall back to the reference.

Precedence for launch parameters (highest first): an active tuned config
installed via :func:`use_launch_config` (the tuner speaking — it must win so
a tuned serve/train step does not silently fall back to static defaults),
then explicit call-site keyword arguments, then the registry defaults.
"""

from __future__ import annotations

import contextlib
import functools
import importlib
import inspect
import os
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Iterable, List, Optional, Tuple

import jax

from repro import compat
from repro.core.spaces import ConfigSpace, Option
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace

REF = "ref"
PALLAS = "pallas"
PALLAS_INTERPRET = "pallas_interpret"
MODES = (REF, PALLAS, PALLAS_INTERPRET)

KERNEL_MODE_ENV = "REPRO_KERNEL_MODE"


def detect_backend() -> str:
    """The effective jax backend: 'tpu' | 'gpu' | 'cpu'."""
    return jax.default_backend()


def default_mode(backend: Optional[str] = None) -> str:
    """Dispatch mode before per-call overrides: env var, then backend."""
    env = os.environ.get(KERNEL_MODE_ENV, "")
    if env:
        if env not in MODES:
            raise ValueError(
                f"{KERNEL_MODE_ENV}={env!r} is not one of {MODES}")
        return env
    backend = backend or detect_backend()
    if backend != "tpu":
        return REF
    if not compat.HAS_PALLAS_TPU:
        raise RuntimeError(
            "the backend is a TPU but jax.experimental.pallas.tpu did not "
            "import; set REPRO_KERNEL_MODE=ref to run the references on "
            "purpose")
    return PALLAS


# --------------------------------------------------------------------------
# registry
# --------------------------------------------------------------------------

@dataclass(frozen=True)
class KernelFamily:
    """One kernel family: implementations + its tunable launch surface.

    ``pallas``/``ref`` are lazy ``"module:attr"`` references so importing the
    registry never imports kernel modules (and therefore never requires a
    functional pallas lowering).  ``variants`` holds secondary entry points
    that share the family's launch surface (e.g. decode attention).
    """

    name: str
    pallas: str
    ref: str
    launch_options: Tuple[Option, ...] = ()
    variants: Tuple[Tuple[str, Tuple[str, str]], ...] = ()  # (name, (pallas, ref))

    def option(self, name: str) -> Option:
        for o in self.launch_options:
            if o.name == name:
                return o
        raise KeyError(f"{self.name} has no launch option {name!r}")


_REGISTRY: Dict[str, KernelFamily] = {}


def register_family(fam: KernelFamily) -> KernelFamily:
    if fam.name in _REGISTRY:
        raise ValueError(f"kernel family {fam.name!r} already registered")
    _REGISTRY[fam.name] = fam
    return fam


def get_family(name: str) -> KernelFamily:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"unknown kernel family {name!r}; known: {sorted(_REGISTRY)}")


def families() -> List[str]:
    return sorted(_REGISTRY)


@functools.lru_cache(maxsize=None)
def _load(ref: str) -> Callable:
    module, attr = ref.split(":")
    return getattr(importlib.import_module(module), attr)


def _impl_ref(fam: KernelFamily, mode: str, variant: Optional[str]) -> str:
    pallas, ref = fam.pallas, fam.ref
    if variant is not None:
        pallas, ref = dict(fam.variants)[variant]
    return ref if mode == REF else pallas


def pallas_fn(family: str, variant: Optional[str] = None) -> Callable:
    return _load(_impl_ref(get_family(family), PALLAS, variant))


def ref_fn(family: str, variant: Optional[str] = None) -> Callable:
    return _load(_impl_ref(get_family(family), REF, variant))


# --------------------------------------------------------------------------
# launch configuration
# --------------------------------------------------------------------------

_local = threading.local()


def _active() -> Dict[str, Dict[str, Any]]:
    return getattr(_local, "launch", {})


def split_launch_config(config: Dict[str, Any]) -> Dict[str, Dict[str, Any]]:
    """Normalize flat ``{"family.param": v}`` / nested dicts to nested form.

    Unknown families or parameters raise — a tuned configuration that cannot
    land on a real launch knob is a bug in the space, not noise to ignore.
    """
    nested: Dict[str, Dict[str, Any]] = {}
    for key, val in (config or {}).items():
        if isinstance(val, dict):
            fam_name, params = key, val
        elif "." in key:
            fam_name, pname = key.split(".", 1)
            params = {pname: val}
        else:
            raise KeyError(
                f"launch config key {key!r} is not 'family.param' or nested")
        fam = get_family(fam_name)
        for pname, v in params.items():
            fam.option(pname)  # existence check
            nested.setdefault(fam_name, {})[pname] = v
    return nested


class use_launch_config:
    """Install a tuned launch configuration for dispatches underneath.

    Accepts flat (``{"flash_attention.q_block": 256}``) or nested
    (``{"flash_attention": {"q_block": 256}}``) form; nests are merged over
    any outer active config.  With ``exclusive=True`` the config underneath
    is exactly this one — any outer active config is shadowed, not merged
    (the serve/train step factories use this so a compiled step is a pure
    function of its ``launch_config``, whatever happens to be installed when
    jax finally traces it).  Values are trace-time constants: wrapping the
    traced body of a jit-compiled serve/train step bakes them into that
    trace.  jax's jit cache does NOT see the active config — re-entering an
    already-compiled step under a different config is a cache hit that keeps
    the old launch geometry.  Deploying a new config to a jitted step
    requires a fresh jit (or threading the config through static args — the
    ``launch_config`` argument of the serve/train step factories does the
    former).

    The manager is re-entrant and reusable — one instance may be entered
    recursively, across sequential ``with`` blocks, or from several threads
    at once (the save-stack is per-thread, since the active config is) —
    and the prior configuration is restored on exit even when the body
    raises.  Validation against the registry happens eagerly at
    construction.
    """

    def __init__(self, config: Optional[Dict[str, Any]], *,
                 exclusive: bool = False):
        self._overrides = split_launch_config(config or {})
        self._exclusive = exclusive

    def __enter__(self) -> Dict[str, Dict[str, Any]]:
        prev = _active()
        if self._exclusive:
            merged = {f: dict(p) for f, p in self._overrides.items()}
        else:
            merged = {f: dict(p) for f, p in prev.items()}
            for f, p in self._overrides.items():
                merged.setdefault(f, {}).update(p)
        saved = getattr(_local, "saved_configs", None)
        if saved is None:
            saved = _local.saved_configs = []
        saved.append(prev)
        _local.launch = merged
        return merged

    def __exit__(self, exc_type, exc, tb) -> bool:
        # with-blocks unwind LIFO within a thread, so a plain per-thread
        # stack restores correctly however instances nest or interleave
        _local.launch = _local.saved_configs.pop()
        return False


def launch_params(family: str, **explicit: Any) -> Dict[str, Any]:
    """Resolved launch parameters: active tuned > explicit (non-None) > default."""
    fam = get_family(family)
    out = {o.name: o.default for o in fam.launch_options}
    out.update({k: v for k, v in explicit.items() if v is not None})
    out.update(_active().get(family, {}))
    unknown = set(explicit) - {o.name for o in fam.launch_options}
    if unknown:
        raise KeyError(f"{family} has no launch options {sorted(unknown)}")
    return out


@dataclass(frozen=True)
class Resolution:
    """Outcome of one dispatch decision.  ``backward`` marks the decision a
    differentiated op makes for its backward pass (always the reference)."""
    family: str
    mode: str
    interpret: bool
    launch: Dict[str, Any] = field(default_factory=dict)
    backward: bool = False

    @property
    def impl(self) -> Callable:
        return pallas_fn(self.family) if self.mode != REF else ref_fn(self.family)


@contextlib.contextmanager
def record_resolutions():
    """Observe every dispatch decision made underneath (same thread).

    Yields a list that each :func:`resolve` call appends its
    :class:`Resolution` to — including resolutions made while *tracing* a
    jit-compiled step, which is where launch parameters are baked.  This is
    the ground truth for "did the tuned config reach the kernel call":
    wiring tests and audits read the recorded ``launch`` dicts instead of
    trusting the config plumbing.

    Spies isolate: each nested or concurrent spy gets its OWN result list
    (never a shared one), and the active-spy registry is an immutable
    per-thread tuple — entering or exiting one spy rebuilds the tuple
    instead of mutating a list other spies hold, so an inner spy exiting
    (in any order, e.g. via an ``ExitStack``) can never detach or clobber
    an outer spy's recordings.  Detachment matches by identity, not
    equality: two empty result lists compare equal.
    """
    rec: List[Resolution] = []
    _local.recorders = getattr(_local, "recorders", ()) + (rec,)
    try:
        yield rec
    finally:
        active = getattr(_local, "recorders", ())
        for i in range(len(active) - 1, -1, -1):
            if active[i] is rec:
                _local.recorders = active[:i] + active[i + 1:]
                break


def _notify_recorders(res: Resolution) -> None:
    for rec in getattr(_local, "recorders", ()):
        rec.append(res)


def resolve(family: str, mode: Optional[str] = None, *,
            backward: bool = False, **explicit: Any) -> Resolution:
    mode = mode or default_mode()
    if mode not in MODES:
        raise ValueError(f"mode {mode!r} not one of {MODES}")
    res = Resolution(family=family, mode=mode,
                     interpret=(mode == PALLAS_INTERPRET),
                     launch=launch_params(family, **explicit),
                     backward=backward)
    _notify_recorders(res)
    _notify_profiles(res)
    return res


# --------------------------------------------------------------------------
# dispatch profiling (obs hooks)
# --------------------------------------------------------------------------

class DispatchProfile:
    """Aggregated dispatch telemetry: per-(family, mode) resolution counts
    and wall time spent inside dispatched calls.

    Built on the same notification path as :func:`record_resolutions`, but
    *cross-thread*: a profile observes every resolution process-wide while
    active, because profiling is aggregate bookkeeping (how much, how long),
    not the per-thread wiring ground truth the spy provides.
    """

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self.resolutions: Dict[Tuple[str, str], int] = {}
        self.wall_s: Dict[Tuple[str, str], float] = {}

    def _saw(self, res: Resolution) -> None:
        key = (res.family, res.mode)
        with self._lock:
            self.resolutions[key] = self.resolutions.get(key, 0) + 1

    def _timed(self, family: str, mode: str, dt: float) -> None:
        key = (family, mode)
        with self._lock:
            self.wall_s[key] = self.wall_s.get(key, 0.0) + dt

    def summary(self) -> Dict[str, Dict[str, Any]]:
        """``{"family [mode]": {"resolutions": n, "wall_s": s}}``."""
        out: Dict[str, Dict[str, Any]] = {}
        with self._lock:
            for (fam, mode), n in self.resolutions.items():
                out[f"{fam} [{mode}]"] = {
                    "resolutions": n,
                    "wall_s": round(self.wall_s.get((fam, mode), 0.0), 6)}
            for (fam, mode), s in self.wall_s.items():
                out.setdefault(f"{fam} [{mode}]",
                               {"resolutions": 0})["wall_s"] = round(s, 6)
        return out


_PROFILES: List[DispatchProfile] = []
_PROFILES_LOCK = threading.Lock()


def _notify_profiles(res: Resolution) -> None:
    if _PROFILES:
        with _PROFILES_LOCK:
            active = list(_PROFILES)
        for p in active:
            p._saw(res)


@contextlib.contextmanager
def profile_dispatches():
    """Profile every dispatch made while active (all threads): yields a
    :class:`DispatchProfile` accumulating per-family resolution counts and
    the wall time spent inside dispatched implementations.  When the obs
    tracer is active, each dispatched call additionally exports a span on
    the kernel track and bumps the ``dispatch_resolutions_total`` registry
    instrument."""
    prof = DispatchProfile()
    with _PROFILES_LOCK:
        _PROFILES.append(prof)
    try:
        yield prof
    finally:
        with _PROFILES_LOCK:
            for i in range(len(_PROFILES) - 1, -1, -1):
                if _PROFILES[i] is prof:
                    del _PROFILES[i]
                    break


def dispatch(family: str, *args: Any, mode: Optional[str] = None,
             variant: Optional[str] = None, launch: Optional[Dict] = None,
             **kwargs: Any) -> Any:
    """Generic router: run ``family`` on the resolved implementation.

    Launch parameters the chosen implementation does not accept (e.g.
    ``q_block`` on a reference that has no blocking) are dropped by
    signature inspection, so one launch config drives every mode.

    Note on timing: for jit-compiled callers, ``dispatch`` runs while jax
    *traces* the step, so the profiled wall time is trace/build time — the
    per-family compile cost a tuned launch config pays — not steady-state
    execution time (which the wall-clock measurement backend owns).
    """
    res = resolve(family, mode=mode, **(launch or {}))
    fn = _load(_impl_ref(get_family(family), res.mode, variant))
    accepted = set(inspect.signature(fn).parameters)
    kw = {k: v for k, v in res.launch.items() if k in accepted}
    kw.update(kwargs)
    if res.mode != REF and "interpret" in accepted:
        kw["interpret"] = res.interpret
    if not _PROFILES and not obs_trace.enabled():
        return fn(*args, **kw)
    t0 = time.perf_counter()
    with obs_trace.span(family, cat="dispatch", track=obs_trace.TRACK_KERNEL,
                        mode=res.mode,
                        variant=variant if variant else ""):
        out = fn(*args, **kw)
    dt = time.perf_counter() - t0
    if _PROFILES:
        with _PROFILES_LOCK:
            active = list(_PROFILES)
        for p in active:
            p._timed(family, res.mode, dt)
    if obs_trace.enabled():
        obs_metrics.REGISTRY.inc("dispatch_resolutions_total",
                                 family=family, mode=res.mode)
    return out


# --------------------------------------------------------------------------
# the tunable launch surface
# --------------------------------------------------------------------------

def launch_space(names: Optional[Iterable[str]] = None) -> ConfigSpace:
    """Every registered launch parameter as one CAMEO ``ConfigSpace``.

    Options are prefixed ``family.param`` so the space composes with the
    framework-level space (``repro.tuner.space``) without name collisions.
    """
    opts: List[Option] = []
    for fname in (sorted(names) if names is not None else families()):
        fam = get_family(fname)
        for o in fam.launch_options:
            opts.append(Option(f"{fname}.{o.name}", o.values,
                               default=o.default, kind=o.kind))
    return ConfigSpace(opts)


# --------------------------------------------------------------------------
# built-in families
# --------------------------------------------------------------------------
# Domains are MXU/VPU-aligned recommended-value lists (the analogue of the
# paper's Tables 7-12); defaults match the historical call-site defaults.

register_family(KernelFamily(
    name="flash_attention",
    pallas="repro.kernels.flash_attention.kernel:flash_attention_pallas",
    ref="repro.kernels.flash_attention.ref:attention_blockwise_ref",
    launch_options=(
        Option("q_block", (128, 256, 512, 1024), default=512),
        Option("kv_block", (256, 512, 1024, 2048), default=1024),
    ),
    variants=(
        ("decode", ("repro.kernels.flash_attention.kernel:decode_attention_pallas",
                    "repro.kernels.flash_attention.ref:decode_attention_ref")),
    ),
))

# The paged family's launch surface is consumed by the *serving stack*, not
# the kernel call: ``page_size``/``pages_per_slot_max`` shape the KV pool the
# caches are built with, ``prefill_chunk`` drives the batcher's chunked
# admission (0 = whole-prompt prefill).  Registering them here keeps the
# contract — every kernel-family knob joins ``launch_space()`` — while the
# kernel itself reads the geometry off the pool arrays it is handed.
register_family(KernelFamily(
    # repro: ignore[kernel-option-unused] -- consumed by the serving stack (pool geometry / chunked admission), not the kernel signature; see comment above
    name="paged_attention",
    pallas="repro.kernels.paged_attention.kernel:paged_decode_attention_pallas",
    ref="repro.kernels.paged_attention.ref:paged_decode_attention_ref",
    launch_options=(
        Option("page_size", (32, 64, 128, 256), default=64),
        Option("pages_per_slot_max", (4, 8, 16, 32), default=8),
        Option("prefill_chunk", (0, 64, 128, 256), default=0),
    ),
))

register_family(KernelFamily(
    name="mamba_scan",
    pallas="repro.kernels.mamba_scan.kernel:selective_scan_pallas",
    ref="repro.kernels.mamba_scan.ref:selective_scan_chunked_ref",
    launch_options=(
        Option("chunk", (64, 128, 256, 512), default=256),
        Option("c_block", (128, 256, 512, 1024), default=512),
    ),
))

register_family(KernelFamily(
    name="ssd",
    pallas="repro.kernels.ssd.kernel:ssd_pallas",
    ref="repro.kernels.ssd.ref:ssd_ref",
    launch_options=(
        Option("chunk", (32, 64, 128, 256), default=64),
    ),
))

register_family(KernelFamily(
    name="rmsnorm",
    pallas="repro.kernels.rmsnorm.kernel:rmsnorm_pallas",
    ref="repro.kernels.rmsnorm.ref:rmsnorm_ref",
    launch_options=(
        Option("row_block", (64, 128, 256, 512), default=256),
    ),
))
