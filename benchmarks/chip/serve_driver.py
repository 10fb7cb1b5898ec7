"""Open-loop serving on the wall clock over the batcher's public calls.

Set-up builds the system under test from a configuration file: the
program's model at the file's sizes, seeded weights made on the device
(``weights.py``), the paged ``ContinuousBatcher`` with the file's geometry,
and the program's persistent compile cache.  It warms every prefill length
and page count that the run's requests will use and the decode step, and
checks that every served forward ran a compiled Pallas kernel (``pallas``;
the CPU tests ask for ``pallas_interpret`` instead).

The window then submits each request once its due time has passed, calls
``tick()``, and timestamps every token it sees delivered.  Time to first
token is taken from the due time, so a stall shows on every request that
waits behind it; a request due in the window whose first token has not come
by the window's end counts with the wait it has had so far.  The gaps
between tokens are those of every request, inside the window.

After the window, what was served is checked against the configuration's
plain reference (``correct.py``).
"""

from __future__ import annotations

import gc
import os
import shutil
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

import numpy as np

import harness


@dataclass
class Served:
    """One request as the client saw it."""
    uid: int
    due_s: float
    prompt: np.ndarray
    max_new_tokens: int
    submitted_s: Optional[float] = None
    token_s: List[float] = field(default_factory=list)
    tokens: List[int] = field(default_factory=list)
    finished: bool = False


@dataclass
class TickRecord:
    """What one traced tick did: prompt lengths prefilled, and the context
    length each decoded slot attended over."""
    prefills: List[int]
    decode_lens: List[int]


class System:
    """The program under test, built from a configuration file and a seed."""

    def __init__(self, cfg_file: Dict[str, Any], seed: int,
                 kernel_mode: str = "pallas"):
        import jax
        from repro.kernels import dispatch
        from repro.models.model import build_model
        from repro.serving.paging import PagedPlan
        from repro.utils.config import RunConfig, ShapeConfig

        import weights

        self.cfg_file = cfg_file
        self.mc = harness.model_config(cfg_file)
        geo = cfg_file["serving"]
        self.plan = PagedPlan(paging=True, pool_pages=geo["pool_pages"],
                              page_size=geo["page_size"],
                              pages_per_slot_max=geo["pages_per_slot_max"])
        self.num_slots = geo["num_slots"]
        self.run = RunConfig(model=self.mc, shape=ShapeConfig(
            "chipbench", self.plan.slot_capacity, self.num_slots, "decode"))
        self.model = build_model(self.mc, self.run.parallel)
        self.params = weights.make_params(self.model, harness.jax_key(seed))
        self.seed = seed
        self.devices = jax.devices()
        # every served forward has to resolve to this mode
        if dispatch.default_mode() != kernel_mode:
            raise RuntimeError(
                f"kernels would run {dispatch.default_mode()!r}, not "
                f"{kernel_mode!r}: unset {dispatch.KERNEL_MODE_ENV}")
        self.kernel_mode = kernel_mode
        self.checked = False    # served resolutions seen to be compiled

    def batcher(self):
        from repro.serving.scheduler import ContinuousBatcher
        return ContinuousBatcher(self.model, self.run, self.params,
                                 num_slots=self.num_slots, paged=self.plan,
                                 seed=self.seed, on_too_long="reject")


def warm(system: System, batcher, requests) -> Dict[str, Any]:
    """Compile and load every shape the window's requests use: each prefill
    length, the decode step, and the batcher's admission scatter for each
    page count.  Returns what was warmed."""
    import jax
    from repro.kernels import dispatch
    from repro.serving import scheduler
    from repro.serving.scheduler import Request

    plan = system.plan
    lens = sorted({len(r.prompt) for r in requests})
    pages = sorted({plan.pages_for(len(r.prompt) + r.max_new_tokens - 1)
                    for r in requests} & set(
                        range(1, plan.pages_per_slot_max + 1)))
    rng = harness.numpy_rng(system.seed, salt=1)
    done_before = len(batcher.completed)
    with dispatch.record_resolutions() as rec:
        for i, n in enumerate(lens):
            batcher.submit(Request(
                uid=-1 - i, prompt=rng.integers(
                    0, system.mc.vocab_size, n, dtype=np.int32),
                max_new_tokens=2))
        batcher.run_until_drained()
    if len(batcher.completed) - done_before != len(lens):
        raise RuntimeError("warm-up requests did not all complete")
    mode = system.kernel_mode
    bad = [r for r in rec if not r.backward and r.mode != mode]
    # the steps trace once per model: a later batcher records nothing
    if bad or not (rec or system.checked):
        raise RuntimeError(f"served forwards not all compiled {mode}: {bad}")
    system.checked = True
    # the batcher scatters a prefilled request into its reserved pages
    # outside jit, so each page count is a shape of its own; one copy of
    # the state at a time, as an admission makes
    one_state, _ = batcher._prefill(
        system.params, {"tokens": np.zeros((1, lens[0]), np.int32)})
    for n in pages:
        jax.block_until_ready(scheduler._scatter_paged_rows(
            batcher.state.caches, one_state.caches, 0, list(range(n)),
            plan.page_size, plan.pages_per_slot_max,
            scratch_page=plan.pool_pages))
    del one_state
    batcher.completed.clear()
    return {"prefill_lengths": lens, "page_counts": pages,
            "resolutions": sorted({(r.family, r.mode) for r in rec})}


class CompileCounter:
    """Counts backend compilations while it is armed (JAX's monitoring
    events), so a compile inside the window shows."""

    def __init__(self):
        import jax
        self.armed = False
        self.count = 0

        def on_event(event, duration, **kw):
            if self.armed and event == "/jax/core/compile/backend_compile_duration":
                self.count += 1

        jax.monitoring.register_event_duration_secs_listener(on_event)


def _observe(batcher, by_uid: Dict[int, Served], t: float,
             seen: Dict[int, int]) -> TickRecord:
    """Timestamp the tokens the last tick delivered; say what it did."""
    states = [s for s in batcher._slots if s is not None] + batcher.completed
    prefills, decode_lens = [], []
    for rs in states:
        uid = rs.request.uid
        served = by_uid.get(uid)
        if served is None:
            continue
        n = len(rs.generated)
        before = seen.get(uid, 0)
        if n > before:
            for tok in rs.generated[before:]:
                served.tokens.append(int(tok))
                served.token_s.append(t)
            if before == 0:
                prefills.append(len(served.prompt))
            if n - before > 1 or before > 0:
                decode_lens.append(len(served.prompt) + n - 1)
            seen[uid] = n
        if rs.done:
            served.finished = True
    batcher.completed.clear()
    return TickRecord(prefills, decode_lens)


def _pct(values, q) -> Optional[float]:
    return float(np.percentile(np.asarray(values), q)) if len(values) else None


def serve_window(batcher, requests, seconds: float, *,
                 trace_dir: Optional[str] = None,
                 compile_counter: Optional[CompileCounter] = None,
                 ) -> Dict[str, Any]:
    """Drive the batcher over ``requests`` for ``seconds`` of wall clock.
    With ``trace_dir`` the profiler records a stretch of at least
    ``trace_s`` seconds from 30% into the window."""
    import jax
    from repro.serving.scheduler import Request

    served = [Served(r.uid, r.due_s, r.prompt, r.max_new_tokens)
              for r in requests]
    by_uid = {s.uid: s for s in served}
    seen: Dict[int, int] = {}
    ticks: List[TickRecord] = []
    traced: List[TickRecord] = []
    trace_start = 0.3 * seconds
    trace_s = min(6.0, 0.4 * seconds)
    tracing = False
    trace_began = None
    lateness = []
    tick_wall = 0.0
    n_ticks = 0
    pf0, dc0 = batcher.prefill_s, batcher.decode_s
    occ0 = batcher.mean_occupancy * batcher.ticks
    bt0 = batcher.ticks
    rejected0 = batcher.rejected_too_long
    i = 0
    if compile_counter is not None:
        compile_counter.armed = True
    t0 = time.perf_counter()
    while True:
        t = time.perf_counter() - t0
        if t >= seconds:
            break
        if trace_dir and not tracing and trace_began is None \
                and t >= trace_start:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            tracing, trace_began = True, t
        with jax.profiler.TraceAnnotation("bench.submit"):
            while i < len(served) and served[i].due_s <= t:
                s = served[i]
                batcher.submit(Request(uid=s.uid, prompt=s.prompt,
                                       max_new_tokens=s.max_new_tokens))
                s.submitted_s = time.perf_counter() - t0
                lateness.append(s.submitted_s - s.due_s)
                i += 1
        busy = batcher.queue or any(s is not None for s in batcher._slots)
        if not busy:
            nxt = served[i].due_s if i < len(served) else seconds
            with jax.profiler.TraceAnnotation("bench.wait"):
                time.sleep(max(0.0, min(nxt, seconds) - t))
        else:
            w0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.tick"):
                batcher.tick()
            w1 = time.perf_counter()
            tick_wall += w1 - w0
            n_ticks += 1
            rec = _observe(batcher, by_uid, w1 - t0, seen)
            ticks.append(rec)
            if tracing:
                traced.append(rec)
        if tracing and (time.perf_counter() - t0 - trace_began >= trace_s
                        and sum(len(r.prefills) for r in traced) >= 2):
            jax.profiler.stop_trace()
            tracing = False
    end = time.perf_counter() - t0
    if tracing:
        jax.profiler.stop_trace()
    if compile_counter is not None:
        compile_counter.armed = False
    due = [s for s in served if s.due_s < seconds]
    ttft = [((s.token_s[0] if s.token_s else end) - s.due_s) for s in due]
    itl = []
    for s in served:
        itl.extend(np.diff(s.token_s).tolist())
    out_tokens = sum(len(s.token_s) for s in served)
    occ = batcher.mean_occupancy * batcher.ticks - occ0
    return {
        "served": served,
        "window_s": end,
        "attempted": len(due),
        "failed": batcher.rejected_too_long - rejected0,
        "output_tokens": out_tokens,
        "output_tok_s": out_tokens / end,
        "ttft_ms": [1e3 * x for x in ttft],
        "itl_ms": [1e3 * x for x in itl],
        "lateness_s": lateness,
        "ticks": n_ticks,
        "tick_wall_s": tick_wall,
        "prefill_s": batcher.prefill_s - pf0,
        "decode_s": batcher.decode_s - dc0,
        "batcher_ticks": batcher.ticks - bt0,
        "occupancy_sum": occ,
        "tick_records": ticks,
        "traced_ticks": traced,
        "finished": sum(s.finished for s in served),
        "compiles_in_window": (compile_counter.count
                               if compile_counter is not None else None),
    }


def end_to_end(win: Dict[str, Any]) -> Dict[str, float]:
    """The end-to-end numbers of one window; a cell reports those that
    ``BENCHMARK.json`` names for it."""
    return {
        "output_tok_s": win["output_tok_s"],
        "ttft_p50_ms": _pct(win["ttft_ms"], 50),
        "ttft_p90_ms": _pct(win["ttft_ms"], 90),
        "itl_p50_ms": _pct(win["itl_ms"], 50),
        "itl_p95_ms": _pct(win["itl_ms"], 95),
        "itl_p99_ms": _pct(win["itl_ms"], 99),
    }


@dataclass
class LayerContext:
    """What a per-layer metric reader gets: the window's host-side counts,
    the traced ticks, the trace reduction, the model and the chip's peaks."""
    window: Dict[str, Any]
    num_slots: int
    model: Dict[str, Any]
    reference: Any                  # the configuration's reference module
    peaks: Dict[str, float]
    bench: Any
    reduction: Any = None           # trace_reduce.Reduction or None


def enable_cache() -> str:
    """The program's persistent compile cache, for every program: eager ones
    too are then read back on the next run instead of compiled again."""
    import jax
    from repro.utils.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return cache


def run_cell(bench, cell: Dict[str, Any], seed: int, seconds: float,
             trace: bool, t_start: float, *,
             fault: Optional[Callable[[Any], None]] = None,
             trace_dir: Optional[str] = None,
             kernel_mode: str = "pallas") -> Dict[str, Any]:
    """One run of a serving cell; returns the result object's fields and
    the numbers compared, in the order the result line prints them."""
    import correct
    import peaks as peaks_mod
    import trace_reduce

    cfg_file = bench.config(cell["config"])
    mix = bench.traffic(cell["traffic"])
    kind = bench.traffic_kind(mix["kind"])
    system = System(cfg_file, seed, kernel_mode)
    requests = kind.generate(mix, seed, seconds, system.mc.vocab_size)
    batcher = system.batcher()
    if fault is not None:
        fault(batcher)
    warmed = warm(system, batcher, requests)
    harness.say(f"warmed {warmed}")
    counter = CompileCounter()
    setup_s = time.perf_counter() - t_start
    if trace and trace_dir is None:
        trace_dir = os.path.join(harness.REPO, "chipbench_out", "trace",
                                 cell["name"])
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
    win = serve_window(batcher, requests, seconds,
                       trace_dir=trace_dir if trace else None,
                       compile_counter=counter)
    late = np.asarray(win["lateness_s"]) * 1e3
    harness.say(
        f"window {win['window_s']:.3f}s: {win['attempted']} due, "
        f"{win['finished']} finished, {win['output_tokens']} tokens, "
        f"{win['ticks']} ticks, compiles in window "
        f"{win['compiles_in_window']}; generator late p50 "
        f"{_pct(late, 50)} ms, max {late.max() if len(late) else None} ms")
    peak = harness.peak_bytes(system.devices)
    result: Dict[str, Any] = {"attempted": win["attempted"],
                              "failed": win["failed"]}
    device = harness.device_info(system.devices, peak)
    e2e = end_to_end(win)
    e2e["setup_s"] = setup_s
    harness.say(f"end to end {e2e}")
    if trace:
        reduction = trace_reduce.reduce_xplane(
            trace_reduce.find_xplane(trace_dir))
        ctx = LayerContext(
            window=win, num_slots=system.num_slots, model=cfg_file["model"],
            reference=bench.reference(cfg_file["reference"]),
            peaks=peaks_mod.peaks_for(system.devices[0].device_kind),
            bench=bench, reduction=reduction)
        result["metrics"] = bench.per_layer_metrics(cell["name"], ctx)
        device["busy_s"] = reduction.busy_s
        device["window_s"] = reduction.window_s
        result["breakdown"] = reduction.breakdown()
    else:
        units = {m["name"]: m["unit"]
                 for m in bench.metrics_for("end_to_end", cell["name"])}
        result["metrics"] = {k: {"value": float(v), "unit": units[k]}
                             for k, v in e2e.items()
                             if k in units and v is not None}
    result["device"] = device
    # the reference runs with the program's state freed
    del batcher
    gc.collect()
    compared = correct.check_served(bench, cfg_file, system.params,
                                    win["served"], seed)
    result["correct"] = all(c["value"] <= c["limit"]
                            for c in compared.values())
    result["compared"] = compared
    return result
