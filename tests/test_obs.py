"""Observability subsystem: the span tracer and its Chrome trace-event
exports, the metrics registry as single source of truth for the
discovery-variable names, bit-identity of traced vs untraced runs,
MetricsLogger lifecycle, kernel-dispatch profiling, and the report CLI."""

import contextlib
import json
import threading

import jax
import numpy as np
import pytest

from conftest import tiny_model_config
from repro.core.cameo import Cameo
from repro.core.query import parse_query
from repro.envs.measure import KernelWorkload
from repro.envs.replay_env import (REPLAY_COUNTER_NAMES,
                                   REPLAY_FLEET_COUNTER_NAMES,
                                   make_sim2real_pair)
from repro.envs.sandbox import make_sandbox_pair
from repro.envs.serving_env import ServingEnv
from repro.kernels import dispatch
from repro.models.model import build_model
from repro.obs import metrics as obs_metrics
from repro.obs import report as obs_report
from repro.obs import trace as obs_trace
from repro.obs.metrics import MetricsRegistry
from repro.serving.paging import PagedPlan
from repro.serving.replay import replay_trace
from repro.serving.scheduler import ContinuousBatcher, Request
from repro.train.serve_step import jitted_steps
from repro.tuner.runner import transfer_tune
from repro.utils.config import RunConfig, ShapeConfig
from repro.utils.logging import MetricsLogger
from repro.workloads import make_workload
from repro.workloads.sim import FLEET_COUNTER_NAMES, SIM_COUNTER_NAMES

TINY_CELL = KernelWorkload(name="tiny", batch=1, seq_len=128, heads=2,
                           kv_heads=1, head_dim=16, d_model=64, channels=64,
                           scan_state=4, ssm_heads=2, ssm_head_dim=16,
                           ssm_state=8)
FAMS = ("flash_attention", "rmsnorm")
SIM_SPEC = ("poisson:rate=2500,horizon=0.02,mean_prompt=32,mean_output=16,"
            "max_len=96")
REPLAY_SPEC = ("poisson:rate=1500,horizon=0.004,mean_prompt=6,"
               "mean_output=4,max_len=16")
# the batcher's spans: a tick's phases, and an admission's
TICK_CHILDREN = ("serve.decode", "serve.sample", "serve.feedback")
ADMIT_CHILDREN = ("serve.prefill", "serve.scatter", "serve.first_token")
SERVE_SPANS = ("serve.tick", "serve.admit", "serve.queue") + TICK_CHILDREN \
    + ADMIT_CHILDREN


@pytest.fixture(autouse=True)
def _no_leaked_tracer():
    yield
    obs_trace.stop()


@pytest.fixture(scope="module")
def sim2real():
    return make_sim2real_pair(REPLAY_SPEC, seed=0, repeats=1)


# --------------------------------------------------------------------------
# tracer: event vocabulary, export schema, disabled path, bounds
# --------------------------------------------------------------------------

def test_tracer_exports_valid_chrome_trace(tmp_path):
    path = str(tmp_path / "trace.json")
    with obs_trace.trace_to(path) as tr:
        with obs_trace.span("work", cat="test", n=1):
            pass
        obs_trace.instant("marker", cat="test", note="hi")
        obs_trace.counter("depth", 3.0)
        tr.async_begin("request", 7, prompt_len=4)
        tr.async_end("request", 7, generated=2)
        obs_trace.tuner_event("ask", tuner="cameo", round=1, k=2)
    with open(path) as f:
        doc = json.load(f)
    events = obs_report.validate_trace_doc(doc)
    assert doc["displayTimeUnit"] == "ms"
    assert doc["otherData"]["dropped"] == 0
    phases = {e["ph"] for e in events}
    assert {"M", "X", "i", "C", "b", "e"} <= phases
    # track-name metadata covers every logical track
    meta = {e["pid"] for e in events if e["ph"] == "M"}
    assert set(obs_trace.TRACK_NAMES) <= meta
    # the tuner event is both an exported instant and a structured record
    assert [e for e in events if e.get("cat") == "tuner"]
    assert tr.tuner_rounds == [{"kind": "ask", "tuner": "cameo",
                                "round": 1, "k": 2}]


def test_tracing_disabled_is_noop():
    assert not obs_trace.enabled()
    assert obs_trace.active() is None
    assert obs_trace.span("x") is obs_trace.NULL_SPAN
    with obs_trace.span("x", cat="c") as s:
        s.set(a=1)
    # helpers must not raise (and must not allocate a tracer)
    obs_trace.instant("x")
    obs_trace.counter("x", 1.0)
    obs_trace.tuner_event("ask", round=1)
    assert not obs_trace.enabled()


def test_tracer_bounds_events_and_counts_drops():
    tr = obs_trace.Tracer(max_events=3)
    for i in range(10):
        tr.instant(f"e{i}")
    assert len(tr.events()) == 3
    assert tr.dropped == 7
    doc = tr.to_json()
    assert doc["otherData"]["dropped"] == 7
    assert doc["otherData"]["num_events"] == 3


def test_trace_to_exports_on_exception_and_restores(tmp_path):
    path = str(tmp_path / "partial.json")
    outer = obs_trace.start()
    with pytest.raises(RuntimeError):
        with obs_trace.trace_to(path):
            with obs_trace.span("failing", cat="test"):
                raise RuntimeError("boom")
    # the partial trace was exported, with the error recorded on the span
    events = obs_report.load_trace(path)
    fail = [e for e in events if e.get("name") == "failing"]
    assert fail and fail[0]["args"]["error"] == "RuntimeError"
    # and the previously-active tracer was restored
    assert obs_trace.active() is outer


def test_span_records_error_and_duration():
    tr = obs_trace.start()
    try:
        with pytest.raises(ValueError):
            with tr.span("s", cat="test"):
                raise ValueError("x")
        ev = tr.events()[-1]
        assert ev["ph"] == "X" and ev["dur"] >= 0
        assert ev["args"]["error"] == "ValueError"
    finally:
        obs_trace.stop()


def test_validate_trace_doc_rejects_malformed():
    with pytest.raises(ValueError):
        obs_report.validate_trace_doc({"no": "traceEvents"})
    with pytest.raises(ValueError):
        obs_report.validate_trace_doc(
            {"traceEvents": [{"name": "x", "ph": "Z", "ts": 0}]})
    with pytest.raises(ValueError):
        obs_report.validate_trace_doc(
            {"traceEvents": [{"name": "x", "ph": "i"}]})  # missing ts
    with pytest.raises(ValueError):
        obs_report.validate_trace_doc(
            {"traceEvents": [{"name": "x", "ph": "X", "ts": 0}]})  # no dur
    with pytest.raises(ValueError):
        obs_report.validate_trace_doc(
            {"traceEvents": [{"name": "x", "ph": "b", "ts": 0}]})  # no id


# --------------------------------------------------------------------------
# metrics registry
# --------------------------------------------------------------------------

def test_registry_declare_idempotent_and_conflicting():
    reg = MetricsRegistry()
    a = reg.declare("m", kind="gauge", group="g")
    assert reg.declare("m", kind="gauge", group="g") is a
    with pytest.raises(ValueError):
        reg.declare("m", kind="counter", group="g")
    with pytest.raises(ValueError):
        reg.declare("bad", kind="nope")


def test_registry_discovery_names_compose_by_group_order():
    reg = MetricsRegistry()
    reg.declare("a1", group="a")
    reg.declare("b1", group="b")
    reg.declare("a2", group="a")
    reg.declare("a_obj", group="a", discovery=False)
    assert reg.discovery_names("a") == ("a1", "a2")
    assert reg.discovery_names("b") == ("b1",)
    # the caller's group order defines the composite, not global
    # registration order — column order is the discovery-matrix contract
    assert reg.discovery_names("a", "b") == ("a1", "a2", "b1")
    assert reg.discovery_names("b", "a") == ("b1", "a1", "a2")
    assert "a_obj" in reg.names("a")


def test_registry_instruments_and_kind_enforcement():
    reg = MetricsRegistry()
    assert reg.inc("hits") == 1.0
    assert reg.inc("hits", 2.0) == 3.0
    reg.set("depth", 4.0, replica=1)
    reg.observe("lat_ms", 10.0)
    reg.observe("lat_ms", 30.0)
    assert reg.value("hits") == 3.0
    assert reg.value("depth", replica=1) == 4.0
    assert reg.value("depth", replica=2) is None
    with pytest.raises(ValueError):
        reg.set("hits", 1.0)       # declared (auto) as counter
    snap = reg.snapshot()
    assert snap["lat_ms"][""]["count"] == 2.0
    assert snap["lat_ms"][""]["max"] == 30.0
    # auto-declared instruments are runtime bookkeeping, never mediators
    assert reg.spec("hits").group == "runtime"
    assert not reg.spec("hits").discovery
    reg.reset_values()
    assert reg.value("hits") is None
    assert reg.names("runtime")  # declarations survive a value reset


def test_derived_counter_tuples_are_the_historical_contract():
    sim = ("queue_depth_mean", "queue_depth_max", "occupancy_mean",
           "prefill_decode_ratio", "slo_violation_rate",
           "page_pool_occupancy", "page_faults", "prefill_chunks_inflight")
    fleet = ("routing_imbalance", "replica_queue_depth_max",
             "straggler_flagged")
    replay = ("rejected_rate", "rejected_too_long")
    assert SIM_COUNTER_NAMES == sim
    assert FLEET_COUNTER_NAMES == sim + fleet
    assert REPLAY_COUNTER_NAMES == sim + replay
    assert REPLAY_FLEET_COUNTER_NAMES == sim + replay + fleet
    # and they are exactly what the global registry derives
    assert SIM_COUNTER_NAMES == obs_metrics.discovery_names("serving")
    assert REPLAY_FLEET_COUNTER_NAMES == obs_metrics.discovery_names(
        "serving", "replay", "fleet")
    # objective clones are declared but excluded from discovery
    assert "latency" in obs_metrics.REGISTRY.names("serving")
    assert "latency" not in SIM_COUNTER_NAMES


@pytest.mark.parametrize("kind", ["sim", "fleet", "replay"])
def test_envs_emit_registered_discovery_names(kind, request):
    """sim, fleet, and replay measurements emit exactly the names their
    subsystem declared in the registry — the counter dict covers the
    derived discovery tuple, and the env's counter_names IS that tuple."""
    if kind == "sim":
        env = ServingEnv(SIM_SPEC, cell=TINY_CELL, families=FAMS, seed=0)
        expected, groups = SIM_COUNTER_NAMES, ("serving",)
    elif kind == "fleet":
        env = ServingEnv(SIM_SPEC, cell=TINY_CELL, families=FAMS, seed=0,
                         fleet=True)
        expected, groups = FLEET_COUNTER_NAMES, ("serving", "fleet")
    else:
        env = request.getfixturevalue("sim2real")[1]
        expected, groups = REPLAY_COUNTER_NAMES, ("serving", "replay")
    assert tuple(env.counter_names) == expected
    assert expected == obs_metrics.REGISTRY.discovery_names(*groups)
    counters, _ = env.intervene(env.space.default_config())
    assert set(expected) <= set(counters)


# --------------------------------------------------------------------------
# bit-identity: tracing must not perturb anything measured or tuned
# --------------------------------------------------------------------------

def test_sim_counters_bit_identical_under_tracing():
    env = ServingEnv(SIM_SPEC, cell=TINY_CELL, families=FAMS, seed=0)
    cfg = env.space.default_config()
    base = env.simulate(cfg)
    with obs_trace.trace_to(None) as tr:
        traced = env.simulate(cfg)
    assert traced.counters() == base.counters()
    assert (traced.completed, traced.ticks, traced.makespan_us) == \
        (base.completed, base.ticks, base.makespan_us)
    # and the traced run did emit modeled-time lifecycle events
    sim_events = [e for e in tr.events()
                  if e.get("pid") == obs_trace.TRACK_SIM]
    assert sim_events


# the dense batcher, a paged one, and a paged one admitting in chunks
PLANS = {
    "dense": None,
    "paged": PagedPlan(paging=True, pool_pages=16, page_size=4,
                       pages_per_slot_max=8),
    "chunked": PagedPlan(paging=True, pool_pages=16, page_size=4,
                         pages_per_slot_max=8, prefill_chunk=4),
}


def _replay_tokens(served_model, traced: bool, paged=None):
    cfg, run, model, params = served_model
    trace = make_workload(REPLAY_SPEC).generate(0)
    b = ContinuousBatcher(model, run, params, num_slots=2, cache_len=32,
                          paged=paged)
    if traced:
        with obs_trace.trace_to(None):
            rep = replay_trace(b, trace, seed=0)
    else:
        rep = replay_trace(b, trace, seed=0)
    toks = [(rs.request.uid, [int(t) for t in rs.generated])
            for rs in b.completed]
    return rep, sorted(toks)


@pytest.fixture(scope="module")
def served_model():
    cfg = tiny_model_config()
    run = RunConfig(model=cfg, shape=ShapeConfig("s", 64, 4, "decode"))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, run, model, params


@pytest.mark.parametrize("plan", sorted(PLANS))
def test_replay_tokens_and_counters_bit_identical_under_tracing(served_model,
                                                                plan):
    r0, t0 = _replay_tokens(served_model, traced=False, paged=PLANS[plan])
    r1, t1 = _replay_tokens(served_model, traced=True, paged=PLANS[plan])
    assert t0 == t1 and t0
    for f in ("completed", "rejected", "ticks", "tokens", "mean_occupancy",
              "queue_depth_mean", "queue_depth_max"):
        assert getattr(r0, f) == getattr(r1, f), f


def test_cameo_trajectory_bit_identical_under_tracing():
    def run_tune():
        src, tgt = make_sandbox_pair(0)
        d_s = src.dataset(150, seed=1)
        q = parse_query("minimize latency within 12 samples")
        cam = Cameo(src.space, q, d_s, counter_names=src.counter_names,
                    seed=0)
        cam.run(tgt, budget=8)
        return cam

    base = run_tune()
    with obs_trace.trace_to(None) as tr:
        traced = run_tune()
    assert traced.trace.action == base.trace.action
    assert traced.trace.best_y == base.trace.best_y
    assert traced.best == base.best
    # the traced run produced structured per-round ask/tell events
    kinds = [ev["kind"] for ev in tr.tuner_rounds]
    assert "ask" in kinds and "tell" in kinds
    tells = [ev for ev in tr.tuner_rounds if ev["kind"] == "tell"]
    assert tells[-1]["round"] == 8
    assert all("best_y" in ev for ev in tells)


# --------------------------------------------------------------------------
# traced replay smoke: the acceptance-criteria run
# --------------------------------------------------------------------------

def test_traced_sim2real_run_exports_lifecycle_and_tuner(tmp_path, sim2real):
    src, tgt = sim2real
    path = str(tmp_path / "sim2real_trace.json")
    with obs_trace.trace_to(path):
        res = transfer_tune("cameo", src, tgt, budget=2, n_source=16,
                            n_target_init=2, query_text=tgt.query_text,
                            seed=0)
    assert np.isfinite(res.best_y)
    events = obs_report.load_trace(path)  # validates the schema
    names = {e.get("name") for e in events}
    # the real batcher's spans, one per phase of a tick or an admission
    assert set(SERVE_SPANS) <= names
    # the instants they replace are gone
    assert not {"admit", "page_reserve", "retire"} & names
    # the real batcher and the simulator name their lifecycle alike
    for name in ("serve.queue", "serve.prefill"):
        tracks = {e["pid"] for e in events if e.get("name") == name}
        assert {obs_trace.TRACK_SERVE, obs_trace.TRACK_SIM} <= tracks, name
    # async request lifecycles paired by uid
    assert obs_report.request_latencies(events)
    # env deployment spans and per-round tuner events
    assert "deployment" in names and "measure" in names
    tuner = [e for e in events if e.get("cat") == "tuner"]
    assert tuner and {"ask", "tell"} <= {e["name"] for e in tuner}
    # the report CLI summarizes it without error
    rep = obs_report.summarize(events)
    assert rep["lifecycle_us"].get("serve.queue", 0) > 0
    assert rep["lifecycle_us"].get("serve.decode", 0) > 0
    assert rep["tuner_rounds"]
    assert obs_report.main([path, "--slo-ms", "30"]) == 0
    assert obs_report.main([path, "--json"]) == 0


# --------------------------------------------------------------------------
# the batcher's spans: phases of a tick, host syncs, the profiler's clock
# --------------------------------------------------------------------------

def _serve_batcher(served_model, plan, n_requests=3, max_new=4):
    cfg, run, model, params = served_model
    b = ContinuousBatcher(model, run, params, num_slots=2, cache_len=32,
                          paged=PLANS[plan])
    rng = np.random.default_rng(3)
    for i in range(n_requests):
        b.submit(Request(uid=i, prompt=rng.integers(
            0, cfg.vocab_size, 5 + i, dtype=np.int32),
            max_new_tokens=max_new))
    return b


def _inside(child, parent):
    """``child`` lies within ``parent`` on the same track and thread (the
    tracer rounds times to the nanosecond)."""
    eps = 1e-3
    return (child["pid"] == parent["pid"] and child["tid"] == parent["tid"]
            and child["ts"] >= parent["ts"] - eps
            and child["ts"] + child["dur"]
            <= parent["ts"] + parent["dur"] + eps)


@pytest.mark.parametrize("plan", sorted(PLANS))
def test_tick_and_admission_spans_nest_and_count_syncs(served_model, plan):
    with obs_trace.trace_to(None) as tr:
        b = _serve_batcher(served_model, plan)
        b.run_until_drained()
    spans = [e for e in tr.events()
             if e["ph"] == "X" and e["name"].startswith("serve.")]
    ticks = [e for e in spans if e["name"] == "serve.tick"]
    admits = [e for e in spans if e["name"] == "serve.admit"]
    assert len(admits) == 3
    decoded = 0
    for t in ticks:
        inner = sorted((e for e in spans if e is not t and _inside(e, t)),
                       key=lambda e: e["ts"])
        direct = [e["name"] for e in inner
                  if e["name"] in TICK_CHILDREN]
        mine = [e for e in inner if e["name"] == "serve.admit"]
        assert len(mine) == t["args"]["admitted"]
        if t["args"]["live"]:
            decoded += 1
            assert direct == list(TICK_CHILDREN)   # once each, in order
            assert inner[-1]["name"] == "serve.feedback"
        else:
            assert direct == []
        # one wait for the decode step, one read of every slot's token,
        # and each admission's own
        assert t["args"]["syncs"] == (
            (2 if t["args"]["live"] else 0)
            + sum(a["args"]["syncs"] for a in mine))
    assert decoded == b.ticks
    assert sum(t["args"]["admitted"] for t in ticks) == 3
    for a in admits:
        assert a["args"]["syncs"] == 2
        assert a["args"]["slot"] in (0, 1)
        assert (a["args"]["pages"] > 0) == (plan != "dense")
        inner = sorted((e for e in spans if e is not a and _inside(e, a)),
                       key=lambda e: e["ts"])
        assert [e["name"] for e in inner] == list(ADMIT_CHILDREN)
        assert {e["args"]["uid"] for e in inner} == {a["args"]["uid"]}
        assert any(_inside(a, t) for t in ticks)
    queued = [e for e in spans if e["name"] == "serve.queue"]
    assert sorted(e["args"]["uid"] for e in queued) == [0, 1, 2]


@pytest.mark.parametrize("plan", sorted(PLANS))
def test_admissions_and_ticks_count_kv_pages(served_model, plan):
    """Where the batcher pages a KV cache, ``serve.admit``'s ``pages`` are
    the KV pages it reserved and ``serve.tick`` gives the pool pages its
    live slots hold when the tick ends; the dense path gives neither."""
    with obs_trace.trace_to(None) as tr:
        b = _serve_batcher(served_model, plan)
        b.run_until_drained()
    spans = [e for e in tr.events() if e["ph"] == "X"]
    admits = [e["args"] for e in spans if e["name"] == "serve.admit"]
    ticks = [e["args"] for e in spans if e["name"] == "serve.tick"]
    if plan == "dense":
        assert all(a["pages"] == 0 for a in admits)
        assert not any("kv_pages_held" in t for t in ticks)
        return
    assert all(a["pages"] > 0 and "kv_pages" not in a for a in admits)
    held = [t["kv_pages_held"] for t in ticks]
    if plan == "paged":
        # two slots: the first tick seats two requests, which outlive it
        assert ticks[0]["admitted"] == 2
        assert held[0] == admits[0]["pages"] + admits[1]["pages"]
    assert max(held) >= max(a["pages"] for a in admits)
    assert all(0 <= h <= 16 for h in held)
    assert held[-1] == 0                        # every request has left


def test_kv_page_counts_absent_without_a_kv_cache():
    """An attention-free model's pages are bookkeeping: no KV count."""
    cfg = tiny_model_config(family="ssm", attn_type="none", num_heads=0,
                            num_kv_heads=0, d_ff=0, ssm_state=4, ssm_chunk=4)
    run = RunConfig(model=cfg, shape=ShapeConfig("s", 64, 2, "decode"))
    model = build_model(cfg)
    b = ContinuousBatcher(model, run, model.init(jax.random.PRNGKey(0)),
                          num_slots=2, paged=PLANS["paged"])
    b.submit(Request(uid=0, prompt=np.arange(5, dtype=np.int32),
                     max_new_tokens=3))
    with obs_trace.trace_to(None) as tr:
        b.run_until_drained()
    spans = [e for e in tr.events() if e["ph"] == "X"]
    admits = [e["args"] for e in spans if e["name"] == "serve.admit"]
    assert len(admits) == 1 and admits[0]["pages"] > 0
    assert not any("kv_pages_held" in e["args"] for e in spans
                   if e["name"] == "serve.tick")


def _tick_by_tick(served_model, live: int, traced: bool):
    """Drive 4 slots holding ``live`` requests to the end; per tick, every
    request's ``generated`` list, and the traced run's ``serve.tick``s."""
    cfg, run, model, params = served_model
    b = ContinuousBatcher(model, run, params, num_slots=4, cache_len=32)
    rng = np.random.default_rng(5)
    per_tick = []
    tracing = obs_trace.trace_to(None) if traced else contextlib.nullcontext()
    with tracing as tr:
        for i in range(live):
            b.submit(Request(uid=i, prompt=rng.integers(
                0, cfg.vocab_size, 4 + i, dtype=np.int32), max_new_tokens=6))
        while b.tick():
            states = [s for s in b._slots if s is not None] + b.completed
            per_tick.append({rs.request.uid: list(rs.generated)
                             for rs in states})
    ticks = [e for e in tr.events() if e["name"] == "serve.tick"] \
        if traced else []
    return per_tick, ticks


@pytest.mark.parametrize("live", [1, 2, 4])
def test_decode_tick_reads_tokens_once_whatever_the_live_count(served_model,
                                                               live):
    per_tick, ticks = _tick_by_tick(served_model, live, traced=True)
    assert len(ticks) == len(per_tick) + 1     # the last tick finds none
    for t in ticks[:-1]:
        # the decode wait and one read of every slot's token; the first
        # tick adds its admissions' two each
        admitted = t["args"]["admitted"]
        assert t["args"]["syncs"] == 2 + 2 * admitted, t["args"]
    assert ticks[0]["args"]["admitted"] == live
    assert all(t["args"]["live"] == live for t in ticks[:-1])
    assert per_tick == _tick_by_tick(served_model, live, traced=False)[0]


def test_serve_spans_reach_the_profiler_host_plane(served_model, tmp_path):
    from jax.profiler import ProfileData

    b = _serve_batcher(served_model, "paged", n_requests=2, max_new=3)
    b.run_until_drained()                 # compiled before the session
    b = _serve_batcher(served_model, "paged", n_requests=2, max_new=3)
    with jax.profiler.trace(str(tmp_path)), obs_trace.trace_to(None):
        b.run_until_drained()
    files = list(tmp_path.rglob("*.xplane.pb"))
    assert len(files) == 1
    host = {e.name for plane in ProfileData.from_file(str(files[0])).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events}
    assert set(SERVE_SPANS) - {"serve.queue"} <= host
    # modeled-time spans (``complete``) have no wall interval to mirror
    assert "serve.queue" not in host


def test_no_profiler_annotation_without_a_tracer(served_model, monkeypatch):
    def refuse(*a, **kw):
        raise AssertionError("TraceAnnotation made with no tracer active")

    b = _serve_batcher(served_model, "paged")
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", refuse)
    assert not obs_trace.enabled()
    b.run_until_drained()
    assert len(b.completed) == 3
    # the patch is live: an active tracer's span does make one
    with obs_trace.trace_to(None):
        with pytest.raises(AssertionError, match="no tracer"):
            with obs_trace.span("serve.tick"):
                pass


def test_report_cli_rejects_invalid_file(tmp_path):
    bad = tmp_path / "bad.json"
    bad.write_text("{not json")
    assert obs_report.main([str(bad)]) == 2
    worse = tmp_path / "worse.json"
    worse.write_text(json.dumps({"traceEvents": [{"ph": "?", "ts": 0}]}))
    assert obs_report.main([str(worse)]) == 2


# --------------------------------------------------------------------------
# MetricsLogger: context manager, idempotent close, registry routing
# --------------------------------------------------------------------------

def test_metrics_logger_context_manager_and_registry(tmp_path):
    p = tmp_path / "m.jsonl"
    with MetricsLogger(path=str(p), name="obs-test") as log:
        log.log(1, loss=0.5, event="init")
        fh = log._fh
        assert fh is not None and not fh.closed
    assert log._fh is None and fh.closed
    log.close()                     # idempotent
    log.log(2, loss=0.25)           # after close: stderr only, no raise
    recs = [json.loads(line) for line in p.read_text().splitlines()]
    assert len(recs) == 1 and recs[0]["loss"] == 0.5
    # numeric metrics are mirrored into the registry as labeled gauges
    assert obs_metrics.REGISTRY.value("loss", logger="obs-test") == 0.25


def test_metrics_logger_closes_on_exception(tmp_path):
    p = tmp_path / "m.jsonl"
    with pytest.raises(RuntimeError):
        with MetricsLogger(path=str(p), name="t") as log:
            log.log(0, a=1.0)
            raise RuntimeError("boom")
    assert log._fh is None


def test_metrics_logger_no_path_is_safe():
    with MetricsLogger(name="nofile") as log:
        log.log(0, x=1.0)
    log.close()


# --------------------------------------------------------------------------
# dispatch: spy isolation regressions + profiling hooks
# --------------------------------------------------------------------------

def test_record_resolutions_nested_spies_are_isolated():
    with dispatch.record_resolutions() as outer:
        dispatch.resolve("rmsnorm")
        with dispatch.record_resolutions() as inner:
            dispatch.resolve("ssd")
        dispatch.resolve("mamba_scan")
    assert [r.family for r in outer] == ["rmsnorm", "ssd", "mamba_scan"]
    assert [r.family for r in inner] == ["ssd"]


def test_record_resolutions_out_of_order_exit_keeps_inner_spy():
    # an ExitStack can close the older spy first; the younger one must
    # keep recording and detach itself cleanly afterwards
    a = dispatch.record_resolutions()
    b = dispatch.record_resolutions()
    ra = a.__enter__()
    rb = b.__enter__()
    a.__exit__(None, None, None)
    dispatch.resolve("rmsnorm")
    b.__exit__(None, None, None)
    dispatch.resolve("ssd")     # nothing should record this
    assert ra == []
    assert [r.family for r in rb] == ["rmsnorm"]


def test_record_resolutions_concurrent_threads_are_isolated():
    seen = {}
    go = threading.Barrier(2)

    def spy(name, family, n):
        with dispatch.record_resolutions() as rec:
            go.wait()
            for _ in range(n):
                dispatch.resolve(family)
        seen[name] = [r.family for r in rec]

    t1 = threading.Thread(target=spy, args=("a", "rmsnorm", 3))
    t2 = threading.Thread(target=spy, args=("b", "ssd", 2))
    t1.start(); t2.start(); t1.join(); t2.join()
    assert seen["a"] == ["rmsnorm"] * 3
    assert seen["b"] == ["ssd"] * 2


def test_profile_dispatches_counts_and_times():
    x = np.ones((2, 8), np.float32)
    w = np.ones((8,), np.float32)
    mode = dispatch.default_mode()
    with dispatch.profile_dispatches() as prof:
        dispatch.resolve("ssd")
        t = threading.Thread(target=lambda: dispatch.resolve("ssd"))
        t.start(); t.join()
        dispatch.dispatch("rmsnorm", x, w)
    # cross-thread resolutions all observed
    assert prof.resolutions[("ssd", mode)] == 2
    assert prof.resolutions[("rmsnorm", mode)] == 1
    assert prof.wall_s[("rmsnorm", mode)] > 0
    summ = prof.summary()
    assert summ[f"ssd [{mode}]"]["resolutions"] == 2
    # nothing recorded once the profile exits
    dispatch.resolve("ssd")
    assert prof.resolutions[("ssd", mode)] == 2


def test_dispatch_traced_emits_kernel_track_span():
    x = np.ones((2, 8), np.float32)
    w = np.ones((8,), np.float32)
    mode = dispatch.default_mode()
    before = obs_metrics.REGISTRY.value("dispatch_resolutions_total",
                                        family="rmsnorm", mode=mode) or 0.0
    with obs_trace.trace_to(None) as tr:
        dispatch.dispatch("rmsnorm", x, w)
    spans = [e for e in tr.events()
             if e.get("pid") == obs_trace.TRACK_KERNEL and e["ph"] == "X"]
    assert spans and spans[0]["name"] == "rmsnorm"
    assert spans[0]["args"]["mode"] == mode
    after = obs_metrics.REGISTRY.value("dispatch_resolutions_total",
                                       family="rmsnorm", mode=mode)
    assert after == before + 1


def test_jit_cache_hit_miss_instants(served_model):
    cfg, run, model, params = served_model
    with obs_trace.trace_to(None) as tr:
        s1 = jitted_steps(model, run, cache_len=24)
        s2 = jitted_steps(model, run, cache_len=24)
    assert s1 is s2
    names = [e["name"] for e in tr.events() if e.get("cat") == "jit_cache"]
    assert "jit_cache_miss" in names and "jit_cache_hit" in names
    assert (obs_metrics.REGISTRY.value("jit_cache_hits") or 0) >= 1
    assert (obs_metrics.REGISTRY.value("jit_cache_misses") or 0) >= 1
