"""Paged KV cache + chunked prefill: the dense-equivalence anchor (a single
full-size page reproduces the dense path bit-for-bit, at the kernel and
through the whole batcher), page-pool allocation/churn, chunked-prefill
scheduling, PromptTooLong rejection, and the paged replay counters."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import tiny_model_config
from repro.kernels.flash_attention.ref import decode_attention_ref
from repro.kernels.paged_attention.kernel import paged_decode_attention_pallas
from repro.kernels.paged_attention.ref import (gather_pages,
                                               paged_decode_attention_ref)
from repro.models.model import build_model
from repro.serving.paging import PagedPlan
from repro.serving.replay import replay_trace
from repro.serving.scheduler import ContinuousBatcher, PromptTooLong, Request
from repro.utils.config import RunConfig, ShapeConfig
from repro.workloads import ServingPlan, make_workload
from repro.workloads.sim import SIM_COUNTER_NAMES

pytestmark = pytest.mark.paged

RNG = np.random.default_rng(0)


def rand(*shape):
    return jnp.asarray(RNG.normal(size=shape).astype(np.float32))


def _paged_layout(k_cache, v_cache, page_size, perm=None):
    """Scatter a dense (B, Hkv, L, D) cache into a (P, Hkv, page_size, D)
    paged pool.  ``perm`` shuffles which pool page holds which logical page
    (identity when None), so tests cover non-contiguous page tables."""
    b, hkv, l, d = k_cache.shape
    assert l % page_size == 0
    n_pages = l // page_size
    order = np.arange(b * n_pages) if perm is None else np.asarray(perm)
    k_pages = np.zeros((b * n_pages, hkv, page_size, d), np.float32)
    v_pages = np.zeros_like(k_pages)
    table = np.zeros((b, n_pages), np.int32)
    for bi in range(b):
        for p in range(n_pages):
            pid = int(order[bi * n_pages + p])
            k_pages[pid] = k_cache[bi, :, p * page_size:(p + 1) * page_size]
            v_pages[pid] = v_cache[bi, :, p * page_size:(p + 1) * page_size]
            table[bi, p] = pid
    return jnp.asarray(k_pages), jnp.asarray(v_pages), jnp.asarray(table)


# --------------------------------------------------------------------------
# kernel level: the dense-equivalence anchor
# --------------------------------------------------------------------------

def test_single_full_page_is_bit_identical_to_dense():
    # one page of exactly cache_len tokens with an identity table: the
    # gathered layout IS the dense cache, so the oracle must match the dense
    # decode reference bit-for-bit — not approximately
    b, l, hq, hkv, d = 3, 16, 4, 2, 8
    q = rand(b, 1, hq, d)
    k_cache, v_cache = rand(b, hkv, l, d), rand(b, hkv, l, d)
    lens = jnp.asarray([5, 16, 1], jnp.int32)
    k_pages, v_pages, table = _paged_layout(np.asarray(k_cache),
                                            np.asarray(v_cache), page_size=l)
    out = paged_decode_attention_ref(q, k_pages, v_pages, table, lens)
    ref = decode_attention_ref(q, k_cache, v_cache, lens)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_permuted_multi_page_pool_is_bit_identical_to_dense():
    b, l, ps, hq, hkv, d = 2, 32, 8, 4, 2, 8
    q = rand(b, 1, hq, d)
    k_cache, v_cache = rand(b, hkv, l, d), rand(b, hkv, l, d)
    lens = jnp.asarray([19, 32], jnp.int32)
    perm = np.random.default_rng(3).permutation(b * (l // ps))
    k_pages, v_pages, table = _paged_layout(
        np.asarray(k_cache), np.asarray(v_cache), ps, perm)
    # the gather reconstructs the dense rows exactly...
    np.testing.assert_array_equal(
        np.asarray(gather_pages(k_pages, table)), np.asarray(k_cache))
    # ...so the attention output is bit-identical too
    out = paged_decode_attention_ref(q, k_pages, v_pages, table, lens)
    ref = decode_attention_ref(q, k_cache, v_cache, lens)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_pallas_interpret_matches_ref():
    b, l, ps, hq, hkv, d = 2, 32, 8, 4, 2, 16
    q = rand(b, 1, hq, d)
    k_cache, v_cache = rand(b, hkv, l, d), rand(b, hkv, l, d)
    lens = jnp.asarray([13, 27], jnp.int32)
    perm = np.random.default_rng(5).permutation(b * (l // ps))
    k_pages, v_pages, table = _paged_layout(
        np.asarray(k_cache), np.asarray(v_cache), ps, perm)
    ref = paged_decode_attention_ref(q, k_pages, v_pages, table, lens)
    out = paged_decode_attention_pallas(q, k_pages, v_pages, table, lens,
                                        interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=1e-4)
    # softcap path too
    ref_c = paged_decode_attention_ref(q, k_pages, v_pages, table, lens,
                                       logit_softcap=5.0)
    out_c = paged_decode_attention_pallas(q, k_pages, v_pages, table, lens,
                                          logit_softcap=5.0, interpret=True)
    np.testing.assert_allclose(np.asarray(out_c), np.asarray(ref_c),
                               atol=2e-5, rtol=1e-4)


# --------------------------------------------------------------------------
# batcher level: paged serving reproduces the dense batcher bit-for-bit
# --------------------------------------------------------------------------

@pytest.fixture(scope="module")
def served():
    cfg = tiny_model_config()
    run = RunConfig(model=cfg, shape=ShapeConfig("s", 64, 4, "decode"))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, run, model, params


def _prompts(cfg, n, length=5, seed=2):
    keys = jax.random.split(jax.random.PRNGKey(seed), n)
    return [np.asarray(jax.random.randint(k, (length,), 0, cfg.vocab_size))
            for k in keys]


def _generated(served, *, paged=None, n_requests=3, max_new=4,
               num_slots=2, cache_len=32, eos_token=None):
    cfg, run, model, params = served
    b = ContinuousBatcher(model, run, params, num_slots=num_slots,
                          cache_len=cache_len, paged=paged,
                          eos_token=eos_token)
    for i, p in enumerate(_prompts(cfg, n_requests)):
        b.submit(Request(uid=i, prompt=p, max_new_tokens=max_new))
    done = b.run_until_drained()
    return [(d.request.uid, list(d.generated)) for d in done], b


def test_paged_single_full_page_matches_dense_batcher(served):
    dense, _ = _generated(served)
    paged, b = _generated(served, paged=PagedPlan(
        paging=True, pool_pages=2, page_size=32, pages_per_slot_max=1))
    # bit-identical tokens AND identical completion order
    assert paged == dense
    assert sorted(b._free_pages) == [0, 1]  # every page back in the pool


def test_paged_multi_page_matches_dense_batcher(served):
    dense, _ = _generated(served)
    paged, _ = _generated(served, paged=PagedPlan(
        paging=True, pool_pages=8, page_size=4, pages_per_slot_max=8))
    assert paged == dense


def test_chunked_prefill_matches_unchunked(served):
    dense, _ = _generated(served)
    chunked, b = _generated(served, paged=PagedPlan(
        paging=True, pool_pages=8, page_size=4, pages_per_slot_max=8,
        prefill_chunk=2))
    # chunking is a scheduling decision: the jitted prefill still runs once
    # over the full prompt, so tokens AND completion order are unchanged
    assert chunked == dense
    assert b.prefill_chunks >= 3 * 3  # ceil(5/2) chunks per request
    assert b._prefilling is None


def test_pool_exhaustion_defers_admission_not_correctness(served):
    # worst case per request = 5 + 3 tokens = 2 pages of 4; a 2-page pool
    # serializes the requests even though 2 slots are free
    dense, _ = _generated(served)
    paged, b = _generated(served, paged=PagedPlan(
        paging=True, pool_pages=2, page_size=4, pages_per_slot_max=8))
    assert paged == dense
    assert b.mean_occupancy <= 1.0  # never two resident at once


def test_slot_churn_with_eos_matches_dense(served):
    cfg, run, model, params = served
    # greedy first token of the first prompt becomes "EOS": slots churn and
    # freed pages are re-issued to later requests mid-run
    from repro.train.serve_step import generate
    p0 = _prompts(cfg, 1)[0]
    ref = np.asarray(generate(model, run, params,
                              {"tokens": jnp.asarray(p0)[None]},
                              num_steps=1))[0]
    eos = int(ref[0])
    dense, _ = _generated(served, n_requests=4, max_new=6, eos_token=eos)
    paged, _ = _generated(served, n_requests=4, max_new=6, eos_token=eos,
                          paged=PagedPlan(paging=True, pool_pages=4,
                                          page_size=4, pages_per_slot_max=8))
    assert paged == dense


def test_paged_requires_model_support(served):
    cfg, run, model, params = served
    stripped = model._replace(init_paged_decode_state=None)
    with pytest.raises(NotImplementedError, match="paged decode"):
        ContinuousBatcher(stripped, run, params, paged=PagedPlan(paging=True))
    # paging=off never touches the paged path
    b = ContinuousBatcher(stripped, run, params, cache_len=32,
                          paged=PagedPlan(paging=False))
    assert b.paged is None and b.cache_len == 32


# --------------------------------------------------------------------------
# admission limits: PromptTooLong
# --------------------------------------------------------------------------

def test_prompt_too_long_raises_with_geometry(served):
    cfg, run, model, params = served
    b = ContinuousBatcher(model, run, params, num_slots=1, cache_len=16)
    with pytest.raises(PromptTooLong, match="dense cache") as e:
        b.submit(Request(uid=7, prompt=np.arange(14), max_new_tokens=8))
    assert e.value.uid == 7 and e.value.needed == 21 and e.value.limit == 16
    # paged limit is min(slot capacity, whole pool)
    b = ContinuousBatcher(model, run, params, num_slots=1,
                          paged=PagedPlan(paging=True, pool_pages=2,
                                          page_size=4, pages_per_slot_max=8))
    with pytest.raises(PromptTooLong, match="paged slot") as e:
        b.submit(Request(uid=8, prompt=np.arange(6), max_new_tokens=4))
    assert e.value.limit == 8  # 2 pool pages x 4, not 8 x 4


def test_prompt_too_long_reject_counts_instead(served):
    cfg, run, model, params = served
    b = ContinuousBatcher(model, run, params, num_slots=1, cache_len=16,
                          on_too_long="reject")
    b.submit(Request(uid=0, prompt=np.arange(14), max_new_tokens=8))
    b.submit(Request(uid=1, prompt=np.asarray([1, 2]), max_new_tokens=2))
    assert b.rejected_too_long == 1
    assert [r.uid for r in b.queue] == [1]
    done = b.run_until_drained()
    assert [d.request.uid for d in done] == [1]
    with pytest.raises(ValueError, match="on_too_long"):
        ContinuousBatcher(model, run, params, on_too_long="bogus")


# --------------------------------------------------------------------------
# replay counters
# --------------------------------------------------------------------------

def test_replay_reports_paged_counters(served):
    cfg, run, model, params = served
    tr = make_workload("poisson:rate=1500,horizon=0.004,mean_prompt=5,"
                       "mean_output=3,max_len=12").generate(0)
    b = ContinuousBatcher(model, run, params, num_slots=2,
                          paged=PagedPlan(paging=True, pool_pages=8,
                                          page_size=4, pages_per_slot_max=4,
                                          prefill_chunk=2),
                          on_too_long="reject")
    rep = replay_trace(b, tr, seed=0)
    assert rep.completed == len(tr)
    c = rep.counters()
    assert {"page_pool_occupancy", "page_faults", "prefill_chunks_inflight",
            "rejected_too_long"} <= set(c)
    assert 0.0 < c["page_pool_occupancy"] <= 1.0
    assert c["page_faults"] == 0.0  # the real batcher defers, never faults
    assert c["prefill_chunks_inflight"] > 0.0
    assert c["rejected_too_long"] == 0.0
    # a dense replay emits the same counter names, pinned to zero
    bd = ContinuousBatcher(model, run, params, num_slots=2, cache_len=32)
    cd = replay_trace(bd, tr, seed=0).counters()
    assert cd["page_pool_occupancy"] == cd["prefill_chunks_inflight"] == 0.0


# --------------------------------------------------------------------------
# simulator: paging off is the pre-refactor sim; paging on moves the price
# --------------------------------------------------------------------------

def test_sim_paging_off_matches_legacy_and_on_differs():
    from repro.envs.measure import KernelWorkload
    from repro.workloads import ServingSimulator

    cell = KernelWorkload(name="tiny", batch=1, seq_len=128, heads=2,
                          kv_heads=1, head_dim=16, d_model=64, channels=64,
                          scan_state=4, ssm_heads=2, ssm_head_dim=16,
                          ssm_state=8)
    tr = make_workload("poisson:rate=2000,horizon=0.02,mean_prompt=32,"
                       "mean_output=16,max_len=96").generate(0)
    sim = ServingSimulator(cell, ("flash_attention", "rmsnorm"))
    plan = ServingPlan()
    legacy = sim.run(tr, plan, {})
    off = sim.run(tr, plan, {"pages.paging": "off"})
    assert off == legacy  # the refactor left the dense sim bit-identical
    on = sim.run(tr, plan, {"pages.paging": "on"})
    assert on.feasible
    assert on.p99_latency_us != legacy.p99_latency_us
    assert on.page_pool_occupancy > 0.0
    assert legacy.page_pool_occupancy == 0.0
    assert set(on.counters()) == set(legacy.counters())
    assert set(SIM_COUNTER_NAMES) <= set(on.counters())
