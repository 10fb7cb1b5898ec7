"""The benchmark's jamba files on the CPU: a tiny Jamba configuration added
to a copy of the benchmark runs end to end in interpret mode and reads
``correct``; a decode state that drops what a step wrote reads not
correct; the paged-attention counts and their reader, checked by hand; the
new mixes offer every seed the same work, and a slot holds any request."""

from __future__ import annotations

import json
import os
import shutil
import time

import jax
import pytest

from chipfixtures import CHIP, REPO, TINY_MIX, interpret  # noqa: F401
from test_faults import state_unchanged
import harness
import serve_driver

TINY_JAMBA = dict(
    name="tiny-jamba", family="hybrid", num_layers=8, d_model=64,
    num_heads=4, num_kv_heads=1, head_dim=16, d_ff=128, vocab_size=128,
    norm_eps=1e-6, rope_theta=0.0, tie_embeddings=True, attn_type="gqa",
    ssm_state=4, ssm_conv=4, ssm_expand=2, ssm_chunk=16,
    ssm_dt_bc_norm=True, attn_layer_period=4, attn_layer_offset=2,
    dtype="bfloat16")
# widest logit gap at this size and load, seeds 777-780 and 2**31 + 77
# (pallas_interpret on the CPU): program 0.0006-0.0153, float8 control
# 0.111-0.183; the limit sits between the two
LIMIT = 0.04


@pytest.fixture
def jamba_bench(tmp_path):
    """A copy of the benchmark with a tiny Jamba cell on a short mix."""
    from repro.utils.config import ModelConfig

    root = tmp_path / "chip"
    shutil.copytree(CHIP, root, ignore=shutil.ignore_patterns(
        "tests", "__pycache__"))
    cfg = {"name": "tiny-jamba", "source": "test", "reduced": [],
           "model": ModelConfig(**TINY_JAMBA).to_dict(),
           "serving": {"num_slots": 2, "page_size": 16,
                       "pages_per_slot_max": 5, "pool_pages": 10},
           "reference": "jamba_lm",
           "correct": {"sample_requests": 8, "reference_len": 80,
                       "reference_batch": 2, "max_logit_gap": LIMIT}}
    (root / "configs" / "tiny-jamba.json").write_text(json.dumps(cfg))
    (root / "traffic" / "tiny-chat.json").write_text(json.dumps(TINY_MIX))
    spec = harness.load_json(os.path.join(REPO, "BENCHMARK.json"))
    spec["configs"].append({"name": "tiny-jamba", "source": "test",
                            "file": "configs/tiny-jamba.json",
                            "reduced": [], "why": "CPU test"})
    spec["workloads"].append({"name": "tiny-jamba-chat",
                              "config": "tiny-jamba", "traffic": "tiny-chat",
                              "chips": 1, "why": "CPU test"})
    return harness.Bench(spec, str(root))


def _run(bench, kernel_mode, **kw):
    return serve_driver.run_cell(
        bench, bench.cell("tiny-jamba-chat"), seed=2 ** 31 + 77,
        seconds=3.0, t_start=time.perf_counter(), kernel_mode=kernel_mode,
        **kw)


def test_tiny_jamba_runs_end_to_end_and_is_correct(jamba_bench, interpret):
    res = _run(jamba_bench, interpret, trace=False)
    assert res["correct"] is True, res["compared"]
    assert res["compared"]["max_logit_gap"]["value"] <= LIMIT
    assert res["failed"] == 0
    assert res["metrics"]["output_tok_s"]["value"] > 0


def kv_unchanged(batcher):
    """The decode step keeps the KV pages it was given: the attention
    layers never see the tokens decoded after the prompt."""
    from repro.models.attention import PagedKVCache
    decode = batcher._decode
    paged = lambda x: isinstance(x, PagedKVCache)

    def step(params, state, tokens):
        new, logits = decode(params, state, tokens)
        caches = jax.tree.map(
            lambda o, n: n._replace(k_pages=o.k_pages, v_pages=o.v_pages)
            if paged(n) else n, state.caches, new.caches, is_leaf=paged)
        return new._replace(caches=caches), logits

    batcher._decode = step


@pytest.mark.parametrize("fault", [state_unchanged, kv_unchanged])
def test_broken_decode_state_is_not_correct(jamba_bench, interpret, fault):
    res = _run(jamba_bench, interpret, trace=False, fault=fault)
    assert res["correct"] is False, res["compared"]


def test_paged_attention_counts_by_hand():
    k = harness.Bench({"workloads": []}).kernel("paged_attention")
    # 1000 cached tokens, 20 query heads over 1 KV head of 128, bf16:
    # q k^T and p v, 2 x 20 x 128 x 1000 each
    flops, nbytes = k.cost(1000, 20, 1, 128)
    assert flops == 2 * (2 * 20 * 128 * 1000)
    # K and V: 1000 x 128 x 2 bytes each; q and out: 20 x 128 x 2 each
    assert nbytes == 2 * 1000 * 128 * 2 + 2 * 20 * 128 * 2
    # the live context alone: twice the tokens, twice the work
    assert k.cost(2000, 20, 1, 128)[0] == 2 * flops


def test_roofline_reader_counts_every_attention_layer():
    import peaks
    bench = harness.Bench({"workloads": []})
    m = harness.load_json(os.path.join(CHIP, "configs",
                                       "jamba2-3b.json"))["model"]
    ticks = [serve_driver.TickRecord([], [3000, 5000]),
             serve_driver.TickRecord([4096], [])]
    red = type("R", (), {"kernel_ns": {"paged_decode_attention": 2e6}})()
    ctx = serve_driver.LayerContext(
        window={"traced_ticks": ticks}, num_slots=32, model=m,
        reference=None, peaks=peaks.PEAKS["TPU v5 lite"], bench=bench,
        reduction=red)
    got = bench.metric_reader("paged_attention_roofline").read(ctx)
    # 2 attention layers x 8000 tokens of K and V (128 x 2 bytes each),
    # plus q and out of 20 heads for each of the 2 slots, over 819 GB/s
    nbytes = 2 * (2 * 8000 * 256 + 2 * 2 * 20 * 256)
    assert got == pytest.approx(100 * nbytes / 819e9 / 2e-3)
    red.kernel_ns = {}
    assert bench.metric_reader("paged_attention_roofline").read(ctx) is None


@pytest.mark.parametrize("name,slot_tokens", [
    ("longdoc-jamba2-3b", 104 * 128), ("decode-mamba-2.8b", 20 * 128)])
def test_new_mixes_offer_every_seed_the_same_work(name, slot_tokens):
    bench = harness.Bench({"workloads": []})
    mix = bench.traffic(name)
    assert mix["kind"] == "open_lognormal"
    kind = bench.traffic_kind(mix["kind"])
    lens = lambda reqs: sorted((len(r.prompt), r.max_new_tokens)
                               for r in reqs)
    a, b = (kind.generate(mix, s, 51.0, 65536) for s in (2 ** 31 + 1, 7))
    assert len(a) == round(mix["rate_per_s"] * 51.0)
    # the same prompt and output multisets; another order, other tokens
    assert sorted(len(r.prompt) for r in a) == sorted(
        len(r.prompt) for r in b)
    assert sorted(r.max_new_tokens for r in a) == sorted(
        r.max_new_tokens for r in b)
    assert [r.prompt.tobytes() for r in a] != [r.prompt.tobytes() for r in b]
    assert mix["prompt_min"] <= lens(a)[0][0]
    assert all(p % mix["prompt_round"] == 0 for p, _ in lens(a))
    # the longest prompt with the longest output fits a slot's pages
    assert mix["prompt_max"] + mix["output_max"] <= slot_tokens
    assert all(p + o <= slot_tokens for p, o in lens(a))
