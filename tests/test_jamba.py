"""Jamba (AI21-Jamba2-3B's registry entry) at its smoke size on the CPU:
prefill and paged decode through the ``ContinuousBatcher``, with the Pallas
kernels interpreted, against the benchmark's plain float32 reference
(``benchmarks/chip/reference/jamba_lm.py``) run over each whole sequence.
The same comparison fails when a part of the layer is left out; the
registry's published sizes; and mamba-2.8b's parameter tree, which the new
options must leave as it was."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_model_config, get_smoke_config
from repro.models import transformer
from repro.models.model import build_model
from repro.serving.paging import PagedPlan
from repro.serving.scheduler import ContinuousBatcher, Request
from repro.tuner.space import launch_families_for
from repro.utils.config import ModelConfig, RunConfig, ShapeConfig

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REFERENCE = os.path.join(REPO, "benchmarks", "chip", "reference")

# float32 throughout at the smoke size: what differs between the program
# (chunked scan, online-softmax flash and paged kernels, recurrent decode)
# and the reference (sequential recurrence, plain softmax, one pass) is the
# order of float32 sums: the widest gap reads 1.5e-5 on logits of order 1,
# and the tolerance leaves ~7x of that.  Each part left out moves some logit
# by 0.58 to 3.2, so MOVED, far below those, still lies 100x above ATOL.
ATOL = 1e-4
MOVED = 1e-2
PLAN = PagedPlan(paging=True, pool_pages=12, page_size=8,
                 pages_per_slot_max=6)


@pytest.fixture(scope="module")
def jamba_ref():
    if REFERENCE not in sys.path:
        sys.path.insert(0, REFERENCE)
    import jamba_lm
    return jamba_lm


@pytest.fixture(scope="module")
def served():
    """The smoke config and seeded weights; every norm weight and conv bias
    drawn away from its initial constant, so a norm whose weight is dropped
    shows."""
    cfg = get_smoke_config("jamba2-3b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    flat, tree = jax.tree_util.tree_flatten_with_path(params)
    keys = jax.random.split(jax.random.PRNGKey(1), len(flat))
    leaves = []
    for k, (path, x) in zip(keys, flat):
        name = str(getattr(path[-1], "key", ""))
        if name == "scale":
            x = x * jax.random.uniform(k, x.shape, x.dtype, 0.5, 1.5)
        elif name == "conv_b":
            x = 0.1 * jax.random.normal(k, x.shape, x.dtype)
        leaves.append(x)
    return cfg, jax.tree_util.tree_unflatten(tree, leaves)


def _served_logits(cfg, params, monkeypatch):
    """Serve three requests through the paged batcher in two slots (the
    third admitted once a slot frees) with the Pallas kernels interpreted;
    returns each request's prompt, tokens and the logits that chose them,
    in order."""
    monkeypatch.setenv("REPRO_KERNEL_MODE", "pallas_interpret")
    model = build_model(cfg)
    run = RunConfig(model=cfg, shape=ShapeConfig("s", PLAN.slot_capacity, 2,
                                                 "decode"))
    b = ContinuousBatcher(model, run, params, num_slots=2, paged=PLAN)
    seen = {}
    prefill, decode = b._prefill, b._decode

    def on_prefill(p, batch):
        state, logits = prefill(p, batch)
        seen[int(batch["tokens"].shape[1])] = [np.asarray(logits[0])]
        return state, logits

    def on_decode(p, state, tokens):
        new, logits = decode(p, state, tokens)
        for rs in b._slots:
            if rs is not None:
                seen[len(rs.request.prompt)].append(
                    np.asarray(logits[rs.slot]))
        return new, logits

    b._prefill, b._decode = on_prefill, on_decode
    rng = np.random.default_rng(3)
    for uid, n in enumerate((13, 21, 30)):
        b.submit(Request(uid=uid, prompt=rng.integers(
            0, cfg.vocab_size, n, dtype=np.int32), max_new_tokens=7))
    done = b.run_until_drained()
    assert len(done) == 3
    return [(rs.request.prompt, rs.generated,
             np.stack(seen[len(rs.request.prompt)])) for rs in done]


def _gap_to_reference(ref, cfg, params, served_logits):
    """The widest difference between a served logit and the reference's at
    the same position of the request's whole sequence."""
    worst = 0.0
    for prompt, generated, logits in served_logits:
        toks = np.concatenate([prompt, np.asarray(generated[:-1], np.int32)])
        with jax.default_matmul_precision("highest"):
            h = ref.hidden(params, cfg.to_dict(), jnp.asarray(toks)[None])
            want = np.asarray(h[0] @ ref.head(params, cfg.to_dict()))
        got = logits[:len(generated)]
        assert got.shape == want[len(prompt) - 1:].shape
        worst = max(worst, float(np.max(np.abs(
            got - want[len(prompt) - 1:]))))
    return worst


def test_served_logits_match_reference(served, jamba_ref, monkeypatch):
    cfg, params = served
    assert _gap_to_reference(jamba_ref, cfg, params,
                             _served_logits(cfg, params, monkeypatch)) < ATOL


def _swap_offset(cfg, params):
    """Attention at the start of each period instead of its offset, with
    the same weights in each layer kind."""
    pat = transformer.block_pattern(cfg)
    blocks = dict(params["blocks"])
    blocks["sub0"], blocks[f"sub{cfg.attn_layer_offset}"] = \
        blocks[f"sub{cfg.attn_layer_offset}"], blocks["sub0"]
    assert pat[0] == "mamba1"
    return cfg.replace(attn_layer_offset=0), dict(params, blocks=blocks)


MUTANTS = {
    "dt_b_c_norms_dropped": lambda c, p: (c.replace(ssm_dt_bc_norm=False), p),
    # d_ff 0 leaves the attention layers' MLP (it reads its weights) and
    # drops the one after each Mamba mixer
    "mamba_mlp_dropped": lambda c, p: (c.replace(d_ff=0), p),
    "offset_dropped": _swap_offset,
    "rope_applied": lambda c, p: (c.replace(rope_theta=10000.0), p),
}


@pytest.mark.parametrize("mutant", sorted(MUTANTS))
def test_each_left_out_part_fails_the_comparison(served, jamba_ref,
                                                 monkeypatch, mutant):
    cfg, params = served
    mcfg, mparams = MUTANTS[mutant](cfg, params)
    got = _served_logits(mcfg, mparams, monkeypatch)
    assert _gap_to_reference(jamba_ref, cfg, params, got) > MOVED


def test_layer_pattern_follows_period_and_offset():
    full = get_model_config("jamba2-3b")
    layers = [transformer.block_pattern(full)[i % 14] for i in range(28)]
    assert [i for i, k in enumerate(layers) if k == "dense"] == [7, 21]
    assert transformer.num_superblocks(full) == 2
    smoke = get_smoke_config("jamba2-3b")
    assert transformer.block_pattern(smoke) == ["mamba1", "mamba1", "dense",
                                                "mamba1"]
    with pytest.raises(ValueError):
        full.replace(attn_layer_offset=14)


def test_registry_has_the_published_sizes(jamba_ref):
    c = get_model_config("jamba2-3b")
    assert (c.num_layers, c.d_model, c.num_heads, c.num_kv_heads, c.head_dim,
            c.d_ff, c.vocab_size, c.ssm_state, c.ssm_conv,
            c.ssm_expand * c.d_model, c.norm_eps) == (
        28, 2560, 20, 1, 128, 8192, 65536, 16, 4, 5120, 1e-6)
    assert c.tie_embeddings and c.ssm_dt_bc_norm and c.rope_theta == 0
    # 3.03 B, as the model card's "3B"; the reference counts the same
    assert c.param_count() == jamba_ref.param_count(c.to_dict()) \
        == 3029337472
    assert launch_families_for(c) == ["rmsnorm", "flash_attention",
                                      "mamba_scan"]


def test_paged_decode_state_holds_both_kinds():
    cfg = get_smoke_config("jamba2-3b")
    st = jax.eval_shape(lambda: build_model(cfg).init_paged_decode_state(
        2, PLAN.pool_pages, PLAN.page_size, PLAN.pages_per_slot_max))
    attn = st[f"sub{cfg.attn_layer_offset}"]
    assert attn.k_pages.shape == (1, PLAN.pool_pages + 1, 1, 8, 16)
    assert attn.page_table.shape == (1, 2, PLAN.pages_per_slot_max)
    assert st["sub0"].ssm.shape == (1, 2, 160, 4)


# mamba-2.8b (benchmarks/chip/configs/mamba-2.8b.json) as the tree stood
# before the jamba options: leaf paths and shapes, stacked over 64 layers
MAMBA_2P8B_TREE = {
    "blocks/sub0/mixer/A_log": (64, 5120, 16),
    "blocks/sub0/mixer/D": (64, 5120),
    "blocks/sub0/mixer/conv_b": (64, 5120),
    "blocks/sub0/mixer/conv_w": (64, 4, 5120),
    "blocks/sub0/mixer/dt_bias": (64, 5120),
    "blocks/sub0/mixer/w_bcdt": (64, 5120, 192),
    "blocks/sub0/mixer/w_dt": (64, 160, 5120),
    "blocks/sub0/mixer/w_out": (64, 5120, 2560),
    "blocks/sub0/mixer/w_x": (64, 2560, 5120),
    "blocks/sub0/mixer/w_z": (64, 2560, 5120),
    "blocks/sub0/norm/scale": (64, 2560),
    "embed/embedding": (50280, 2560),
    "final_norm/scale": (2560,),
}


def test_mamba_2p8b_parameter_tree_is_unchanged():
    import json
    with open(os.path.join(REPO, "benchmarks", "chip", "configs",
                           "mamba-2.8b.json")) as f:
        cfg = ModelConfig(**json.load(f)["model"])
    shapes = jax.eval_shape(build_model(cfg).init, jax.random.PRNGKey(0))
    got = {"/".join(str(k.key) for k in path): tuple(x.shape)
           for path, x in jax.tree_util.tree_flatten_with_path(shapes)[0]}
    assert got == MAMBA_2P8B_TREE
