"""Hypothesis property tests on system invariants."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis", reason="dev-only dep: pip install -r requirements-dev.txt")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core.spaces import ConfigSpace, Option
from repro.core.epsilon import hull_volume_fraction
from repro.data.pipeline import DataConfig, SyntheticLMData
from repro.kernels.flash_attention import ref as aref
from repro.kernels.mamba_scan import ref as sref
from repro.kernels.ssd import ref as ssdref

SETTINGS = dict(max_examples=20, deadline=None)


# -- config space -------------------------------------------------------------

@st.composite
def spaces(draw):
    n = draw(st.integers(2, 6))
    opts = []
    for i in range(n):
        kind = draw(st.sampled_from(["numeric", "categorical"]))
        if kind == "numeric":
            vals = tuple(sorted(draw(st.sets(
                st.integers(0, 100), min_size=2, max_size=5))))
        else:
            vals = tuple(f"v{j}" for j in range(draw(st.integers(2, 4))))
        opts.append(Option(f"o{i}", vals, kind=kind))
    return ConfigSpace(opts)


@given(spaces(), st.integers(0, 2 ** 31 - 1))
@settings(**SETTINGS)
def test_encode_decode_roundtrip(space, seed):
    rng = np.random.default_rng(seed)
    cfg = space.sample(rng, 1)[0]
    assert space.decode(space.encode(cfg)) == cfg


@given(spaces())
@settings(**SETTINGS)
def test_encoding_in_unit_cube(space):
    rng = np.random.default_rng(0)
    for cfg in space.sample(rng, 8):
        x = space.encode(cfg)
        assert (x >= 0).all() and (x <= 1).all()


@given(spaces(), st.integers(0, 100))
@settings(**SETTINGS)
def test_neighbors_are_valid_configs(space, seed):
    rng = np.random.default_rng(seed)
    cfg = space.sample(rng, 1)[0]
    for nb in space.neighbors(cfg, rng, 6):
        for o in space.options:
            assert nb[o.name] in o.values


# -- hull volume ----------------------------------------------------------------

@given(st.integers(2, 30), st.integers(1, 5), st.integers(0, 1000))
@settings(**SETTINGS)
def test_hull_volume_bounds_and_monotonicity(n, d, seed):
    rng = np.random.default_rng(seed)
    pts = rng.uniform(0, 1, (n, d))
    v = hull_volume_fraction(pts)
    assert 0.0 <= v <= 1.0
    v2 = hull_volume_fraction(np.vstack([pts, rng.uniform(0, 1, (3, d))]))
    assert v2 >= v - 1e-12


# -- data pipeline ------------------------------------------------------------------

@given(st.integers(0, 10_000), st.integers(1, 4))
@settings(**SETTINGS)
def test_data_deterministic_and_sharded(step, shards):
    base = dict(vocab_size=64, seq_len=16, global_batch=8)
    full = SyntheticLMData(DataConfig(**base, seed=5))
    ref = full.batch_at(step)["inputs"]
    # same step twice -> identical
    np.testing.assert_array_equal(ref, full.batch_at(step)["inputs"])
    if 8 % shards == 0:
        parts = [SyntheticLMData(DataConfig(**base, seed=5,
                                            num_shards=shards, shard_id=i)
                                 ).batch_at(step)["inputs"]
                 for i in range(shards)]
        for p in parts:
            assert p.shape == (8 // shards, 16)


@given(st.integers(0, 500))
@settings(**SETTINGS)
def test_data_tokens_in_vocab(step):
    d = SyntheticLMData(DataConfig(vocab_size=32, seq_len=8, global_batch=4))
    b = d.batch_at(step)
    assert b["inputs"].min() >= 0 and b["inputs"].max() < 32
    # targets are inputs shifted by one
    np.testing.assert_array_equal(b["inputs"][:, 1:], b["targets"][:, :-1])


# -- kernel semantics ------------------------------------------------------------

@given(st.integers(1, 2), st.integers(4, 24), st.integers(1, 2),
       st.integers(0, 100))
@settings(**SETTINGS)
def test_blockwise_attention_equals_plain(b, s, hkv, seed):
    rng = np.random.default_rng(seed)
    g = 2
    d = 8
    q = jnp.asarray(rng.normal(size=(b, s, hkv * g, d)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(b, s, hkv, d)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(b, s, hkv, d)).astype(np.float32))
    ref = aref.attention_ref(q, k, v, causal=True)
    out = aref.attention_blockwise_ref(q, k, v, causal=True, kv_block=7)
    np.testing.assert_allclose(out, ref, atol=5e-5, rtol=1e-3)


@given(st.integers(2, 64), st.integers(1, 64), st.integers(0, 50))
@settings(**SETTINGS)
def test_selective_scan_chunk_invariance(l, chunk, seed):
    rng = np.random.default_rng(seed)
    b, c, n = 1, 4, 3
    x = jnp.asarray(rng.normal(size=(b, l, c)).astype(np.float32))
    dt = jnp.abs(jnp.asarray(rng.normal(size=(b, l, c)).astype(np.float32))) * 0.1
    A = -jnp.abs(jnp.asarray(rng.normal(size=(c, n)).astype(np.float32)))
    Bm = jnp.asarray(rng.normal(size=(b, l, n)).astype(np.float32))
    Cm = jnp.asarray(rng.normal(size=(b, l, n)).astype(np.float32))
    D = jnp.asarray(rng.normal(size=(c,)).astype(np.float32))
    ref = sref.selective_scan_ref(x, dt, A, Bm, Cm, D)
    out = sref.selective_scan_chunked_ref(x, dt, A, Bm, Cm, D, chunk=chunk)
    np.testing.assert_allclose(out, ref, atol=1e-4, rtol=1e-3)


@given(st.integers(2, 40), st.sampled_from([4, 8, 16]), st.integers(0, 50))
@settings(**SETTINGS)
def test_ssd_chunk_invariance(l, chunk, seed):
    rng = np.random.default_rng(seed)
    b, h, p, g, n = 1, 2, 4, 1, 3
    x = jnp.asarray(rng.normal(size=(b, l, h, p)).astype(np.float32))
    dt = jnp.abs(jnp.asarray(rng.normal(size=(b, l, h)).astype(np.float32))) * 0.1
    A = -jnp.abs(jnp.asarray(rng.normal(size=(h,)).astype(np.float32)))
    Bm = jnp.asarray(rng.normal(size=(b, l, g, n)).astype(np.float32))
    Cm = jnp.asarray(rng.normal(size=(b, l, g, n)).astype(np.float32))
    D = jnp.asarray(rng.normal(size=(h,)).astype(np.float32))
    r1 = ssdref.ssd_ref(x, dt, A, Bm, Cm, D, chunk=chunk)
    r2 = ssdref.ssd_ref(x, dt, A, Bm, Cm, D, chunk=l)
    np.testing.assert_allclose(r1, r2, atol=1e-4, rtol=1e-3)


@given(st.integers(1, 4), st.integers(1, 4), st.integers(0, 100))
@settings(**SETTINGS)
def test_paged_decode_bit_identical_to_dense(b, n_pages, seed):
    # any scatter of the dense cache across pool pages (here: a random
    # permutation) gathers back to the identical rows, so paged decode
    # attention equals the dense decode reference bit-for-bit — the invariant
    # the whole paged serving path rests on
    from repro.kernels.paged_attention import ref as pref
    rng = np.random.default_rng(seed)
    ps, hq, hkv, d = 4, 4, 2, 8
    l = n_pages * ps
    q = jnp.asarray(rng.normal(size=(b, 1, hq, d)).astype(np.float32))
    k = rng.normal(size=(b, hkv, l, d)).astype(np.float32)  # heads-major
    v = rng.normal(size=(b, hkv, l, d)).astype(np.float32)
    lens = jnp.asarray(rng.integers(1, l + 1, size=b), jnp.int32)
    perm = rng.permutation(b * n_pages)
    k_pages = np.zeros((b * n_pages, hkv, ps, d), np.float32)
    v_pages = np.zeros_like(k_pages)
    table = np.zeros((b, n_pages), np.int32)
    for bi in range(b):
        for p in range(n_pages):
            pid = int(perm[bi * n_pages + p])
            k_pages[pid] = k[bi, :, p * ps:(p + 1) * ps]
            v_pages[pid] = v[bi, :, p * ps:(p + 1) * ps]
            table[bi, p] = pid
    out = pref.paged_decode_attention_ref(
        q, jnp.asarray(k_pages), jnp.asarray(v_pages), jnp.asarray(table),
        lens)
    ref = aref.decode_attention_ref(q, jnp.asarray(k), jnp.asarray(v), lens)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


# -- checkpoint roundtrip -------------------------------------------------------

@given(shapes=st.lists(st.tuples(st.integers(1, 5), st.integers(1, 5)),
                       min_size=1, max_size=4),
       seed=st.integers(0, 1000))
@settings(max_examples=10, deadline=None)
def test_checkpoint_roundtrip_arbitrary_trees(shapes, seed, tmp_path_factory):
    from repro.checkpoint.manager import CheckpointManager
    from repro.utils.trees import tree_allclose

    rng = np.random.default_rng(seed)
    tree = {f"k{i}": jnp.asarray(rng.normal(size=s).astype(np.float32))
            for i, s in enumerate(shapes)}
    d = tmp_path_factory.mktemp("ckpt")
    mgr = CheckpointManager(str(d), keep=1)
    mgr.save(1, tree, blocking=True)
    out = mgr.restore(1, jax.eval_shape(lambda: tree))
    assert tree_allclose(tree, out)
