"""The traffic copies: deterministic by seed, the same work for every seed
in a stratified order that seeds change only between neighbouring ranks,
prompt lengths rounded up and clipped."""

from __future__ import annotations

import numpy as np
import pytest

import chipfixtures  # noqa: F401  (the benchmark on the path)
import harness

BENCH = harness.Bench.from_repo()


def _mix(name="chat-mamba-2.8b"):
    return BENCH.traffic(name)


def test_same_seed_same_requests():
    kind = BENCH.traffic_kind("open_lognormal")
    a = kind.generate(_mix(), 2**31 + 99, 45.0, 50280)
    b = kind.generate(_mix(), 2**31 + 99, 45.0, 50280)
    assert [(r.due_s, r.max_new_tokens) for r in a] == \
        [(r.due_s, r.max_new_tokens) for r in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


def test_seeds_permute_the_same_work():
    kind = BENCH.traffic_kind("open_lognormal")
    mix = _mix()
    a = kind.generate(mix, 1, 45.0, 50280)
    b = kind.generate(mix, 2**33 + 5, 45.0, 50280)
    assert len(a) == len(b) == round(mix["rate_per_s"] * 45.0)
    assert sorted(len(r.prompt) for r in a) == sorted(len(r.prompt) for r in b)
    assert sorted(r.max_new_tokens for r in a) == \
        sorted(r.max_new_tokens for r in b)
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]
    gaps = lambda rs: np.sort(np.diff([r.due_s for r in rs]))
    assert np.allclose(np.sort(np.concatenate([gaps(a), [45.0 - a[-1].due_s]])),
                       np.sort(np.concatenate([gaps(b), [45.0 - b[-1].due_s]])))
    assert a[0].due_s == 0.0 and a[-1].due_s < 45.0


def test_lengths_rounded_and_clipped():
    kind = BENCH.traffic_kind("open_lognormal")
    mix = _mix()
    prompts, outputs = kind.lengths(mix, 400)
    assert prompts.min() >= mix["prompt_min"]
    assert prompts.max() <= mix["prompt_max"]
    assert (prompts % mix["prompt_round"] == 0).all()
    assert outputs.min() >= mix["output_min"]
    assert outputs.max() <= mix["output_max"]
    assert abs(np.median(prompts) - mix["prompt_median"]) <= \
        mix["prompt_round"]


@pytest.mark.parametrize("n", [54, 56])
def test_each_block_takes_one_of_each_stratum(n):
    kind = BENCH.traffic_kind("open_lognormal")
    vals = np.arange(n)
    got = kind.stratified_order(vals[::-1].copy(), 6,
                                np.random.default_rng(2**31 + 3))
    assert sorted(got.tolist()) == vals.tolist()
    which = np.concatenate([[k] * len(s) for k, s in
                            enumerate(np.array_split(vals, 6))])
    for j in range(0, n - 5, 6):
        assert sorted(which[got[j:j + 6]].tolist()) == list(range(6))


def test_seeds_swap_only_neighbouring_ranks():
    kind = BENCH.traffic_kind("open_lognormal")
    vals = np.random.default_rng(7).permutation(41)
    outs = [kind.swap_neighbours(vals, np.random.default_rng([2**33 + k]))
            for k in range(3)]
    for out in outs:
        assert sorted(out.tolist()) == list(range(41))
        assert (out // 2 == vals // 2).all()      # ranks 2k, 2k+1 pair up
    assert any((out != vals).any() for out in outs)
    assert (outs[0] != outs[1]).any()
