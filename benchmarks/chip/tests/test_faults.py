"""A run with the timed path broken underneath has to come out not
correct: the harness's look for a chip is skipped, everything else of a run
is driven on the CPU at a tiny size, with each fault a serving cell can
have planted in the batcher's compiled steps."""

from __future__ import annotations

import time

import jax.numpy as jnp
import pytest

from chipfixtures import interpret, tiny_bench  # noqa: F401
import serve_driver


def state_unchanged(batcher):
    """The decode step hands back the state it was given."""
    decode = batcher._decode

    def step(params, state, tokens):
        _, logits = decode(params, state, tokens)
        return state, logits

    batcher._decode = step


def half_batch(batcher):
    """The decode step computes half of the slots only: every odd slot gets
    the row of the even slot before it."""
    decode = batcher._decode

    def step(params, state, tokens):
        new, logits = decode(params, state, tokens)
        return new, jnp.repeat(logits[0::2], 2, axis=0)[:logits.shape[0]]

    batcher._decode = step


def token_altered(batcher):
    """Every token comes out one id above the one the step ranks first."""
    decode, prefill = batcher._decode, batcher._prefill

    def shift(logits):
        return jnp.roll(logits, 1, axis=-1)

    batcher._decode = lambda p, s, t: (lambda o: (o[0], shift(o[1])))(
        decode(p, s, t))
    batcher._prefill = lambda p, b: (lambda o: (o[0], shift(o[1])))(
        prefill(p, b))


@pytest.mark.parametrize("fault", [state_unchanged, half_batch,
                                   token_altered])
def test_broken_step_is_not_correct(tiny_bench, interpret, fault):
    res = serve_driver.run_cell(
        tiny_bench, tiny_bench.cell("tiny-ssm-chat"), seed=12345,
        seconds=4.0, trace=False, t_start=time.perf_counter(), fault=fault,
        kernel_mode=interpret)
    assert res["correct"] is False, res["compared"]
