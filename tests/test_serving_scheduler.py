"""Continuous-batching scheduler: correctness vs the single-request
generate() path, slot reuse, EOS/max-token stopping, occupancy, admission
edge cases, drain-stall detection, and token feedback through the host
mirror against the per-slot device loop."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import tiny_model_config
from repro.configs.registry import get_smoke_config
from repro.models.model import build_model
from repro.serving.paging import PagedPlan
from repro.serving.scheduler import ContinuousBatcher, DrainStall, Request
from repro.train.serve_step import generate, sample_token
from repro.utils.config import RunConfig, ShapeConfig


@pytest.fixture(scope="module")
def served():
    cfg = tiny_model_config()
    run = RunConfig(model=cfg, shape=ShapeConfig("s", 64, 4, "decode"))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, run, model, params


def test_matches_single_request_greedy(served):
    cfg, run, model, params = served
    prompt = np.asarray(
        jax.random.randint(jax.random.PRNGKey(1), (6,), 0, cfg.vocab_size))
    ref = np.asarray(generate(model, run, params,
                              {"tokens": jnp.asarray(prompt)[None]},
                              num_steps=5))[0]
    b = ContinuousBatcher(model, run, params, num_slots=2, cache_len=32)
    b.submit(Request(uid=0, prompt=prompt, max_new_tokens=5))
    done = b.run_until_drained()
    assert len(done) == 1
    np.testing.assert_array_equal(np.asarray(done[0].generated), ref)


def test_concurrent_requests_match_sequential(served):
    cfg, run, model, params = served
    rng = jax.random.PRNGKey(2)
    prompts = [np.asarray(jax.random.randint(k, (5,), 0, cfg.vocab_size))
               for k in jax.random.split(rng, 3)]
    refs = [np.asarray(generate(model, run, params,
                                {"tokens": jnp.asarray(p)[None]},
                                num_steps=4))[0] for p in prompts]
    b = ContinuousBatcher(model, run, params, num_slots=2, cache_len=32)
    for i, p in enumerate(prompts):  # 3 requests > 2 slots: forces reuse
        b.submit(Request(uid=i, prompt=p, max_new_tokens=4))
    done = b.run_until_drained()
    assert len(done) == 3
    by_uid = {d.request.uid: d.generated for d in done}
    for i, ref in enumerate(refs):
        np.testing.assert_array_equal(np.asarray(by_uid[i]), ref)


def test_eos_stops_early(served):
    cfg, run, model, params = served
    prompt = np.asarray([1, 2, 3])
    b = ContinuousBatcher(model, run, params, num_slots=1, cache_len=32)
    b.submit(Request(uid=0, prompt=prompt, max_new_tokens=30))
    # pick the greedy first token as "EOS" so it stops immediately
    ref = np.asarray(generate(model, run, params,
                              {"tokens": jnp.asarray(prompt)[None]},
                              num_steps=1))[0]
    b.eos_token = int(ref[0])
    done = b.run_until_drained()
    assert len(done) == 1
    assert len(done[0].generated) == 1


def test_occupancy_tracked(served):
    cfg, run, model, params = served
    b = ContinuousBatcher(model, run, params, num_slots=2, cache_len=32)
    for i in range(4):
        b.submit(Request(uid=i, prompt=np.asarray([1, 2]), max_new_tokens=3))
    b.run_until_drained()
    assert 1.0 <= b.mean_occupancy <= 2.0
    assert len(b.completed) == 4


# --------------------------------------------------------------------------
# admission edge cases
# --------------------------------------------------------------------------

def test_submit_while_full_queues_until_slot_frees(served):
    cfg, run, model, params = served
    b = ContinuousBatcher(model, run, params, num_slots=1, cache_len=32)
    b.submit(Request(uid=0, prompt=np.asarray([1, 2]), max_new_tokens=4))
    b.tick()  # admits uid 0; the only slot is now busy
    b.submit(Request(uid=1, prompt=np.asarray([3, 4]), max_new_tokens=2))
    b.tick()
    # uid 1 stays queued while uid 0 holds the slot
    assert [r.uid for r in b.queue] == [1]
    assert b._slots[0] is not None and b._slots[0].request.uid == 0
    done = b.run_until_drained()
    assert {d.request.uid for d in done} == {0, 1}
    assert not b.queue


def test_zero_free_slots_after_maybe_finish(served):
    # both requests finish on the same tick: _maybe_finish frees both slots
    # and the next tick admits from the queue into the freed slots
    cfg, run, model, params = served
    b = ContinuousBatcher(model, run, params, num_slots=2, cache_len=32)
    for i in range(2):
        b.submit(Request(uid=i, prompt=np.asarray([1, 2]),
                         max_new_tokens=3))
    b.submit(Request(uid=2, prompt=np.asarray([5, 6]), max_new_tokens=3))
    b.tick()   # admit 0, 1 (token 1 from prefill, token 2 decoded)
    assert b._free_slots() == [] and [r.uid for r in b.queue] == [2]
    b.tick()   # token 3 for both -> both finish, both slots free
    assert len(b._free_slots()) == 2
    assert len(b.completed) == 2
    done = b.run_until_drained()
    assert {d.request.uid for d in done} == {0, 1, 2}


def test_mean_occupancy_of_empty_run(served):
    cfg, run, model, params = served
    b = ContinuousBatcher(model, run, params, num_slots=2, cache_len=32)
    assert b.run_until_drained() == []
    assert b.mean_occupancy == 0.0   # no div-by-zero on zero ticks
    assert b.ticks == 0 and not b.stalled


# --------------------------------------------------------------------------
# interleave policy
# --------------------------------------------------------------------------

def test_drain_policy_refills_only_when_batch_empties(served):
    # mirror of the simulator's 'drain' admission gate: with a resident
    # request, queued work must wait until every slot frees
    cfg, run, model, params = served
    b = ContinuousBatcher(model, run, params, num_slots=2, cache_len=32,
                          interleave="drain")
    b.submit(Request(uid=0, prompt=np.asarray([1, 2]), max_new_tokens=4))
    b.tick()   # admits uid 0 (empty batch)
    b.submit(Request(uid=1, prompt=np.asarray([3, 4]), max_new_tokens=2))
    b.tick()
    # a free slot exists, but drain holds uid 1 back while uid 0 runs
    assert [r.uid for r in b.queue] == [1]
    done = b.run_until_drained()
    assert {d.request.uid for d in done} == {0, 1}
    with pytest.raises(ValueError, match="interleave"):
        ContinuousBatcher(model, run, params, interleave="bogus")


# --------------------------------------------------------------------------
# mixed-temperature batches
# --------------------------------------------------------------------------

def test_mixed_temperature_batch_samples_per_request(served):
    # a hot request in slot 0 must not drag a greedy request resident in
    # slot 1 onto its temperature (the live[0] sampling bug): the greedy
    # request still reproduces the single-request greedy reference exactly
    cfg, run, model, params = served
    greedy_prompt = np.asarray([1, 2, 3])
    ref = np.asarray(generate(model, run, params,
                              {"tokens": jnp.asarray(greedy_prompt)[None]},
                              num_steps=5))[0]
    b = ContinuousBatcher(model, run, params, num_slots=2, cache_len=32)
    b.submit(Request(uid=0, prompt=np.asarray([4, 5]), max_new_tokens=5,
                     temperature=8.0))      # occupies slot 0
    b.submit(Request(uid=1, prompt=greedy_prompt, max_new_tokens=5,
                     temperature=0.0))      # slot 1, decodes greedily
    done = b.run_until_drained()
    by_uid = {d.request.uid: d.generated for d in done}
    np.testing.assert_array_equal(np.asarray(by_uid[1]), ref)
    assert all(0 <= t < cfg.vocab_size for t in by_uid[0])


def test_mixed_temperature_batch_deterministic_per_seed(served):
    cfg, run, model, params = served

    def tokens(seed):
        b = ContinuousBatcher(model, run, params, num_slots=2, cache_len=32,
                              seed=seed)
        b.submit(Request(uid=0, prompt=np.asarray([4, 5]), max_new_tokens=6,
                         temperature=5.0))
        b.submit(Request(uid=1, prompt=np.asarray([1, 2]), max_new_tokens=6))
        done = b.run_until_drained()
        return {d.request.uid: list(d.generated) for d in done}

    assert tokens(7) == tokens(7)
    # the hot stream actually samples: across seeds it almost surely moves
    assert tokens(7)[0] != tokens(8)[0] or tokens(7)[0] != tokens(9)[0]


# --------------------------------------------------------------------------
# drain-stall detection
# --------------------------------------------------------------------------

def test_run_until_drained_raises_on_tick_budget(served):
    cfg, run, model, params = served
    b = ContinuousBatcher(model, run, params, num_slots=1, cache_len=32)
    b.submit(Request(uid=0, prompt=np.asarray([1, 2]), max_new_tokens=8))
    b.submit(Request(uid=1, prompt=np.asarray([3, 4]), max_new_tokens=8))
    with pytest.raises(DrainStall, match="not drained after 2 ticks") as e:
        b.run_until_drained(max_ticks=2)
    assert e.value.pending > 0
    # the budget is per call, not cumulative: a fresh call finishes the work
    done = b.run_until_drained(max_ticks=100)
    assert {d.request.uid for d in done} == {0, 1}
    assert not b.stalled


def test_run_until_drained_warn_flags_partial(served):
    cfg, run, model, params = served
    b = ContinuousBatcher(model, run, params, num_slots=1, cache_len=32)
    b.submit(Request(uid=0, prompt=np.asarray([1, 2]), max_new_tokens=8))
    with pytest.warns(RuntimeWarning, match="not drained"):
        done = b.run_until_drained(max_ticks=1, on_limit="warn")
    assert b.stalled and done == []
    with pytest.raises(ValueError, match="on_limit"):
        b.run_until_drained(on_limit="bogus")


# --------------------------------------------------------------------------
# token feedback: the host mirror against the per-slot device loop
# --------------------------------------------------------------------------

class PerSlotFeedbackBatcher(ContinuousBatcher):
    """The oracle: tokens fed back as the batcher once did, into a device
    vector with a read and an eager scatter per live slot (the first token
    of an admission too)."""

    def __init__(self, *args, **kw):
        super().__init__(*args, **kw)
        self._dev_tokens = jnp.zeros((self.num_slots,), jnp.int32)

    @property
    def _tokens(self):
        return self._dev_tokens

    def _prefill_and_seat(self, req, slot, pages):
        syncs = super()._prefill_and_seat(req, slot, pages)
        rs = self._slots[slot] or self.completed[-1]
        self._dev_tokens = self._dev_tokens.at[slot].set(rs.generated[0])
        return syncs

    def _step(self, live):
        self.ticks += 1
        self._occupancy_sum += len(live)
        new_state, logits = self._decode(self.params, self.state,
                                         self._dev_tokens[:, None])
        jax.block_until_ready(logits)
        self.state = new_state
        self._key, sub = jax.random.split(self._key)
        if any(rs.request.temperature > 0.0 for rs in live):
            temps = np.zeros((self.num_slots,), np.float32)
            for rs in live:
                temps[rs.slot] = rs.request.temperature
            toks = sample_token(logits, sub, jnp.asarray(temps))
        else:
            toks = sample_token(logits, sub, 0.0)
        for rs in live:
            tok = int(toks[rs.slot])
            rs.generated.append(tok)
            self._dev_tokens = self._dev_tokens.at[rs.slot].set(tok)
            self._maybe_finish(rs, tok)
        return 1 + len(live)


# (tick, request): two at the start, two more mid-flight, greedy and hot
# requests sharing steps, never more than three of the four slots live
FEEDBACK_TRAFFIC = [
    (0, dict(uid=0, prompt=[3, 1, 4, 1, 5], max_new_tokens=9)),
    (0, dict(uid=1, prompt=[2, 7], max_new_tokens=4, temperature=3.0)),
    (2, dict(uid=2, prompt=[6, 2, 8], max_new_tokens=5)),
    (5, dict(uid=3, prompt=[1, 4, 1, 4, 2], max_new_tokens=6,
             temperature=1.5)),
]


def _feedback_batchers(model_name, plan):
    if model_name == "moe":
        # rows of one decode step share the router's groups, so an empty
        # row's input could reach a live row through expert capacity
        cfg = get_smoke_config("llama4-maverick-400b-a17b")
    else:
        cfg = tiny_model_config()
    run = RunConfig(model=cfg, shape=ShapeConfig("s", 64, 4, "decode"))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    paged = PagedPlan(paging=True, pool_pages=16, page_size=4,
                      pages_per_slot_max=8) if plan == "paged" else None

    def make(cls, eos=None):
        return cls(model, run, params, num_slots=4, cache_len=32, seed=11,
                   paged=paged, eos_token=eos)
    return make


def _drive(batchers):
    """Tick the batchers side by side over FEEDBACK_TRAFFIC; after every
    tick, each one's tokens per request and its decode input."""
    for b in batchers:
        b.ticks_seen = []
    for tick in range(40):
        for at, kw in FEEDBACK_TRAFFIC:
            if at == tick:
                for b in batchers:
                    b.submit(Request(**{**kw, "prompt": np.asarray(
                        kw["prompt"], np.int32)}))
        for b in batchers:
            b.tick()
            states = [s for s in b._slots if s is not None] + b.completed
            b.ticks_seen.append((
                {rs.request.uid: list(rs.generated) for rs in states},
                np.asarray(b._tokens)))
    return [b.ticks_seen for b in batchers]


@pytest.mark.parametrize("model_name,plan", [
    ("tiny", "dense"), ("tiny", "paged"), ("moe", "dense"), ("moe", "paged")])
def test_host_mirror_feeds_back_what_per_slot_loop_did(model_name, plan):
    make = _feedback_batchers(model_name, plan)
    # stop uid 0 on EOS: its third token, unless that came sooner
    (probe,) = _drive([make(ContinuousBatcher)])
    eos = probe[-1][0][0][2]
    new, old = _drive([make(ContinuousBatcher, eos),
                       make(PerSlotFeedbackBatcher, eos)])
    for (gen_new, tok_new), (gen_old, tok_old) in zip(new, old):
        assert gen_new == gen_old
        np.testing.assert_array_equal(tok_new, tok_old)   # empty rows too
    final = new[-1][0]
    assert sorted(final) == [0, 1, 2, 3]
    assert len(final[0]) < 9 and final[0][-1] == eos
    assert all(len(g) == kw["max_new_tokens"] or g[-1] == eos
               for (_, kw), g in zip(FEEDBACK_TRAFFIC, map(final.get,
                                                           range(4))))
