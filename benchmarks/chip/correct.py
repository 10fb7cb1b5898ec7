"""The comparison that decides ``correct`` for a served model.

Once the window has closed, a sample of the requests the program finished,
drawn from the seed and always holding the one with the most served tokens,
is run through the configuration's plain float32 reference: each prompt with
the tokens served after it, in one forward pass.  For every served token the
gap is the reference's best logit at that position less the reference's
logit of the served token (0 where the reference agrees).  The number
compared is the widest gap over the sample, ``max_logit_gap``, against the
configuration's ``correct.max_logit_gap``.  Every request is greedy, so a
sound program serves the reference's best token up to near-ties.

The control (``quant="fp8"``) puts the reference itself in the program's
place, with its projection matrices rounded to float8: at each position of
the same prompts and tokens, the gap of the token that the float8 forward
ranks first.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

import harness


def sample(served: Sequence[Any], seed: int, n: int) -> List[Any]:
    """Up to ``n`` finished requests: the one with the most served tokens,
    then others drawn from the seed."""
    done = [s for s in served if s.finished and s.tokens]
    if not done:
        return []
    longest = max(done, key=lambda s: (len(s.tokens), -s.uid))
    rest = [s for s in done if s is not longest]
    rng = harness.numpy_rng(seed, salt=2)
    pick = rng.permutation(len(rest))[:max(n - 1, 0)]
    return [longest] + [rest[i] for i in sorted(pick)]


@functools.lru_cache(maxsize=None)
def _scorer():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def score(h, head, served, other):
        logits = jnp.matmul(h, head, precision=jax.lax.Precision.HIGHEST)
        best = jnp.max(logits, -1)
        at = lambda t: jnp.take_along_axis(logits, t[:, None], -1)[:, 0]
        return best, at(served), at(other), jnp.argmax(logits, -1)

    return score


def readings(ref, params, model: Dict[str, Any], reqs: Sequence[Any],
             seq_len: int, group: int, quant: Optional[str] = None
             ) -> Dict[str, np.ndarray]:
    """Per served token: the reference's gap of the served token and, with
    ``quant``, of the token the quantised forward ranks first."""
    import jax
    import jax.numpy as jnp

    seqs, spans = [], []
    for r in reqs:
        p, g = len(r.prompt), len(r.tokens)
        toks = np.concatenate([r.prompt, np.asarray(r.tokens[:-1], np.int32)])
        if len(toks) > seq_len:
            raise ValueError(f"request {r.uid}: {len(toks)} tokens exceed "
                             f"the reference length {seq_len}")
        row = np.zeros((seq_len,), np.int32)
        row[:len(toks)] = toks
        served = np.zeros((seq_len,), np.int32)
        served[p - 1:p - 1 + g] = r.tokens
        seqs.append((row, served))
        spans.append((p - 1, p - 1 + g))
    score = _scorer()
    picks: List[np.ndarray] = [np.zeros((seq_len,), np.int32)] * len(seqs)
    with jax.default_matmul_precision("highest"):
        if quant is not None:
            head = ref.head(params, model, quant)
            picks = []
            for i in range(0, len(seqs), group):
                h = _hidden(ref, params, model, seqs[i:i + group], group,
                            quant)
                for j, (row, served) in enumerate(seqs[i:i + group]):
                    picks.append(np.asarray(score(h[j], head, served,
                                                  served)[3], np.int32))
            del head
        head = ref.head(params, model)
        gaps, gaps_ctrl = [], []
        for i in range(0, len(seqs), group):
            h = _hidden(ref, params, model, seqs[i:i + group], group)
            for j, (row, served) in enumerate(seqs[i:i + group]):
                k = i + j
                best, at_s, at_o, _ = score(h[j], head, served,
                                            jnp.asarray(picks[k]))
                lo, hi = spans[k]
                best = np.asarray(best)[lo:hi]
                gaps.append(best - np.asarray(at_s)[lo:hi])
                gaps_ctrl.append(best - np.asarray(at_o)[lo:hi])
    out = {"served": np.concatenate(gaps)}
    if quant is not None:
        out["control"] = np.concatenate(gaps_ctrl)
    return out


def _hidden(ref, params, model, rows, group, quant=None):
    import jax.numpy as jnp
    toks = np.stack([r for r, _ in rows] + [rows[0][0]] * (group - len(rows)))
    return ref.hidden(params, model, jnp.asarray(toks), quant=quant)


def check_served(bench, cfg_file: Dict[str, Any], params, served, seed: int
                 ) -> Dict[str, Dict[str, float]]:
    """The numbers compared, each with its limit; prints them last on
    standard error."""
    spec = cfg_file["correct"]
    reqs = sample(served, seed, spec["sample_requests"])
    if not reqs:
        harness.say("compared: no request finished in the window")
        return {"finished_requests": {"value": 0.0, "limit": -1.0}}
    ref = bench.reference(cfg_file["reference"])
    got = readings(ref, params, cfg_file["model"], reqs,
                   spec["reference_len"], spec["reference_batch"])
    gap = float(np.max(got["served"]))
    agree = float(np.mean(got["served"] == 0.0))
    harness.say(f"reference over {len(reqs)} requests, "
                f"{len(got['served'])} served tokens; top-1 agreement "
                f"{agree:.4f}")
    compared = {"max_logit_gap": {"value": gap,
                                  "limit": float(spec["max_logit_gap"])}}
    for k, v in compared.items():
        harness.say(f"compared {k}: {v['value']!r} limit {v['limit']!r}")
    return compared
