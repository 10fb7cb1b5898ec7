#!/usr/bin/env python3
"""Smoke check of the system on a TPU chip, at published model widths.

    python chip_smoke.py               # one chip: serve zamba2-2.7b
    python chip_smoke.py --four-chips  # four chips: data-parallel training

One chip (the default): zamba2-2.7b at its published widths (54 layers,
d_model 2560), seeded random bf16 weights, served through the paged
``ContinuousBatcher`` — the batcher ``repro.launch.serve --workload`` and
``ReplayServingEnv`` drive — by ``serving.replay.replay_trace``: 16 seeded
requests with prompts of 128 to 480 tokens and 32 new tokens each.  Every
request must complete, every kernel dispatch traced on the served path must
be a compiled Pallas kernel, and one request's prefill and first decode
logits must agree with the same model run with every kernel family in
``ref`` mode.

``--four-chips``: the data-parallel train step ``repro.launch.train`` builds
(``make_mesh`` + ``state_shardings`` over every device), for zamba2-2.7b at
published widths cut to one period of its layer pattern (5 Mamba-2 layers
plus the shared attention block), global batch 8 x 512 tokens, 3 steps —
against the same 3 steps on one device.  The losses must agree.

The last line of standard output is ``{"ok": true, "device": {...}}`` and
the exit code 0 only when every check passed on a TPU; on any other backend,
or outside a checkout of this repository, the script exits non-zero and
prints no result.  It runs in one process, which holds the chip.  Nothing
printed above the last line is a speed measurement.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ARCH = "zamba2-2.7b"
PROMPT_LENS = (128, 256, 384, 480)
N_REQUESTS = 16
NEW_TOKENS = 32
NUM_SLOTS = 8
COMPARE_DECODE_STEPS = 4

# Pallas vs ref logits: max |difference| over max |ref logit| of one row.
# Both runs use the same bf16 weights and carry bf16 activations between
# layers; they differ only inside the kernels (f32 math in VMEM against the
# XLA reference ops), so each layer's output may differ by a bf16 rounding,
# 2^-8 relative.  Over 54 layers such independent differences add up about
# as the square root of the count, ~3%; 5% leaves room for the tail.
LOGIT_TOL = 5e-2
# 4-way data-parallel vs one-device train step: the same math with the
# gradient mean taken as a mean of four shard means, in a different order.
# In bf16 compute that reorders roundings of 2^-8 relative inside the
# backward, which moves the updated weights, and so the next losses, by far
# less than 1% at this learning rate; the first step's loss sees only the
# forward and must agree even closer.
LOSS_TOL = 1e-2


def _say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def _import_repro() -> bool:
    src = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        return False
    sys.path.insert(0, src)
    return True


def _rel_err(a, b) -> float:
    import numpy as np
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    return float(np.max(np.abs(a - b)) / max(float(np.max(np.abs(b))), 1e-30))


def _modes(records) -> dict:
    out: dict = {}
    for r in records:
        key = r.mode + (" [interpret]" if r.interpret else "") + \
            (" [backward]" if r.backward else "")
        out.setdefault(r.family, {}).setdefault(key, 0)
        out[r.family][key] += 1
    return out


def _check_forward_modes(records, mode: str, interpret: bool) -> None:
    """Every forward dispatch ran ``mode``; only backward passes ran ref."""
    from repro.kernels import dispatch
    assert records, "no kernel dispatch was traced"
    for r in records:
        if r.backward:
            assert r.mode == dispatch.REF, r
            continue
        assert r.mode == mode and r.interpret == interpret, (
            f"{r.family} resolved to {r.mode} (interpret={r.interpret}), "
            f"expected {mode} (interpret={interpret})")


def _tap_logits(batcher, sink: list) -> None:
    """Copy out the logits of the batcher's compiled steps as they return."""
    prefill, decode = batcher._prefill, batcher._decode

    def tapped_prefill(params, batch):
        state, logits = prefill(params, batch)
        sink.append(logits[0])
        return state, logits

    def tapped_decode(params, state, tokens):
        state, logits = decode(params, state, tokens)
        sink.append(logits[0])
        return state, logits

    batcher._prefill, batcher._decode = tapped_prefill, tapped_decode


def serve_check(cfg, *, seed: int = 0, prompt_lens=PROMPT_LENS,
                n_requests: int = N_REQUESTS, new_tokens: int = NEW_TOKENS,
                num_slots: int = NUM_SLOTS,
                compare_steps: int = COMPARE_DECODE_STEPS,
                logit_tol: float = LOGIT_TOL) -> dict:
    """Serve a seeded trace through the paged batcher and check it; raises
    ``AssertionError`` on any failed check."""
    import jax
    import numpy as np

    from repro.kernels import dispatch
    from repro.models.model import build_model
    from repro.serving.paging import PagedPlan
    from repro.serving.replay import replay_trace
    from repro.serving.scheduler import ContinuousBatcher, Request
    from repro.train.serve_step import jitted_steps
    from repro.utils.config import RunConfig, ShapeConfig
    from repro.workloads.traces import RequestSpec, Trace

    mode = dispatch.default_mode()
    assert mode != dispatch.REF, "the served path must run the kernels"
    plan = PagedPlan.from_config({"pages.paging": "on"})
    run = RunConfig(model=cfg, shape=ShapeConfig(
        "chip_smoke", plan.slot_capacity, num_slots, "decode"))
    model = build_model(cfg, run.parallel)
    params = jax.jit(model.init)(jax.random.PRNGKey(seed))
    jax.block_until_ready(params)

    def batcher():
        return ContinuousBatcher(model, run, params, num_slots=num_slots,
                                 paged=plan, seed=seed)

    def trace(lens, out_len):
        rng = np.random.default_rng(seed)
        gaps = rng.exponential(0.01, size=len(lens))
        reqs = tuple(RequestSpec(uid=i, arrival_s=float(t), prompt_len=n,
                                 output_len=out_len)
                     for i, (t, n) in enumerate(zip(np.cumsum(gaps), lens)))
        return Trace(kind="chip_smoke", spec="chip_smoke", seed=seed,
                     requests=reqs)

    served = batcher()
    with dispatch.record_resolutions() as rec:
        # set-up: one request per prompt length compiles every prefill
        # length and the decode step
        t0 = time.perf_counter()
        warm = replay_trace(served, trace(prompt_lens, 2), seed=seed)
        compile_s = time.perf_counter() - t0
        lens = [prompt_lens[i % len(prompt_lens)] for i in range(n_requests)]
        report = replay_trace(served, trace(lens, new_tokens), seed=seed + 1)
    assert warm.completed == len(prompt_lens), warm
    assert report.completed == n_requests and report.rejected == 0, report
    assert report.tokens == n_requests * new_tokens, report
    _check_forward_modes(rec, mode, mode == dispatch.PALLAS_INTERPRET)

    # one request on a fresh batcher (same compiled steps), logits tapped
    prompt = np.random.default_rng(seed + 2).integers(
        0, cfg.vocab_size, size=prompt_lens[0], dtype=np.int32)
    check = batcher()
    seen: list = []
    _tap_logits(check, seen)
    check.submit(Request(uid=0, prompt=prompt,
                         max_new_tokens=compare_steps + 1))
    (done,) = check.run_until_drained()
    ours = [np.asarray(x, np.float32) for x in seen]
    assert len(ours) == compare_steps + 1, len(ours)

    # the same model with every family in ref mode: fresh traces, since the
    # compiled-step cache keys on the kernel mode; decode is teacher-forced
    # with the batcher's tokens
    os.environ[dispatch.KERNEL_MODE_ENV] = dispatch.REF
    try:
        prefill, decode = jitted_steps(model, run, cache_len=plan.slot_capacity)
        with dispatch.record_resolutions() as rec_ref:
            state, logits = prefill(params, {"tokens": prompt[None]})
            refs = [np.asarray(logits[0], np.float32)]
            for tok in done.generated[:compare_steps]:
                state, logits = decode(params, state,
                                       np.asarray([[tok]], np.int32))
                refs.append(np.asarray(logits[0], np.float32))
    finally:
        del os.environ[dispatch.KERNEL_MODE_ENV]
    _check_forward_modes(rec_ref, dispatch.REF, False)
    errs = [_rel_err(a, b) for a, b in zip(ours, refs)]
    assert all(np.isfinite(x).all() for x in ours), "non-finite logits"
    assert max(errs) <= logit_tol, (
        f"logits vs ref: {errs} (tolerance {logit_tol})")
    return {
        "compile_setup_s": compile_s,
        "prefill_lengths_compiled": len(set(prompt_lens)),
        "requests_completed": report.completed,
        "tokens_generated": report.tokens,
        "logit_rel_err": {"prefill": errs[0], "decode": errs[1:]},
        "top1_agree": [int(np.argmax(a) == np.argmax(b))
                       for a, b in zip(ours, refs)],
        "modes": _modes(rec),
    }


def train_check(cfg, devices, *, seed: int = 0, global_batch: int = 8,
                seq_len: int = 512, steps: int = 3,
                loss_tol: float = LOSS_TOL) -> dict:
    """The data-parallel train step over ``devices`` against the same steps
    on one device; raises ``AssertionError`` on any failed check."""
    import jax
    import numpy as np

    from repro.data.pipeline import make_data
    from repro.kernels import dispatch
    from repro.launch.mesh import batch_shardings, make_mesh, state_shardings
    from repro.models.model import build_model
    from repro.train.optimizer import make_optimizer
    from repro.train.train_step import init_train_state, make_train_step
    from repro.utils.config import (MeshConfig, RunConfig, ShapeConfig,
                                    TrainConfig)

    mode = dispatch.default_mode()
    assert mode != dispatch.REF, "the train step must run the kernels"
    base = RunConfig(model=cfg,
                     shape=ShapeConfig("chip_smoke_train", seq_len,
                                       global_batch, "train"),
                     train=TrainConfig(lr=1e-4, warmup_steps=1,
                                       total_steps=steps))
    data = make_data(cfg, base.shape, seed=seed)
    batches = [data.batch_at(i) for i in range(steps)]

    def losses_on(n: int):
        run = base.replace(mesh=MeshConfig(shape=(n,), axes=("data",)))
        run.validate()
        model = build_model(cfg, run.parallel)
        opt = make_optimizer(run.train)
        mesh = make_mesh(run.mesh)
        with jax.set_mesh(mesh):
            def init():
                return init_train_state(model, run, opt,
                                        jax.random.PRNGKey(seed))
            shard = state_shardings(jax.eval_shape(init), run, mesh)
            bshard = batch_shardings(batches[0], mesh)
            state = jax.jit(init, out_shardings=shard)()
            step = jax.jit(make_train_step(model, run, opt),
                           in_shardings=(shard, bshard), donate_argnums=(0,))
            out = []
            with dispatch.record_resolutions() as rec:
                for b in batches:
                    state, metrics = step(state, jax.device_put(b, bshard))
                    out.append(float(metrics["loss"]))
        return out, rec

    t0 = time.perf_counter()
    many, rec = losses_on(len(devices))
    one, _ = losses_on(1)
    _check_forward_modes(rec, mode, mode == dispatch.PALLAS_INTERPRET)
    assert any(r.backward for r in rec), "no backward pass was traced"
    assert np.isfinite(many).all() and np.isfinite(one).all(), (many, one)
    diffs = [abs(a - b) / max(abs(b), 1e-30) for a, b in zip(many, one)]
    assert max(diffs) <= loss_tol, (
        f"losses {many} on {len(devices)} devices vs {one} on one: "
        f"relative differences {diffs} (tolerance {loss_tol})")
    return {"setup_and_steps_s": time.perf_counter() - t0,
            "losses_data_parallel": many, "losses_one_device": one,
            "loss_rel_diff": diffs, "modes": _modes(rec)}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip data-parallel training "
                         "check against one device")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    if not _import_repro():
        print("chip_smoke.py must run from a checkout of this repository "
              "(no src/repro beside it)", file=sys.stderr)
        return 2
    import jax

    if jax.default_backend() != "tpu":
        print(f"no TPU: jax found {jax.default_backend()!r}",
              file=sys.stderr)
        return 1
    from repro.configs.registry import get_model_config
    from repro.kernels import dispatch
    from repro.utils.compile_cache import enable_compile_cache

    _say(f"compile cache: {enable_compile_cache()}")
    devices = jax.devices()
    dev = devices[0]
    _say(f"{len(devices)} x {dev.device_kind}, kernel mode "
         f"{dispatch.default_mode()}")
    cfg = get_model_config(ARCH)
    if args.four_chips:
        if len(devices) != 4:
            print(f"--four-chips needs 4 devices, found {len(devices)}",
                  file=sys.stderr)
            return 1
        # one period of the layer pattern: 5 Mamba-2 + the shared attention
        result = train_check(cfg.replace(num_layers=cfg.hybrid_attn_period),
                             devices, seed=args.seed)
    else:
        result = serve_check(cfg, seed=args.seed)
    stats = dev.memory_stats() or {}
    result["peak_bytes_in_use"] = stats.get("peak_bytes_in_use",
                                            "not reported")
    for k, v in result.items():
        _say(f"{k}: {json.dumps(v)}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
