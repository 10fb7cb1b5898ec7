"""Model FLOP/s utilization of the prefill step: the model operations of
every prompt prefilled in the window (the configuration's reference
``prefill_flops``) over the batcher's prefill time (``prefill_s``) times the
chip's bf16 peak, in %."""


def read(run):
    w = run.window
    if w["prefill_s"] <= 0:
        return None
    flops = sum(run.reference.prefill_flops(run.model, n)
                for t in w["tick_records"] for n in t.prefills)
    if not flops:
        return None
    return 100.0 * flops / (w["prefill_s"] * run.peaks["bf16_flops"])
