"""Model FLOP/s utilization of the decode step: the model operations of
every token decoded in the window, each at its own context length (the
configuration's reference ``decode_flops``), over the batcher's decode time
(``decode_s``) times the chip's bf16 peak, in %.  Empty slots are no work of
the model."""


def read(run):
    w = run.window
    if w["decode_s"] <= 0:
        return None
    flops = sum(run.reference.decode_flops(run.model, c)
                for t in w["tick_records"] for c in t.decode_lens)
    if not flops:
        return None
    return 100.0 * flops / (w["decode_s"] * run.peaks["bf16_flops"])
