"""Roofline share of the paged decode-attention kernel over the traced
ticks: operations and bytes of every decoded slot at the context it
attended over (``kernels/paged_attention.py``), in every attention layer,
against the kernel's device time.  The counts are of the live context, so
pages the kernel walks without need lower the share."""


def _attention_layers(m):
    period = m.get("attn_layer_period", 0)
    if period:      # one attention layer a period (jamba)
        return m["num_layers"] // period
    return m["num_layers"]


def read(run):
    import peaks
    red = run.reduction
    if red is None or not red.kernel_ns.get("paged_decode_attention"):
        return None
    m = run.model
    k = run.bench.kernel("paged_attention")
    flops = nbytes = 0.0
    for t in run.window["traced_ticks"]:
        for c in t.decode_lens:
            f, b = k.cost(c, m["num_heads"], m["num_kv_heads"],
                          m["head_dim"])
            flops, nbytes = flops + f, nbytes + b
    layers = _attention_layers(m)
    return peaks.roofline_share(flops * layers, nbytes * layers,
                                red.kernel_ns["paged_decode_attention"],
                                run.peaks)
