"""Roofline share of the Mamba-1 selective-scan prefill kernel over the
traced ticks: operations and bytes of each prompt prefilled there
(``kernels/mamba_scan.py``), in every layer, against the kernel's device
time."""

def read(run):
    import peaks
    red = run.reduction
    if red is None or not red.kernel_ns.get("selective_scan"):
        return None
    m = run.model
    k = run.bench.kernel("mamba_scan")
    flops = nbytes = 0.0
    for t in run.window["traced_ticks"]:
        for n in t.prefills:
            f, b = k.cost(n, m["ssm_expand"] * m["d_model"], m["ssm_state"])
            flops, nbytes = flops + f, nbytes + b
    layers = m["num_layers"]
    return peaks.roofline_share(flops * layers, nbytes * layers,
                                red.kernel_ns["selective_scan"], run.peaks)
