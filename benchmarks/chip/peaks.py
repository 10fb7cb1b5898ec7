"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` that JAX reports.  A device missing here is an error: a
roofline or utilization against a guessed peak is no number.

Source: Google Cloud documentation, "TPU v5e" (system architecture):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s per chip.
"""

from __future__ import annotations

from typing import Dict

PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
    },
}


def peaks_for(device_kind: str) -> Dict[str, float]:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None


def roofline_share(flops: float, nbytes: float, kernel_ns: float,
                   peaks: Dict[str, float]):
    """The least time the chip could take for ``flops`` and ``nbytes`` over
    the measured ``kernel_ns``, in %; None where nothing was measured."""
    if not kernel_ns:
        return None
    t_min = max(flops / peaks["bf16_flops"],
                nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * t_min / (kernel_ns / 1e9)
