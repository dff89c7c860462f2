"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

Copied from the program's ``repro.core.costmodel.TPU_PEAKS`` so that the
yardstick cannot move with the program.  A kind missing here is an error,
never a default.

The MXU peak is the bf16 one.  The configurations' f32 products run at
``Precision.HIGHEST``, which the MXU executes as six bf16 passes, so a
compute-bound f32 tile kernel tops out near 1/6 of a roofline drawn
against this peak.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "flops": 197e12,           # bf16 FLOP/s
        "hbm_bytes_per_s": 819e9,
        "source": "Google Cloud documentation, 'TPU v5e': 197 TFLOP/s "
                  "bf16, 16 GB HBM at 819 GB/s",
    },
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}") from None


def least_seconds(flops: float, nbytes: float, device_kind: str) -> float:
    """The roofline: the least time one chip needs for ``flops`` operations
    over ``nbytes`` of HBM traffic."""
    p = peaks(device_kind)
    return max(flops / p["flops"], nbytes / p["hbm_bytes_per_s"])
