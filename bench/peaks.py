"""Published peaks of each accelerator the benchmark runs on, keyed by
``jax.Device.device_kind``. A device that is not listed is an error: a
roofline or utilisation share against a guessed peak means nothing."""
from __future__ import annotations

#: Google Cloud documentation, "TPU v5e": per chip, 197 TFLOP/s bf16,
#: 393 TOP/s int8, 16 GB HBM2 at 819 GB/s.
PEAKS = {
    "TPU v5 lite": {
        "flops_bf16": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peak(device_kind: str) -> dict:
    """The peak table entry of ``device_kind``; raises KeyError when the
    device is not in the table."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None


def roofline_seconds(flops: float, nbytes: float, device_kind: str):
    """(least seconds the chip needs for the work, which bound sets it)."""
    p = peak(device_kind)
    t_c = flops / p["flops_bf16"]
    t_m = nbytes / p["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
