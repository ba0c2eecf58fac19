"""Published peaks of one chip, keyed by ``device_kind`` as JAX reports it.

A kind that is not here is an error, never a default: a share of a peak
taken against the wrong chip's numbers is worse than none.
"""

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s
    # int8, 16 GB of HBM at 819 GB/s, 1,600 Gbit/s of interconnect.
    "TPU v5 lite": {
        "flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "ici_bytes_per_s": 200e9,
        "source": "cloud.google.com/tpu/docs/v5e (system architecture)",
    },
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise SystemExit(
            f"chipbench: no peaks for device kind {device_kind!r}; add it "
            "to chipbench/peaks.py with its source") from None
