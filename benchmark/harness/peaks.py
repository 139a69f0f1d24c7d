"""Published per-chip peaks, keyed by `jax.devices()[0].device_kind`.

Copied from `deeplearning4j_tpu/inference/profiler.py:DEVICE_PEAKS` so that a
later PR cannot move the yardstick by editing the program. A device kind
that is not a key is an error, never a default."""
from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e" system architecture:
    # 197 TFLOP/s bf16 on the MXU, 16 GB HBM2e at 819 GB/s per chip.
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "cloud.google.com/tpu/docs/v5e (TPU v5e system "
                  "architecture): 197 TFLOP/s bf16, 819 GB/s, 16 GB",
    },
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peak for device kind {device_kind!r}; add it to "
            f"benchmark/harness/peaks.py with its source") from None


def least_seconds(flops: float, byts: float, peaks: dict) -> float:
    """The least time the chip could take for that work: the larger of
    operations over peak operations and bytes over peak bytes a second."""
    return max(flops / peaks["bf16_flops_per_s"],
               byts / peaks["hbm_bytes_per_s"])
