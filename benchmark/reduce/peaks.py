"""Published peaks by `device_kind` (copy of
hetu_tpu/telemetry/profiler.py:DEVICE_PEAKS). A device that is not in the
table is an error, never another chip's numbers."""

DEVICE_PEAKS = {
    "TPU v5 lite": {
        "tflops": 197.0, "gbs": 819.0, "hbm_gb": 16.0,
        "source": "Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
                  "16 GB HBM at 819 GB/s per chip"},
}


def peaks(device_kind):
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device_kind {device_kind!r}; add a row "
            "with its source to benchmark/reduce/peaks.py") from None


def utilization(items_per_s, flops_per_item, chips, device_kind):
    """Model FLOP/s utilization: required operations per second over
    chips x peak. Recomputed operations do not count."""
    return (items_per_s * flops_per_item
            / (chips * peaks(device_kind)["tflops"] * 1e12))
