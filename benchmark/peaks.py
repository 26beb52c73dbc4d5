"""Published peaks by ``device_kind``. A device that is not here is an
error, never a default.

TPU v5e (JAX reports ``TPU v5 lite``): Google Cloud documentation, "TPU
v5e" system architecture: 197 TFLOP/s bf16, 16 GB HBM2e at 819 GB/s,
1,600 Gbit/s of inter-chip interconnect per chip.
"""

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9,
                    "source": "cloud.google.com/tpu/docs/v5e"},
}


def peak(device_kind):
    if device_kind not in PEAKS:
        raise SystemExit("device_kind %r is not in benchmark/peaks.py %s"
                         % (device_kind, sorted(PEAKS)))
    return PEAKS[device_kind]
