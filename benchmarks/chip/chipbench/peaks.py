"""Published peaks per chip, keyed by JAX's `device_kind`.

Source: Google Cloud documentation, "TPU v5e" (cloud.google.com/tpu/docs/
v5e): 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s. The round
loop's float32 matmuls run at default precision, i.e. as bf16 passes on
the MXU, so bf16 is the peak they are held to. A device kind that is not
in the table is an error, not a default.
"""
PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


def peak(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None
