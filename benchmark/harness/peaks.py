"""Published peaks of the chips the benchmark may run on, by ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" system architecture page: one
chip has 197 TFLOP/s in bf16, 393 TOP/s in int8, 16 GB of HBM2e at 819 GB/s,
and 1,600 Gbit/s of chip-to-chip interconnect. A device that is not in this
table is an error, not a default.
"""

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12, "hbm_bytes": 16e9,
                    "hbm_bytes_per_s": 819e9, "ici_bits_per_s": 1600e9},
}


def peaks(device_kind):
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise SystemExit(f"no published peaks for device kind {device_kind!r}; "
                         f"add it with its source to benchmark/harness/peaks.py")
