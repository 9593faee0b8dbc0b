"""Peaks of the chips this benchmark has run on, keyed by ``device_kind`` as
JAX reports it.  A device that is not listed is an error, never a default.

Source: Google Cloud documentation, "TPU v5e" system architecture: 197
TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s per chip, 1600 Gbit/s
of chip-to-chip interconnect.
"""
PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes": 16e9, "hbm_bytes_per_s": 819e9,
                    "ici_bits_per_s": 1600e9},
    "TPU v5e": {"bf16_flops": 197e12, "int8_ops": 393e12,
                "hbm_bytes": 16e9, "hbm_bytes_per_s": 819e9,
                "ici_bits_per_s": 1600e9},
}


def peak(device_kind, what):
    if device_kind not in PEAKS:
        raise KeyError("no peaks listed for device kind %r: add a row, with "
                       "its source, to chipbench/lib/peaks.py" % device_kind)
    return PEAKS[device_kind][what]
