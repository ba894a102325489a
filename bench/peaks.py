"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` JAX reports. A copy of the program's ``repro.devices``
table, kept with the benchmark so that no change to the program moves a
yardstick.

Source: Google Cloud documentation, "TPU v5e" (system architecture): per
chip 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s, 1,600 Gbit/s
of chip-to-chip interconnect.

A kind missing from the table is an error, never a default.
"""

from __future__ import annotations

PEAKS: dict[str, dict[str, float]] = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,  # FLOP/s, MXU bf16
        "int8_ops": 393e12,  # OP/s, MXU int8
        "hbm_bw": 819e9,  # B/s
        "hbm_bytes": 16 * 1024**3,
    },
}


def peaks(device_kind: str) -> dict[str, float]:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
