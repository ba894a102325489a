"""Published peaks of the accelerators this repo targets, keyed by the
``device_kind`` JAX reports (``jax.devices()[0].device_kind``).

Source: Google Cloud documentation, "TPU v5e" (system architecture): per
chip 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s, 1,600 Gbit/s of
chip-to-chip interconnect (four links of 400 Gbit/s).

A kind missing from the table is an error, never a default: a number
computed against another chip's peak would be wrong without saying so.
"""

from __future__ import annotations

_V5E = {
    "bf16_flops": 197e12,  # FLOP/s, MXU bf16
    "int8_ops": 393e12,  # OP/s, MXU int8
    "hbm_bw": 819e9,  # B/s
    "hbm_bytes": 16 * 1024**3,
    "ici_link_bw": 50e9,  # B/s per link (400 Gbit/s)
}

PEAKS: dict[str, dict[str, float]] = {
    "TPU v5 lite": _V5E,  # what JAX reports for a v5e chip
}

#: The device the analytic projections (dry-run roofline, fig5/lm rows) assume.
V5E_KIND = "TPU v5 lite"


def peaks(device_kind: str) -> dict[str, float]:
    """The peak table of ``device_kind``; KeyError for a kind not listed."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
