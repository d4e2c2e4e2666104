"""Published peaks per chip, keyed by ``jax.Device.device_kind``.

A device that is not in the table is an error: a roofline or utilisation
against a guessed peak is no measurement.
"""
from __future__ import annotations

_V5E = {
    "bf16_flops": 197e12,          # FLOP/s, bf16 matrix units
    "int8_ops": 393e12,            # OP/s
    "hbm_bytes": 16e9,             # bytes of HBM
    "hbm_bytes_per_s": 819e9,      # HBM bandwidth
    "source": "Google Cloud documentation, 'TPU v5e' (system architecture)",
}

PEAKS: dict[str, dict] = {
    "TPU v5 lite": _V5E,           # what JAX reports for a v5e chip
    "TPU v5e": _V5E,
}


def peaks(device_kind: str) -> dict:
    """The peak table row of ``device_kind``; raises for an unknown chip."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None
