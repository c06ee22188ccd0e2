"""Published peaks of the chips the benchmark runs on, keyed by
``jax.Device.device_kind``.  A kind not in the table is an error."""
from __future__ import annotations

_V5E = {
    "flops_per_s": 197e12,       # bf16 dense matrix units
    "hbm_bytes_per_s": 819e9,
    "hbm_bytes": 16e9,
    "source": "Google Cloud documentation, 'TPU v5e' (system architecture)",
}

PEAKS = {"TPU v5 lite": _V5E, "TPU v5e": _V5E}


def peaks_for(device_kind):
    """The peaks of ``device_kind``; raises ``KeyError`` for an unknown kind."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
