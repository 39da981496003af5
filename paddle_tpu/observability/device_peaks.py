"""Published peak rates of the accelerators this repository has run on,
keyed by the `device_kind` string jax reports on that machine.

One table: every utilization the repository prints divides by a number
from here, and a device that is not in it is an error, not a default.
"""

from typing import Dict, Optional

__all__ = ["DEVICE_PEAKS", "device_peaks", "device_report"]

DEVICE_PEAKS: Dict[str, Dict[str, float]] = {
    # One TPU v5e chip; jax.devices()[0].device_kind read on the chip
    # (2026-09-26). Source: Google Cloud documentation, "TPU v5e".
    "TPU v5 lite": {
        "bf16_flops": 197e12,        # FLOP/s
        "int8_ops": 393e12,          # OP/s
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
    },
}


def device_peaks(device_kind: Optional[str] = None) -> Dict[str, float]:
    """Peaks of `device_kind` (default: jax.devices()[0].device_kind,
    which initializes the backend). LookupError names the device when
    the table does not hold it."""
    if device_kind is None:
        import jax
        device_kind = jax.devices()[0].device_kind
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise LookupError(
            f"no published peak rates for device_kind {device_kind!r}: "
            "utilization is only reported for devices in "
            f"paddle_tpu.observability.device_peaks.DEVICE_PEAKS "
            f"({sorted(DEVICE_PEAKS)})") from None


def device_report() -> Dict[str, object]:
    """The device a result was produced on, as every line the benches
    and chip_smoke.py print names it (initializes the backend)."""
    import jax
    devices = jax.devices()
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}
