"""Published peak rates of the chips this benchmark may run on, keyed by the
`device_kind` string jax reports there. The yardstick keeps its own table: a
PR that edits the program's copy (paddle_tpu/observability/device_peaks.py,
from which the v5e row was taken in PR 23) cannot move a utilization here.
A device that is not in the table is an error, not a default."""

PEAKS = {
    # One TPU v5e chip. Source: Google Cloud documentation, "TPU v5e"
    # (197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of HBM at 819 GB/s).
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
    },
}


class NoChip(RuntimeError):
    """The machine does not hold the chips a cell asks for."""


def require_chips(chips):
    """The device as every result line names it, and its peaks. Raises
    NoChip on a CPU, on a device the table does not hold, and when fewer
    chips are present than the cell needs."""
    import jax

    devices = jax.devices()
    first = devices[0]
    if first.platform != "tpu":
        raise NoChip(f"jax's platform is {first.platform!r}, not 'tpu'")
    if first.device_kind not in PEAKS:
        raise NoChip(f"no published peaks for {first.device_kind!r} "
                     f"(table holds {sorted(PEAKS)})")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, jax sees {len(devices)}")
    report = {"platform": first.platform, "kind": first.device_kind,
              "count": len(devices)}
    return report, PEAKS[first.device_kind]
