"""The device gate and the table of peaks.  The benchmark times the chip:
no accelerator, another platform, fewer chips than the cell asks for, or a
kind this table lacks is an exit, never a fallback."""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    flops: float      # dense bf16 FLOP/s of one chip
    hbm_bytes_s: float
    hbm_bytes: int
    source: str


# Keyed by a substring of jax.devices()[0].device_kind, lower-case, spaces
# removed.  Copied from can_tpu/cli/common.py::_PEAK_BY_DEVICE_KIND (v5e row)
# so that no program PR can move the yardstick.
PEAKS = {
    "v5lite": Peaks(197e12, 819e9, 16 << 30,
                    "Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
                    "16 GB HBM2e at 819 GB/s per chip"),
}


class DeviceError(RuntimeError):
    pass


def peaks_for_kind(kind: str) -> Peaks:
    k = kind.lower().replace(" ", "")
    for sub, peaks in PEAKS.items():
        if sub in k:
            return peaks
    raise DeviceError(f"device kind {kind!r} is not in benchmark/harness/"
                      f"device.py::PEAKS; add its row, with its source, "
                      f"before measuring on it")


def require_chips(chips: int):
    """(devices, peaks) or DeviceError."""
    import jax

    devices = jax.devices()
    d = devices[0]
    if d.platform != "tpu":
        raise DeviceError(f"first device is {d.platform!r}, not a TPU: the "
                          f"benchmark does not fall back")
    if len(devices) < chips:
        raise DeviceError(f"cell needs {chips} chip(s), JAX found {len(devices)}")
    return devices, peaks_for_kind(d.device_kind)


def device_report(devices) -> dict:
    """The result line's ``device`` key.  ``peak_bytes_in_use`` leaves out a
    program's scratch on this client (PERF.md, PR 21); the scratch is what
    ``peak_bytes_reserved`` holds, so the peak is their sum."""
    peak = 0
    for d in devices:
        s = d.memory_stats() or {}
        peak = max(peak, int(s.get("peak_bytes_in_use", 0))
                   + int(s.get("peak_bytes_reserved", 0)))
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices), "memory_peak_bytes": peak}
