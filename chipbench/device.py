"""The chip the benchmark runs on: its check, its name and its peaks."""

from __future__ import annotations

import jax

#: Per-chip peaks, keyed by ``jax.devices()[0].device_kind``.
PEAKS = {
    "TPU v5 lite": {
        "vpu_f32_flops_per_s": 8 * 128 * 4 * 1.5e9,
        "mxu_bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud 'TPU v5e' documentation: 197 TFLOP/s bf16, "
                  "16 GB HBM at 819 GB/s. The VPU f32 peak is derived, not "
                  "from a datasheet: 8 sublanes x 128 lanes x 4 VALU slots "
                  "x 1.5 GHz, the clock being 197e12 / (4 MXU x 128^2 x 2); "
                  "the count of 4 VALU slots is an assumption.",
    },
}


class NoChip(RuntimeError):
    """No TPU, or fewer chips than the cell asks for."""


def require(chips: int) -> list:
    """The first ``chips`` TPU devices, or raise :class:`NoChip`."""
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devices[0].platform!r} devices")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found "
                     f"{len(devices)}")
    peaks(devices[0])
    return devices[:chips]


def peaks(device) -> dict:
    """The peak table's row for ``device``; an unknown kind is an error."""
    if device.device_kind not in PEAKS:
        raise KeyError(f"no peaks for device_kind {device.device_kind!r}; "
                       "add a row to PEAKS with its source")
    return PEAKS[device.device_kind]


def describe(devices: list) -> dict:
    """Platform, kind and count as JAX reports them."""
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": jax.device_count()}


def memory_peak_bytes(devices: list):
    """Peak bytes in use on the fullest of ``devices``, where reported."""
    readings = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                for d in devices]
    readings = [r for r in readings if r is not None]
    return max(readings) if readings else None
