"""The chips a run may use, their published peaks, and their memory."""

from __future__ import annotations

import json
import pathlib

__all__ = ["NoChip", "load_peaks", "pick_devices", "memory_peak_bytes"]


class NoChip(RuntimeError):
    """JAX found no accelerator the benchmark can measure."""


def load_peaks(bench_dir: pathlib.Path) -> dict:
    with open(bench_dir / "peaks.json") as f:
        return json.load(f)["devices"]


def pick_devices(chips: int, peaks: dict):
    """The first ``chips`` TPU devices, or :class:`NoChip`."""
    import jax

    devs = jax.devices()
    if not devs or devs[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform "
                     f"{devs[0].platform if devs else None!r})")
    kind = devs[0].device_kind
    if kind not in peaks:
        raise NoChip(f"device kind {kind!r} has no entry in peaks.json")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs[:chips]


def memory_peak_bytes(devices) -> int | None:
    """Peak bytes in use on the fullest of ``devices``, where reported."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None
