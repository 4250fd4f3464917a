"""The accelerator a run measures: its check, its peaks, its memory."""

from __future__ import annotations

import json
import pathlib
from typing import Any, Dict

PEAKS_FILE = pathlib.Path(__file__).with_name("peaks.json")


class NoAccelerator(RuntimeError):
    """JAX sees no TPU, or fewer chips than the cell asks for."""


def require_tpu(chips: int):
    """The first ``chips`` TPU devices; raises :class:`NoAccelerator`
    when JAX found another platform or too few chips.  A measurement
    never falls back to the CPU."""
    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        raise NoAccelerator(f"no TPU: JAX found {platform} only")
    if len(devices) < chips:
        raise NoAccelerator(f"the cell needs {chips} chips, JAX found "
                            f"{len(devices)}")
    return devices[:chips]


def peaks(device_kind: str) -> Dict[str, Any]:
    """Published peaks of one chip of ``device_kind``.  A kind missing
    from the table is an error, not a default."""
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS_FILE.name}; known: {sorted(table)}")
    return table[device_kind]


def memory_peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest of ``devices`` (0 where the
    backend keeps no count)."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def describe(devices) -> Dict[str, Any]:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}
