"""Which device the run is on, and its published peaks.

A run measures a TPU or nothing: any other platform, fewer chips than the
cell asks for, or a ``device_kind`` missing from bench/peaks.json is an
error, never a fallback.
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent.parent / "peaks.json"


class DeviceError(RuntimeError):
    pass


def peaks_for(kind: str, path: Path = PEAKS) -> dict:
    table = json.loads(path.read_text())["devices"]
    if kind not in table:
        raise DeviceError(f"device_kind {kind!r} is not in {path.name}; "
                          f"known: {sorted(table)}")
    return table[kind]


def check(devices, chips: int, path: Path = PEAKS) -> tuple[dict, dict]:
    """(device record, peaks) for a run on ``chips`` of ``devices``."""
    if not devices or devices[0].platform != "tpu":
        plat = devices[0].platform if devices else "none"
        raise DeviceError(f"no TPU: JAX found platform {plat!r}")
    if len(devices) < chips:
        raise DeviceError(f"the cell asks for {chips} chips; JAX found "
                          f"{len(devices)}")
    kind = devices[0].device_kind
    return ({"platform": "tpu", "kind": kind, "count": len(devices)},
            peaks_for(kind, path))


def memory_peak(devices) -> int:
    """Peak bytes in use on the fullest device (0 where none is reported)."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks, default=0))
