"""The chip a run measures: found or refused, never faked by the CPU."""

from __future__ import annotations

import json
from pathlib import Path
from typing import Any, Dict

PEAKS_FILE = Path(__file__).resolve().with_name("peaks.json")


class NoChip(Exception):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def require_chips(n: int):
    """The first ``n`` TPU devices; raises :class:`NoChip` otherwise."""
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise NoChip(f"JAX found no device: {e}") from e
    if devices[0].platform != "tpu":
        raise NoChip(f"JAX found platform {devices[0].platform!r} "
                     f"({devices[0].device_kind}), not a TPU")
    if len(devices) < n:
        raise NoChip(f"the cell needs {n} chips, JAX found {len(devices)}")
    return devices[:n]


def peaks(device_kind: str) -> Dict[str, Any]:
    """Published peaks of one chip; a kind missing from the table is an
    error, not a default."""
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS_FILE.name}; have {sorted(table)}")
    return table[device_kind]


def describe(devices) -> Dict[str, Any]:
    """The result line's ``device`` entry, with the fullest chip's peak."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}
