"""The chip the run is on, its published peaks, and its memory peak."""

from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parents[1] / "peaks.json"


class NoChip(Exception):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def require_tpu(chips: int) -> dict:
    """``{"platform", "kind", "count"}`` of the chips JAX found; raises
    :class:`NoChip` unless they are TPUs and at least ``chips`` of them."""
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise NoChip(f"JAX found no devices: {e}") from None
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX runs on {devices[0].platform!r}")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX has "
                     f"{len(devices)}")
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}


def peaks(kind: str) -> dict:
    """Published per-chip peaks of ``kind`` (bench/peaks.json); an
    unknown device is an error."""
    table = json.loads(PEAKS.read_text())["devices"]
    if kind not in table:
        raise NoChip(f"no peaks for device kind {kind!r} in {PEAKS.name}; "
                     f"have {sorted(table)}")
    return table[kind]


def memory_peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest of ``devices``."""
    return max(int(d.memory_stats()["peak_bytes_in_use"]) for d in devices)
