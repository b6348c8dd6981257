"""The chips a run holds, and their published peaks."""
from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"


class NoChip(SystemExit):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def peaks(device_kind: str) -> dict:
    """The peaks of one chip of this kind; an unknown kind is an
    error, never a default."""
    with open(PEAKS) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; bench/peaks.json has "
                       f"{sorted(table)}")
    return table[device_kind]


def require_tpu(chips: int):
    """The first ``chips`` TPU devices, or exit nonzero naming what JAX
    found instead."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"bench: JAX found no TPU (platform "
                     f"{devices[0].platform!r}, device "
                     f"{devices[0].device_kind!r}); the benchmark runs "
                     f"only on a TPU")
    if len(devices) < chips:
        raise NoChip(f"bench: the cell needs {chips} TPU chips, JAX "
                     f"found {len(devices)}")
    return devices[:chips]


def describe(devices) -> dict:
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}


def memory_peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest chip, as the backend reports."""
    peaks_ = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
              for d in devices]
    return int(max(peaks_))
