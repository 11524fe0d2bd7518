"""The device a run measures, and the table of its peaks.

A run needs a TPU whose ``device_kind`` is in ``bench/peaks.json`` and at
least the chips its cell asks for.  Anything else is an error, never a
fall-back: a number taken on another device is not a device number.
"""
from __future__ import annotations

import json
import os
from typing import Dict, List

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")


class DeviceError(RuntimeError):
    """The run is on a device the benchmark cannot measure."""


def peaks(kind: str) -> Dict:
    with open(PEAKS_FILE) as f:
        table = json.load(f)
    if kind not in table:
        raise DeviceError(f"no peaks for device kind {kind!r} in "
                          f"{PEAKS_FILE}; known: {sorted(table)}")
    return table[kind]


def require(devices: List, chips: int) -> Dict:
    """Check ``devices`` (``jax.devices()``) and return the peaks row."""
    if not devices or devices[0].platform != "tpu":
        found = devices[0].platform if devices else "nothing"
        raise DeviceError(f"needs a TPU; JAX found {found}")
    if len(devices) < chips:
        raise DeviceError(f"the cell needs {chips} chips; JAX found "
                          f"{len(devices)}")
    return peaks(devices[0].device_kind)


def describe(devices: List) -> Dict:
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}
