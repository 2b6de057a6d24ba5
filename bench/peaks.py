"""The chip's published peaks (``peaks.json``) and every share of them.

A device kind missing from the table is an error, never a default.  No
other file of the benchmark writes a peak down.
"""
from __future__ import annotations

import json
import os

_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "peaks.json")


def peaks(device_kind: str) -> dict:
    with open(_PATH) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r} in {_PATH}")
    return table[device_kind]


def hbm_roofline_pct(bytes_moved: float, seconds: float,
                     device_kind: str) -> float:
    """Share (%) of the HBM roofline: the least time ``bytes_moved`` can
    take at peak bandwidth, over the time it took."""
    least = bytes_moved / peaks(device_kind)["hbm_bytes_per_s"]
    return 100.0 * least / seconds


def flops_pct(flops: float, seconds: float, device_kind: str) -> float:
    """Share (%) of the bf16 compute peak over ``seconds``."""
    return 100.0 * flops / (seconds * peaks(device_kind)["bf16_flops_per_s"])
