"""The table of peaks, keyed by ``device_kind``. A device that is not in the
table is an error, not a default."""

from __future__ import annotations

import json
import os

_PATH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "peaks.json")


def peak_for(device_kind: str) -> dict:
    with open(_PATH) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in "
                       f"bench/peaks.json ({sorted(table)})")
    return table[device_kind]


def least_seconds(need: dict, peak: dict) -> float:
    """The least time the chip could take: the larger of operations over
    peak FLOP/s and bytes over peak bytes/s."""
    return max(need["flops"] / peak["flops_per_s"],
               need["bytes"] / peak["bytes_per_s"])


def bound_by(need: dict, peak: dict) -> str:
    return ("flops" if need["flops"] / peak["flops_per_s"]
            >= need["bytes"] / peak["bytes_per_s"] else "bytes")
