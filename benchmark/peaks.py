"""The table of peaks, keyed by ``device_kind``. A device that is not in
the table is an error, never a default."""

from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def load(device_kind: str) -> dict:
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind.startswith("_") or device_kind not in table:
        raise KeyError(
            f"no peaks for device_kind {device_kind!r} in "
            f"benchmark/peaks.json (it holds "
            f"{sorted(k for k in table if not k.startswith('_'))}): "
            "add its published peaks with their source")
    return table[device_kind]


def least_seconds(flops: float, nbytes: float, peak: dict):
    """The least time the chip could take, and which bound sets it."""
    by_flops = flops / peak["bf16_flops_per_s"]
    by_bytes = nbytes / peak["hbm_bytes_per_s"]
    return (by_flops, "flops") if by_flops >= by_bytes else (by_bytes, "bytes")
