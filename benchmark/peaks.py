"""The table of peaks, keyed by JAX's exact ``device_kind``."""

from __future__ import annotations

import json
import os

_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peaks_for(device_kind: str) -> dict:
    """``{"flops_per_s", "bytes_per_s", "memory_bytes"}`` of one chip.

    A kind the table lacks is an error, never a default: a share of an
    assumed peak is not a measurement."""
    with open(_PATH) as fh:
        table = json.load(fh)
    if device_kind.startswith("_") or device_kind not in table:
        raise KeyError(
            f"no peaks for device_kind {device_kind!r} in {_PATH}; add a "
            f"row with its source")
    return table[device_kind]
