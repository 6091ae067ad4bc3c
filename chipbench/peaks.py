"""The chip's published peaks, keyed by JAX's ``device_kind``."""

from __future__ import annotations

import json
import os

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")


class UnknownDevice(KeyError):
    pass


def peak_for(device_kind: str, path: str = PEAKS_FILE) -> dict:
    """The peak entry of ``device_kind``; an unknown device is an error."""
    with open(path) as f:
        table = json.load(f)
    if device_kind not in table:
        raise UnknownDevice(
            f"no peaks for device_kind {device_kind!r} in {path}; "
            f"known: {sorted(table)}")
    return table[device_kind]
