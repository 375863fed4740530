"""Published peaks of each accelerator, keyed by JAX's ``device_kind``."""
from __future__ import annotations

import json
import os

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")


class UnknownDevice(LookupError):
    pass


def lookup(device_kind: str, path: str = PEAKS_FILE) -> dict:
    """The peaks of ``device_kind``.  A device that is not in the table is
    an error, never a default."""
    with open(path) as f:
        table = json.load(f)
    if device_kind not in table:
        raise UnknownDevice(
            f"no peaks for device kind {device_kind!r}; the table has "
            f"{sorted(table)}")
    return table[device_kind]
