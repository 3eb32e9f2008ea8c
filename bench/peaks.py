"""The table of published peaks, keyed by JAX's ``device_kind``."""
from __future__ import annotations

import json
import os

PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                    "peaks.json")


def peak_for(device_kind: str, path: str = PATH) -> dict:
    """The peaks of one device kind; an unknown kind is an error."""
    with open(path) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r} in {path}; known: {sorted(table)}")
    return table[device_kind]
