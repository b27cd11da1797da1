"""The table of peaks, keyed by ``device_kind``. A device that is not in it
is an error, never a default."""

from __future__ import annotations

import json
import os

_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peak(device_kind: str) -> dict:
    with open(_PATH) as f:
        table = json.load(f)
    if device_kind not in table or device_kind == "source":
        raise KeyError(f"no published peak for device kind {device_kind!r} "
                       f"in {_PATH}; add it with its source")
    return table[device_kind]
