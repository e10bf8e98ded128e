"""The chip's peaks, from one table (``peaks.json``) keyed by the
``device_kind`` JAX reports.  A device that is not in it is an error."""
from __future__ import annotations

import json
from pathlib import Path

TABLE = Path(__file__).with_name("peaks.json")


def peaks(device_kind: str) -> dict:
    table = json.loads(TABLE.read_text())
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(f"no peaks for device kind {device_kind!r} in {TABLE.name}")
    return table[device_kind]
