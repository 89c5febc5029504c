"""The chips' published peaks, keyed by JAX's ``device_kind`` (``peaks.json``)."""

from __future__ import annotations

import json
from pathlib import Path

_TABLE = Path(__file__).resolve().with_name("peaks.json")


def lookup(device_kind: str) -> dict:
    """Peaks of one chip; a device that is not in the table is an error."""
    table = json.loads(_TABLE.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {_TABLE.name}")
    return table[device_kind]
