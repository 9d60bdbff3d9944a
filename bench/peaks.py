"""Published peaks of a device, by the ``device_kind`` JAX reports."""
from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def lookup(device_kind: str) -> dict:
    """The peaks of ``device_kind``; a device not in the table is an error,
    never a default."""
    table = json.loads(PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name}; have {sorted(table)}")
    return table[device_kind]
