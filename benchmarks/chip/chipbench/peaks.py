"""Published peaks of each chip, keyed by JAX's ``device_kind``."""
from __future__ import annotations

import json
from pathlib import Path

TABLE = Path(__file__).with_name("peaks.json")


def peak_for(device_kind: str) -> dict:
    """The row for ``device_kind``; a chip not in the table is an
    error, never a default."""
    table = json.loads(TABLE.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device_kind {device_kind!r} in "
                       f"{TABLE.name}; known: {sorted(table)}")
    return table[device_kind]
