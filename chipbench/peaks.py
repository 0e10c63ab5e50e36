"""Published peaks of each chip, keyed by ``device_kind``. A kind that is
not in ``peaks.json`` is an error, never a default."""
from __future__ import annotations

import json
from pathlib import Path

_FILE = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    with open(_FILE) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r} in {_FILE.name}; have {sorted(table)}")
    return table[device_kind]
