"""The chip's published peaks, keyed by ``device_kind`` (``peaks.json``).
A kind that is not in the table is an error, never a default."""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict

TABLE = Path(__file__).with_name("peaks.json")


def peaks(device_kind: str) -> Dict[str, float]:
    devices = json.loads(TABLE.read_text())["devices"]
    if device_kind not in devices:
        raise KeyError(f"no published peaks for device kind {device_kind!r} "
                       f"in {TABLE.name} (known: {sorted(devices)})")
    return devices[device_kind]
